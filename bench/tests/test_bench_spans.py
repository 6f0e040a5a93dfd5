"""The readings of the program's own spans (``bench/spans.py``), and the
idle gaps named after them, on hand-made events and on a trace recorded
on one TPU v5e chip."""
from pathlib import Path

import pytest

from bench import spans, trace
from bench.spans import HostSpan, ProgramSpans

MS = 1_000_000
SCI, OWNER0, OWNER1 = ("main", 0), ("main", 1), ("main", 2)
DEVICE = "/device:TPU:0"
READERS = ["session_step_ms.mlp", "cut_wait_ms_per_step.mlp",
           "owner_step_ms.mlp", "frame_us_per_step.mlp",
           "host_read_ms_per_step.mlp"]


def _span(name, start, end, thread=SCI, **stats):
    party = {SCI: "scientist", OWNER0: "owner0", OWNER1: "owner1"}[thread]
    return HostSpan(name, start * MS, end * MS, thread,
                    {"party": party, **stats})


def _two_steps():
    """A 20 ms window of two scientist steps (8 and 9 ms) and two owner
    threads, with one step that ends outside the window."""
    return [
        _span("vfl.fit_start", 0, 1),
        _span("vfl.step", 1, 9, step=0),
        _span("vfl.cut_exchange", 2, 5, peer="owner0", step=0),
        _span("vfl.wire.unpack", 4, 5, kind="cut_activations", seq=0),
        _span("vfl.bookkeeping", 7, 9, step=0),
        _span("vfl.host_read", 7.5, 8, bytes=4),
        _span("vfl.step", 10, 19, step=1),
        _span("vfl.cut_exchange", 11, 13, peer="owner0", step=1),
        _span("vfl.wire.unpack", 12.5, 13, kind="cut_activations", seq=1),
        _span("vfl.bookkeeping", 17, 19, step=1),
        _span("vfl.host_read", 17.5, 18.5, bytes=4),
        _span("vfl.step", 19.5, 21, step=2),               # not inside
        _span("vfl.wire.unpack", 2.5, 2.7, OWNER0, kind="head_fwd", seq=0),
        _span("vfl.owner.cut_grad", 3, 6, OWNER0, seq=0),
        _span("vfl.wire.pack", 5, 6, OWNER0, kind="cut_activations", seq=1),
        _span("vfl.host_read", 5.2, 5.7, OWNER0, bytes=32768),
        _span("vfl.owner.cut_grad", 13, 17, OWNER0, seq=1),
        _span("vfl.owner.cut_grad", 12, 14, OWNER1, seq=1),
    ]


def _program(program_spans, window=(0, 20)):
    return ProgramSpans(program_spans, (window[0] * MS, window[1] * MS))


def _trace(ops, bench):
    return trace.Trace(
        {DEVICE: [("jit_cutgrad(1)", s * MS, e * MS) for s, e in ops]},
        {DEVICE: [("fusion", s * MS, e * MS) for s, e in ops]},
        [(n, s * MS, e * MS) for n, s, e in bench])


def _read(metric, ps):
    return spans.READINGS[metric](ps)


@pytest.mark.parametrize("metric, expected", [
    # the median of the two steps inside the window, 8 and 9 ms
    ("session_step_ms.mlp", 8.5),
    # cut exchanges 3 + 2 ms less their unpacks 1 + 0.5 ms, over 2 steps
    ("cut_wait_ms_per_step.mlp", 1.75),
    # the median of 3, 4 and 2 ms, over both owners
    ("owner_step_ms.mlp", 3.0),
    # pack 1 ms less its 0.5 ms read, plus unpacks 1 + 0.5 + 0.2 ms
    ("frame_us_per_step.mlp", 1100.0),
    # reads of 0.5 + 1 + 0.5 ms over 2 steps
    ("host_read_ms_per_step.mlp", 1.0),
])
def test_span_reader_on_hand_made_events(metric, expected):
    assert _read(metric, _program(_two_steps())) == pytest.approx(expected)


@pytest.mark.parametrize("metric", READERS)
def test_span_reader_reads_nothing_without_its_spans(metric, monkeypatch):
    """No program spans (or only others), and a program from before its
    spans: None, never 0."""
    assert _read(metric, _program([])) is None
    assert _read(metric, _program([_span("vfl.fit_start", 0, 1)])) is None
    monkeypatch.setattr(spans, "program_span_names", lambda: None)
    assert _read(metric, _program(_two_steps())) is None


def test_self_time_subtracts_only_children_of_the_same_thread():
    ps = _program(_two_steps() + [
        # an owner's unpack that overlaps the scientist's wait
        _span("vfl.wire.unpack", 3, 4, OWNER1, kind="head_fwd", seq=1)])
    assert ps.self_seconds("vfl.cut_exchange", less=("vfl.wire.unpack",)) \
        == pytest.approx(0.0035)


def test_gaps_are_named_after_the_innermost_scientist_span():
    """Device idle 1-1.5, 3.5-4.5 and 9.5-10 ms: the longest lies in
    the scientist's cut exchange, though an owner's shorter span holds
    it too; the next in the step; the last in an owner's span and in
    ``bench.fit`` alone, so it reads ``bench.fit``."""
    ps = _program(
        [_span("vfl.step", 1, 9, step=0),
         _span("vfl.cut_exchange", 2, 6, peer="owner0", step=0),
         _span("vfl.owner.cut_grad", 3, 5, OWNER0, seq=0),
         _span("vfl.owner.cut_grad", 9, 10, OWNER1, seq=0)],
        window=(0, 10))
    tr = _trace(ops=((0, 1), (1.5, 3.5), (4.5, 9.5)),
                bench=(("bench.window", 0, 10), ("bench.fit", 0, 9.9)))
    assert tr.idle_gaps(10)[0][0] == "bench.fit"
    assert ps.idle_gaps(tr, 10) == [
        ["vfl.cut_exchange", pytest.approx(0.001)],
        ["vfl.step", pytest.approx(0.0005)],
        ["bench.fit", pytest.approx(0.0005)]]


# ---------------------------------------------------------- a chip trace

RECORDED = Path(__file__).parent / "data" / "split_spans.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    """Six steps of the MNIST cell's split training on one TPU v5e chip,
    recorded by ``record_split_spans.py``."""
    return ProgramSpans.from_file(str(RECORDED))


@pytest.mark.parametrize("metric", READERS)
def test_span_reader_reads_the_recorded_chip_trace(recorded, metric):
    value = _read(metric, recorded)
    assert value is not None and value > 0


def test_recorded_steps_cover_the_stretch_they_span(recorded):
    steps = recorded.named("vfl.step")
    assert [sp.stats["step"] for sp in steps] == list(range(len(steps)))
    stretch = steps[-1].end - steps[0].start
    assert sum(sp.end - sp.start for sp in steps) >= 0.95 * stretch


def test_the_recording_drops_only_the_planes_it_names():
    from jax.profiler import ProfileData

    from bench.tests.record_split_spans import drop_planes
    raw = RECORDED.read_bytes()
    assert drop_planes(raw, names=()) == raw
    fewer = drop_planes(raw, names=("/device:TPU:0",))
    names = [p.name for p in ProfileData.from_serialized_xspace(raw).planes]
    kept = [p.name for p in ProfileData.from_serialized_xspace(fewer).planes]
    assert "/host:metadata" not in names
    assert kept == [n for n in names if n != "/device:TPU:0"]


def test_scientist_spans_per_step_and_step_coverage_on_hand_made_events():
    ps = _program(_two_steps())
    per_step = spans.scientist_ms_per_step(ps)
    # two exchanges of 3 and 2 ms, two bookkeepings of 2 ms, over 2 steps
    assert per_step["vfl.cut_exchange"] == pytest.approx(2.5)
    assert per_step["vfl.bookkeeping"] == pytest.approx(2.0)
    assert "vfl.step" not in per_step and "vfl.owner.cut_grad" not in per_step
    # steps 1-9 and 10-19 of the 1-19 ms after ``vfl.fit_start``; the
    # median step (10-19 ms) holds 2 + 2 ms of children
    coverage, children = spans.step_coverage(ps)
    assert coverage == pytest.approx(17 / 18)
    assert children == pytest.approx(4 / 9)


def test_report_of_the_recorded_chip_trace():
    """Every reading, the steps' coverage, and idle gaps named after the
    program's spans, never the benchmark's ``bench.fit``."""
    out = spans.report(str(RECORDED))
    assert out["steps"] == 6
    assert all(v is not None and v > 0 for v in out["readings"].values())
    assert out["step_coverage"] >= 0.95
    assert out["median_step_children"] >= 0.9
    assert out["host_reads_per_step"] >= 6
    assert [name[:4] for name, _ in out["idle_gaps"]] == ["vfl."] * 10
