"""The reduction of a profiler trace to per-layer numbers, on a small
trace recorded on one TPU v5e chip (``data/tiny_trace.xplane.pb``: three
rounds of two jitted programs, ``head_fwd`` and ``trunk_step``, inside a
``bench.window`` span) and on hand-made events."""
from pathlib import Path

import pytest

from bench import harness, trace

DATA = Path(__file__).parent / "data" / "tiny_trace.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return trace.Trace.from_file(str(DATA))


def test_recorded_trace_names_programs_and_bounds_busy(recorded):
    assert recorded.module_names() == {"head_fwd", "trunk_step"}
    assert recorded.n_devices == 1
    assert 0.0 < recorded.busy_s() <= recorded.window_s
    head = recorded.module_seconds(["head_fwd"])
    both = recorded.module_seconds(["head_fwd", "trunk_step"])
    assert 0.0 < head < both <= recorded.window_s
    assert recorded.module_calls(["head_fwd"]) >= 2


def test_recorded_trace_breakdown_shape(recorded):
    ops = recorded.top_ops(10)
    assert 0 < len(ops) <= 10
    assert all(sec > 0 for _, sec in ops)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    gaps = recorded.idle_gaps(10)
    assert 0 < len(gaps) <= 10
    assert all(name.startswith("bench.") for name, _ in gaps)
    assert sum(s for _, s in gaps) <= recorded.window_s


def _renamed(recorded, old, new):
    return trace.Trace(
        {d: [(n.replace(old, new), s, e) for n, s, e in ev]
         for d, ev in recorded.modules.items()},
        recorded.ops, recorded.spans, window=(recorded.lo, recorded.hi))


@pytest.mark.parametrize("names", [["head_fwd"], ["head_fwd", "trunk_step"]])
def test_a_renamed_program_fails_loudly_not_zero(recorded, names):
    """A metric over several programs, one of them renamed, raises; it
    does not read the others' time alone."""
    renamed = _renamed(recorded, "head_fwd", "owner_forward")
    with pytest.raises(trace.MissingModule, match="head_fwd"):
        renamed.module_seconds(names)
    assert renamed.module_seconds(["owner_forward"]) == pytest.approx(
        recorded.module_seconds(["head_fwd"]))


def test_peaks_are_known_for_v5e_and_unknown_kinds_raise():
    p = harness.load_peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.load_peaks("TPU v9 imaginary")


def test_busy_is_the_union_and_gaps_carry_host_spans():
    ms = 1_000_000
    ops = {"/device:TPU:0": [("a", 0, 4 * ms), ("b", 2 * ms, 6 * ms),
                             ("c", 8 * ms, 9 * ms)]}
    mods = {"/device:TPU:0": [("jit_step(7)", 0, 6 * ms),
                              ("jit_step(7)", 8 * ms, 9 * ms)]}
    spans = [("bench.window", 0, 10 * ms), ("bench.fit", 0, 7 * ms),
             ("bench.feed", 6 * ms, 8 * ms)]
    tr = trace.Trace(mods, ops, spans)
    assert tr.window_s == pytest.approx(0.010)
    assert tr.busy_s() == pytest.approx(0.007)
    assert tr.module_seconds(["step"]) == pytest.approx(0.007)
    assert tr.module_calls(["step"]) == 2
    assert tr.idle_gaps(10) == [["bench.feed", pytest.approx(0.002)],
                                ["bench.window", pytest.approx(0.001)]]
    # a (0-4 ms) has b starting inside it, so only b and c are innermost
    assert tr.top_ops(3) == [["step/b", pytest.approx(0.004)],
                             ["step/c", pytest.approx(0.001)]]


def test_top_ops_are_the_innermost_under_their_program():
    ms = 1_000_000
    ops = {"/device:TPU:0": [
        ("%while.3 = (s32[]) while(...)", 0, 10 * ms),
        ("%fusion.1 = f32[8]{0} fusion(...)", 1 * ms, 7 * ms),
        ("%fusion.2 = f32[8]{0} fusion(...)", 7 * ms, 9 * ms)]}
    mods = {"/device:TPU:0": [("jit_trunk(2)", 0, 10 * ms)]}
    tr = trace.Trace(mods, ops, [("bench.window", 0, 10 * ms)])
    assert tr.top_ops(3) == [["trunk/fusion.1", pytest.approx(0.006)],
                             ["trunk/fusion.2", pytest.approx(0.002)]]


def test_events_outside_the_window_are_clipped():
    ms = 1_000_000
    tr = trace.Trace({"/device:TPU:0": [("jit_f(1)", 0, 5 * ms)]},
                     {"/device:TPU:0": [("x", 0, 5 * ms)]},
                     [("bench.window", 2 * ms, 4 * ms)])
    assert tr.busy_s() == pytest.approx(0.002)
    assert tr.module_seconds(["f"]) == pytest.approx(0.002)
