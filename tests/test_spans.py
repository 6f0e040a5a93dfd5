"""The host spans of split training (``repro.federation.spans``), read
back from a profiler trace of a tiny ``fit(mode="split",
backend="queue")`` on the CPU."""
import glob
import subprocess
import sys
from collections import defaultdict

import jax
import pytest

from repro.configs.pyvertical_mnist import CONFIG as MNIST_CFG
from repro.data import make_vertical_mnist_parties
from repro.federation import (VerticalSession, feature_parties, spans,
                               transport)

STEPS = 4
B = 32
#: a float32 cut or cut gradient of one batch
CUT_BYTES = B * MNIST_CFG.split.cut_dim * 4


@pytest.fixture(scope="module")
def session():
    sci, owners = make_vertical_mnist_parties(300, seed=0, keep_frac=0.9)
    session = VerticalSession(*feature_parties(sci, owners))
    session.resolve(group="modp512")
    session.build(MNIST_CFG)
    return session


def _trace_fit(session, out, **fit_kw):
    """``(events, history)`` of a traced split fit of ``STEPS`` steps at
    batch ``B``: every ``vfl.*`` host event as ``(name, start, end,
    thread, stats)``."""
    jax.profiler.start_trace(str(out))
    try:
        history = session.fit(steps=STEPS, batch_size=B, mode="split",
                              verbose=False, **fit_kw)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(out / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            events.extend(
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 (plane.name, i), dict(e.stats))
                for e in line.events if e.name.startswith("vfl."))
    return events, history


@pytest.fixture(scope="module")
def traced(session, tmp_path_factory):
    """``(events, owner names, scalars a step records)`` of a traced
    ``fit(mode="split", backend="queue")``."""
    events, history = _trace_fit(session, tmp_path_factory.mktemp("trace"),
                                 backend="queue")
    scalars = len(history["train"][0]) - 1          # all but "step"
    return events, [o.name for o in session.owners], scalars


def _named(events, name):
    return sorted((e for e in events if e[0] == name), key=lambda e: e[1])


def test_every_span_name_is_recorded_with_its_party(traced):
    events, owners, _ = traced
    assert {e[0] for e in events} == set(spans.SPANS)
    assert all(e[4]["party"] in owners + [spans.SCIENTIST] for e in events)
    for name in (spans.OWNER_FWD_REQUEST, spans.OWNER_CUT_GRAD,
                 spans.CUT_ENCODE):
        assert {e[4]["party"] for e in _named(events, name)} == set(owners)
    # the scientist waits for each owner's cut on its own thread
    exchange = _named(events, spans.CUT_EXCHANGE)
    assert {e[4]["party"] for e in exchange} == {spans.SCIENTIST}
    assert {e[4]["peer"] for e in exchange} == set(owners)


def test_step_spans_carry_the_steps_in_order(traced):
    events, owners, _ = traced
    steps = _named(events, spans.STEP)
    assert [e[4]["step"] for e in steps] == list(range(STEPS))
    assert len({e[3] for e in steps}) == 1
    # each owner handles one cut gradient per step (microbatches=1)
    for owner in owners:
        seqs = [e[4]["seq"] for e in _named(events, spans.OWNER_CUT_GRAD)
                if e[4]["party"] == owner]
        assert seqs == list(range(STEPS))


@pytest.mark.parametrize("name", [spans.CUT_EXCHANGE, spans.BOOKKEEPING,
                                  spans.TRUNK_CUTGRAD, spans.CUT_GRAD_SEND,
                                  spans.HOST_STAGE])
def test_step_children_nest_inside_their_step(traced, name):
    events, _, _ = traced
    steps = {e[4]["step"]: e for e in _named(events, spans.STEP)}
    children = _named(events, name)
    assert children
    for child in children:
        step = steps[child[4]["step"]]
        assert child[3] == step[3]                  # the same thread
        assert step[1] <= child[1] and child[2] <= step[2]


def _inside(events, name, r):
    """The ``name`` spans that hold event ``r`` on its thread."""
    return [p for p in _named(events, name)
            if p[3] == r[3] and p[1] <= r[1] and r[2] <= p[2]]


def _scientist_reads(events):
    """The scientist's reads inside its steps (the warm-up's are not)."""
    return [r for r in _named(events, spans.HOST_READ)
            if r[4]["party"] == spans.SCIENTIST
            and _inside(events, spans.STEP, r)]


def test_only_device_arrays_open_a_host_read(traced):
    """Packing the ``head_fwd`` indices (a host array) reads nothing
    from the device; each owner's framed cut (a device array) is one
    read, inside its pack, with its size.  The scientist reads once a
    step: its cut gradients and loss scalars in one fetch, before the
    packs, so neither its packs nor its bookkeeping hold a read."""
    events, owners, scalars = traced
    reads = defaultdict(list)
    for r in _named(events, spans.HOST_READ):
        for p in _inside(events, spans.WIRE_PACK, r):
            reads[p[4]["kind"]].append(r[4]["bytes"])
    assert "head_fwd" not in reads
    assert "cut_gradients" not in reads
    assert reads["cut_activations"] == [CUT_BYTES] * (STEPS * len(owners))
    mine = _scientist_reads(events)
    assert not [r for r in mine if _inside(events, spans.BOOKKEEPING, r)]
    assert [r[4]["bytes"] for r in mine] == \
        [len(owners) * CUT_BYTES + 4 * scalars] * STEPS
    assert all(_inside(events, spans.CUT_GRAD_SEND, r) for r in mine)


def test_one_host_stage_a_step_with_the_staged_bytes(traced):
    """The scientist puts each step's two cuts and its labels on the
    device in one call: float32 cuts, int32 labels as they land."""
    events, owners, _ = traced
    stages = _named(events, spans.HOST_STAGE)
    assert [e[4]["step"] for e in stages] == list(range(STEPS))
    assert {e[4]["party"] for e in stages} == {spans.SCIENTIST}
    assert [e[4]["bytes"] for e in stages] == \
        [len(owners) * CUT_BYTES + B * 4] * STEPS


def test_direct_backend_reads_only_the_metrics(session, tmp_path,
                                               monkeypatch):
    """By reference (``backend="direct"``), the cut gradients stay on
    the device: the owners receive device arrays, and the scientist's
    one read a step is its metrics', in the bookkeeping."""
    sent = []
    pair = transport.channel_pair

    def tapped(*a, **kw):
        return pair(*a, **kw, tap=lambda msg, blob: sent.append(msg))

    monkeypatch.setattr(transport, "channel_pair", tapped)
    events, history = _trace_fit(session, tmp_path, backend="direct")
    grads = [m.payload["x"] for m in sent if m.kind == "cut_gradients"]
    assert len(grads) == STEPS * len(session.owners)
    assert all(isinstance(g, jax.Array) for g in grads)
    scalars = len(history["train"][0]) - 1
    mine = _scientist_reads(events)
    assert [r[4]["bytes"] for r in mine] == [4 * scalars] * STEPS
    assert all(_inside(events, spans.BOOKKEEPING, r) for r in mine)


def test_microbatches_stage_and_fetch_once_a_chunk(session, tmp_path):
    """With ``microbatches=2`` each chunk of ``B / 2`` rows is one put
    and one fetch of its cut gradients and its metric parts."""
    events, history = _trace_fit(session, tmp_path, backend="queue",
                                 microbatches=2)
    P, scalars = len(session.owners), len(history["train"][0]) - 1
    stages = _named(events, spans.HOST_STAGE)
    assert [e[4]["step"] for e in stages] == \
        [t for t in range(STEPS) for _ in range(2)]
    assert {e[4]["bytes"] for e in stages} == {(P * CUT_BYTES + B * 4) // 2}
    assert [r[4]["bytes"] for r in _scientist_reads(events)] == \
        [P * CUT_BYTES // 2 + 4 * scalars] * (2 * STEPS)


def test_int8_fetch_carries_the_packed_frames(session, tmp_path):
    """With ``compression="int8"`` the codec runs on the device before
    the fetch: what crosses is each owner's packed ``(B, k+4)`` uint8
    frame, as framing sends it."""
    events, history = _trace_fit(session, tmp_path, backend="queue",
                                 compression="int8")
    P, scalars = len(session.owners), len(history["train"][0]) - 1
    frame = B * (MNIST_CFG.split.cut_dim + 4)
    assert [r[4]["bytes"] for r in _scientist_reads(events)] == \
        [P * frame + 4 * scalars] * STEPS


def test_without_jax_a_span_is_a_no_op():
    """The wire stack runs in jax-free processes: there ``span`` loads
    no jax and does nothing."""
    code = ("import sys; from repro.federation import spans; "
            "import repro.federation.transport; "
            "s = spans.span(spans.STEP, party='scientist', step=0); "
            "s.__enter__(); s.__exit__(None, None, None); "
            "print('jax' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True, timeout=120)
    assert out.stdout.strip() == "False"
