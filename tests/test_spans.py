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
from repro.federation import VerticalSession, feature_parties, spans

STEPS = 4


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """``(events, owner names, scalars a step records)``: every
    ``vfl.*`` host event of a traced fit as ``(name, start, end,
    thread, stats)``."""
    sci, owners = make_vertical_mnist_parties(300, seed=0, keep_frac=0.9)
    session = VerticalSession(*feature_parties(sci, owners))
    session.resolve(group="modp512")
    session.build(MNIST_CFG)
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        history = session.fit(steps=STEPS, batch_size=32, mode="split",
                              backend="queue", verbose=False)
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
                                  spans.TRUNK_CUTGRAD, spans.CUT_GRAD_SEND])
def test_step_children_nest_inside_their_step(traced, name):
    events, _, _ = traced
    steps = {e[4]["step"]: e for e in _named(events, spans.STEP)}
    children = _named(events, name)
    assert children
    for child in children:
        step = steps[child[4]["step"]]
        assert child[3] == step[3]                  # the same thread
        assert step[1] <= child[1] and child[2] <= step[2]


def test_only_device_arrays_open_a_host_read(traced):
    """Packing the ``head_fwd`` indices (a host array) reads nothing
    from the device; each framed cut and cut gradient (device arrays)
    is one read, inside its pack, with its size."""
    events, owners, scalars = traced
    reads = defaultdict(list)
    for r in _named(events, spans.HOST_READ):
        for p in _named(events, spans.WIRE_PACK):
            if p[3] == r[3] and p[1] <= r[1] and r[2] <= p[2]:
                reads[p[4]["kind"]].append(r[4]["bytes"])
    assert "head_fwd" not in reads
    cut_bytes = 32 * MNIST_CFG.split.cut_dim * 4         # float32 cuts
    for kind in ("cut_activations", "cut_gradients"):
        assert reads[kind] == [cut_bytes] * (STEPS * len(owners))
    # the loss scalars: one read each, inside the step's bookkeeping
    books = _named(events, spans.BOOKKEEPING)
    in_books = [r for r in _named(events, spans.HOST_READ)
                if any(b[3] == r[3] and b[1] <= r[1] and r[2] <= b[2]
                       for b in books)]
    assert len(in_books) == STEPS * scalars


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
