"""A run with the timed path broken underneath must come out not
correct: a step that leaves the state unchanged, half of the batch left
out (its mean taken over the rest), and an input altered where the
owner produces it."""
import numpy as np
import pytest

from bench import harness
from bench.tests.rehearse import run_cell, steer

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]
         if harness.load_json("traffic", w["traffic"])["kind"] == "train"]


def _state_unchanged(monkeypatch):
    from repro.federation import registry
    monkeypatch.setattr(registry, "apply_updates", lambda p, u: p)


def _half_batch(monkeypatch):
    from repro.federation.session import VerticalSession
    stream = VerticalSession._index_stream

    def halved(self, *a, **kw):
        for idx in stream(self, *a, **kw):
            half = idx[:len(idx) // 2]
            yield np.concatenate([half, half])
    monkeypatch.setattr(VerticalSession, "_index_stream", halved)


def _altered(monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.federation import registry
    def alter(rows):
        if jnp.issubdtype(rows.dtype, jnp.integer):
            mid = rows.shape[1] // 2
            return rows.at[:, mid].set(rows[:, mid] ^ 1)
        return rows.at[0].add(1.0)

    monkeypatch.setattr(
        registry._ProgramCache, "gather_program",
        lambda self: jax.jit(lambda feats, idx: alter(feats[idx])))


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "altered": _altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_a_broken_step_is_not_correct(workload, fault, monkeypatch, capsys):
    steer(monkeypatch)
    FAULTS[fault](monkeypatch)
    rc, line, err = run_cell(capsys, workload, seed=5)
    assert rc == 0, err[-2000:]
    assert line["correct"] is False, line["checks"]
