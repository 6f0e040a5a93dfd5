"""Serving engine: queueing, waves, determinism vs the raw decode path,
backpressure (QueueFull), and degraded service (per-request errors)."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.engine import QueueFull, ServingEngine
from repro.models.model import SplitModel


def _setup(batch_slots=2, ctx=32):
    cfg = get_config("llama3.2-3b", reduced=True)
    model = SplitModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, batch_slots=batch_slots,
                        ctx_len=ctx, max_new=6)
    return cfg, model, params, eng


def test_queue_drains_across_waves():
    cfg, model, params, eng = _setup(batch_slots=2)
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.integers(0, cfg.vocab, 32)) for _ in range(5)]
    out = eng.run()
    assert sorted(out) == sorted(rids)
    assert eng.stats["waves"] == 3            # 2 + 2 + 1
    assert all(len(out[r].generated) == 6 for r in rids)


def test_engine_matches_manual_decode():
    cfg, model, params, eng = _setup(batch_slots=1)
    rng = np.random.default_rng(1)
    ctx = rng.integers(0, cfg.vocab, 32).astype(np.int32)
    rid = eng.submit(ctx, max_new=4)
    out = eng.run()[rid]

    # manual greedy decode of the same request
    S, P = 32, cfg.split.n_owners
    caches = model.cache_init(1, S, n_new=5)
    ot = jnp.asarray(ctx.reshape(1, P, S // P).transpose(1, 0, 2))
    logits, caches = model.prefill(params, {"owner_tokens": ot}, caches)
    toks = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for t in range(4):
        toks.append(int(tok[0, 0]))
        if t < 3:
            logits, caches = model.decode_step(params, caches, tok,
                                               S + t, S // P + t)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    assert out.generated == toks


def test_eos_stops_early():
    cfg, model, params, eng = _setup(batch_slots=1)
    rng = np.random.default_rng(2)
    ctx = rng.integers(0, cfg.vocab, 32)
    # pick the EOS as whatever greedy emits first -> length must be 1
    rid = eng.submit(ctx, max_new=6)
    first = eng.run()[rid].generated[0]
    eng2 = ServingEngine(model, params, batch_slots=1, ctx_len=32,
                         max_new=6, eos_token=first)
    rid2 = eng2.submit(ctx, max_new=6)
    assert eng2.run()[rid2].generated == [first]


def test_rejects_oversized_context():
    cfg, model, params, eng = _setup()
    with pytest.raises(ValueError):
        eng.submit(np.zeros(999, np.int32))


def test_queue_full_carries_backpressure_signal():
    cfg = get_config("llama3.2-3b", reduced=True)
    model = SplitModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, batch_slots=1, ctx_len=32,
                        max_new=2, max_queue=2)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(0, cfg.vocab, 32))
    eng.submit(rng.integers(0, cfg.vocab, 32))
    with pytest.raises(QueueFull) as ei:
        eng.submit(rng.integers(0, cfg.vocab, 32))
    assert ei.value.queue_depth == 2
    assert ei.value.retry_after_s > 0.0
    assert eng.stats["rejected"] == 1
    # bounded blocking submit: gives up after the timeout with the
    # same structured rejection
    t0 = time.monotonic()
    with pytest.raises(QueueFull):
        eng.submit(rng.integers(0, cfg.vocab, 32), block=True,
                   timeout=0.1)
    assert 0.05 < time.monotonic() - t0 < 5.0
    assert eng.stats["rejected"] == 2


def test_blocking_submit_admits_when_queue_drains():
    cfg = get_config("llama3.2-3b", reduced=True)
    model = SplitModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, batch_slots=1, ctx_len=32,
                        max_new=2, max_queue=1)
    rng = np.random.default_rng(3)
    eng.submit(rng.integers(0, cfg.vocab, 32))

    def drain():
        time.sleep(0.2)
        eng._queue.pop(0)       # another thread serving the queue

    th = threading.Thread(target=drain)
    th.start()
    rid = eng.submit(rng.integers(0, cfg.vocab, 32), block=True,
                     timeout=10.0)
    th.join()
    assert isinstance(rid, int)
    assert eng.stats["rejected"] == 0


@pytest.mark.parametrize("scheduler", ["wave", "continuous"])
def test_degraded_service_per_request_errors(scheduler, monkeypatch):
    """A transport/runtime fault mid-schedule fails the affected
    requests with ``Result.error`` set instead of blowing up ``run`` —
    the engine object stays serviceable."""
    cfg = get_config("llama3.2-3b", reduced=True)
    model = SplitModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, batch_slots=2, ctx_len=32,
                        max_new=2, scheduler=scheduler)
    rng = np.random.default_rng(4)
    rids = [eng.submit(rng.integers(0, cfg.vocab, 32)) for _ in range(3)]
    if scheduler == "wave":
        monkeypatch.setattr(eng, "_run_wave",
                            lambda wave: (_ for _ in ()).throw(
                                RuntimeError("wire died")))
    else:
        monkeypatch.setattr(eng, "_continuous_loop",
                            lambda *a: (_ for _ in ()).throw(
                                RuntimeError("wire died")))
    out = eng.run()
    assert sorted(out) == sorted(rids)
    assert all(out[r].error and "wire died" in out[r].error for r in rids)
    assert eng.stats["failed_requests"] == 3
    assert any(e[0] == "degraded" and "wire died" in e[2]
               for e in eng.transcript)
    # the engine still serves fresh work afterwards
    monkeypatch.undo()
    rid = eng.submit(rng.integers(0, cfg.vocab, 32))
    ok = eng.run()
    assert ok[rid].error is None and len(ok[rid].generated) == 2


@pytest.mark.parametrize("scheduler", ["wave", "continuous"])
def test_device_fault_raises(scheduler, monkeypatch):
    """A device fault (JaxRuntimeError: out of memory, failed compile) is
    not a degraded per-request result: ``run`` raises it."""
    cfg = get_config("llama3.2-3b", reduced=True)
    model = SplitModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, batch_slots=2, ctx_len=32,
                        max_new=2, scheduler=scheduler)
    rng = np.random.default_rng(4)
    for _ in range(3):
        eng.submit(rng.integers(0, cfg.vocab, 32))

    def boom(*a):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory")

    hook = "_run_wave" if scheduler == "wave" else "_continuous_loop"
    monkeypatch.setattr(eng, hook, boom)
    with pytest.raises(jax.errors.JaxRuntimeError, match="RESOURCE"):
        eng.run()
    assert eng.stats["failed_requests"] == 0
