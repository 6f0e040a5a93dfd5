"""Smoke run of the split stack on one TPU chip, through ``VerticalSession``.

    python chip_smoke.py

One process, one chip, four phases in order; each check that fails
raises, and the script exits non-zero:

1. device: JAX's default device must be a TPU (there is no CPU path).
2. mnist: the paper's pipeline (Appendix B dual-headed MLP) on 2000
   synthetic vertically split images: PSI resolve, build, one epoch of
   ``fit(mode="split", compression="int8", backend="queue")``, which
   runs the int8 cut-quantize Pallas kernel compiled for the chip.
3. lm_train: ``llama3.2-3b`` at its published widths with depth and
   vocabulary cut (2 layers, cut after 1; vocab 32768), B=4, S=1024 over
   two sequence-slice owners.  From one init each: 3 joint steps, 3
   split steps (direct backend), and 3 split steps with the int8 codec.
   The first loss of each split run must match the first joint loss.
4. lm_serve: ``serve_dataset`` on the trained session, 8 requests of 16
   new tokens each over the direct transport.

Each phase prints one JSON line: wall seconds split into warmup and
steady (``wall_s = warmup_s + steady_s``), the seconds JAX spent tracing
and compiling (``compile_s``, summed over threads, so it can exceed the
warmup where owner threads compile side by side), ``peak_bytes_in_use``
of the device so far, and its losses or tokens.  A split fit's steady
part is the session's own timed region after its warmup handshake; a
joint fit's or the server's is the wall time less ``compile_s``.  The
last line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.pyvertical_mnist import CONFIG as MNIST_CONFIG  # noqa
from repro.data import make_token_dataset, make_vertical_mnist_parties  # noqa
from repro.federation import (VerticalSession, feature_parties,  # noqa
                              sequence_parties)
from repro.federation.transport import get_codec  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

#: relative tolerance of a split run's first loss against the joint
#: one: bf16's machine epsilon (2^-7: 7 stored mantissa bits).  It covers
#: the int8 codec too, whose rounding is at most half a step of
#: absmax / 127 per element, i.e. 2^-8 of the row's largest value.
BF16_RTOL = 2.0 ** -7


class SmokeFailure(RuntimeError):
    """A phase's output failed its check."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (its own
    ``/jax/core/compile/*`` duration events), and persistent-cache
    hits/misses, accumulated since construction."""

    def __init__(self):
        self.seconds = 0.0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_kw):
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._event)


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def timed(clock: CompileClock, fn, steady=None):
    """Run ``fn()``; returns (its result, timing dict).  ``steady(out)``
    gives the steady seconds where the callee times them itself; else
    they are the wall time less the compile time."""
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn()
    wall, comp = time.perf_counter() - t0, clock.seconds - c0
    st = steady(out) if steady else wall - comp
    return out, {"wall_s": wall, "warmup_s": wall - st, "steady_s": st,
                 "compile_s": comp}


def emit(**line) -> None:
    print(json.dumps(line), flush=True)


def check_losses(trail, what: str) -> None:
    check(len(trail) > 0 and all(math.isfinite(v) for v in trail),
          f"{what}: non-finite or missing losses {trail}")


def phase_mnist(clock: CompileClock, n: int = 2000) -> dict:
    """The paper's pipeline, split over the queue backend with int8 cuts."""
    sci, owners = make_vertical_mnist_parties(n, seed=0, keep_frac=0.9)
    session = VerticalSession(*feature_parties(sci, owners))
    session.resolve(group="modp512")
    session.build(MNIST_CONFIG)
    hist, times = timed(clock, lambda: session.fit(
        epochs=1, batch_size=128, eval_frac=0.15, mode="split",
        compression="int8", backend="queue", verbose=False),
        steady=lambda _: session.transport_stats["wall_s"])
    losses = [r["loss"] for r in hist["train"]]
    check_losses(losses, "mnist split int8")

    # the int8 wire frame, decoded on the host, is within half a
    # quantization step of its input (per row: absmax / 127 / 2)
    codec = get_codec("int8")
    x = np.random.default_rng(0).normal(size=(128, 64)).astype(np.float32)
    frame = codec.encode(x)["qp"]
    check(frame.shape == (128, 68) and frame.dtype == np.uint8,
          f"int8 frame is {frame.shape} {frame.dtype}, not (128, 68) uint8")
    err = np.abs(codec.decode({"qp": frame}) - x)
    half_step = np.abs(x).max(axis=-1, keepdims=True) / 127.0 / 2.0
    check(bool(np.all(err <= half_step * (1 + 1e-5))),
          f"int8 frame decodes {float(err.max())} from its input")

    ts = session.transport_stats
    return {"phase": "mnist", **times, "peak_bytes_in_use": peak_bytes(),
            "steps": ts["steps"], "losses": losses,
            "val_accuracy": hist["final"].get("val_accuracy"),
            "cut_payload_bytes_per_step": ts["cut_payload_bytes_per_step"]}


def lm_config():
    """llama3.2-3b at published widths; depth and vocabulary cut to fit
    one 16 GB chip with float32 Adam state.  Returns (config, cuts)."""
    full = get_config("llama3.2-3b")
    cfg = full.replace(n_layers=2, vocab=32768).with_split(cut_layer=1)
    cuts = {k: [getattr(full, k), getattr(cfg, k)]
            for k in ("n_layers", "vocab")}
    return cfg, cuts


def phase_lm_train(clock: CompileClock, cfg, *, batch: int = 4,
                   seq: int = 1024, steps: int = 3, seed: int = 0):
    """Joint, split and split+int8 training, each from the same init.
    Returns the phase line and the session as the last run left it."""
    toks = make_token_dataset(64, seq, cfg.vocab, seed)
    session = VerticalSession(*sequence_parties(toks, cfg.split.n_owners),
                              seed=seed)
    session.resolve(group="modp512")
    runs, trails = {}, {}
    for name, kw in (("joint", {"mode": "joint"}),
                     ("split", {"mode": "split", "backend": "direct"}),
                     ("split_int8", {"mode": "split", "backend": "direct",
                                     "compression": "int8"})):
        session.build(cfg, seed=seed)
        hist, times = timed(clock, lambda: session.fit(
            steps=steps, batch_size=batch, verbose=False, **kw),
            steady=(lambda _: session.transport_stats["wall_s"])
            if kw["mode"] == "split" else None)
        trails[name] = [r["loss"] for r in hist["train"]]
        check_losses(trails[name], f"lm {name}")
        runs[name] = {**times, "peak_bytes_in_use": peak_bytes()}
    j = trails["joint"]
    for name in ("split", "split_int8"):
        first = trails[name][0]
        check(abs(first - j[0]) <= BF16_RTOL * abs(j[0]),
              f"first {name} loss {first} != first joint loss {j[0]} "
              f"(rtol {BF16_RTOL})")
    line = {"phase": "lm_train", "batch": batch, "seq": seq,
            **{k: sum(r[k] for r in runs.values())
               for k in ("wall_s", "warmup_s", "steady_s", "compile_s")},
            "peak_bytes_in_use": peak_bytes(), "runs": runs,
            "losses": trails,
            "max_abs_diff_joint_split": max(
                abs(a - b) for a, b in zip(j, trails["split"]))}
    return line, session


def phase_lm_serve(clock: CompileClock, session, *, max_new: int = 16,
                   n_requests: int = 8) -> dict:
    """Split serving of the session's own contexts over the direct
    transport: every request must come back whole, with no error."""
    (results, engine), times = timed(clock, lambda: session.serve_dataset(
        max_new=max_new, batch_slots=4, n_requests=n_requests,
        transport="direct"))
    vocab = session.config.vocab
    check(len(results) == n_requests,
          f"served {len(results)} of {n_requests} requests")
    for rid, res in sorted(results.items()):
        check(res.error is None, f"request {rid} failed: {res.error}")
        check(len(res.generated) == max_new,
              f"request {rid} got {len(res.generated)} of {max_new} tokens")
        check(all(0 <= t < vocab for t in res.generated),
              f"request {rid} has tokens outside the vocabulary")
    check(engine.stats["failed_requests"] == 0,
          f"{engine.stats['failed_requests']} failed requests")
    return {"phase": "lm_serve", **times, "peak_bytes_in_use": peak_bytes(),
            "tokens_generated": engine.stats["tokens_generated"],
            "tokens": {rid: r.generated for rid, r in sorted(results.items())}}


def main() -> None:
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    emit(phase="device", **device)
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    cache_dir = enable_compile_cache()
    clock = CompileClock()

    emit(**phase_mnist(clock))
    cfg, cuts = lm_config()
    emit(phase="lm_config", arch=cfg.name, cut_from_published=cuts,
         d_model=cfg.d_model, n_heads=cfg.n_heads,
         n_kv_heads=cfg.n_kv_heads, d_ff=cfg.d_ff,
         cut_layer=cfg.split.cut_layer, n_owners=cfg.split.n_owners)
    line, session = phase_lm_train(clock, cfg)
    emit(**line)
    emit(**phase_lm_serve(clock, session))
    emit(phase="compile_cache", dir=cache_dir, **clock.cache,
         files=len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
         else 0)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
