"""Training cells: ``VerticalSession.fit`` driven through set-up, its
first checked steps, the timed window and the comparison with the
reference.

Set-up builds one session from the seed (data, PSI resolve, the model
with the benchmark's weights) and runs the traffic's ``check_steps``
steps through ``fit`` with the window's arguments, reading each party's
first update as its optimizer made it (the readers wrap the update
programs' calls; the programs are the window's).  A second short call
times a step.  The window is one more ``fit`` call of N steps on that
same session, N chosen so that it lasts about ``--seconds``; it runs
from entering ``fit`` to its return with the parameters ready.  After
the window the session is freed and the reference trains the same
weights on the same rows.
"""
from __future__ import annotations

import functools
import gc
import math
import threading
import time

import numpy as np


#: Seconds of steps that set-up spends on a call that times a step, to
#: size the window.
PROBE_S = 1.0


def _norms(tree):
    """Each leaf's norm (traced inside a jitted program)."""
    import jax
    import jax.numpy as jnp
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
        tree)


def _paths(tree):
    import jax
    return {jax.tree_util.keystr(p): v
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _adam_m(state):
    if isinstance(state, dict) and "m" in state:
        return state["m"]
    if isinstance(state, (tuple, list)):
        for s in state:
            m = _adam_m(s)
            if m is not None:
                return m
    return None


class FirstUpdate:
    """Each party's first real update, read as its optimizer made it.

    Wraps the adapter's public update-rule accessors for the checked
    steps only.  The last call at step 0 of each party (earlier ones are
    the warm-up's zero updates) is read: under Adam the first moment
    after one step is ``(1 - b1) g``, so ``|g| = |m| / (1 - b1)``; under
    SGD ``g = (p_before - p_after) / lr``.  The party is the calling
    thread's owner (``owner-<name>``), else the trunk."""

    ACCESSORS = {"owner_update_rule": 3, "owner_tail_rule": 5,
                 "trunk_update_rule": 3}

    def __init__(self, adapter, optimizer: dict):
        import jax
        import jax.numpy as jnp
        self.adapter, self.optimizer = adapter, optimizer
        self.norms = jax.jit(_norms)
        self.copy = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
        self.diff_norms = jax.jit(
            lambda b, a: _norms(jax.tree.map(jnp.subtract, b, a)))
        self.records: dict = {}

    def install(self):
        a = self.adapter
        for name, step_at in self.ACCESSORS.items():
            orig = getattr(a, name)

            def accessor(*args, _orig=orig, _at=step_at, **kw):
                got = _orig(*args, **kw)
                if isinstance(got, tuple):
                    return got[0], self._wrap(got[1], _at)
                return self._wrap(got, _at)
            setattr(a, name, accessor)
        return self

    def uninstall(self):
        for name in self.ACCESSORS:
            self.adapter.__dict__.pop(name, None)

    @staticmethod
    def _party() -> str:
        name = threading.current_thread().name
        return name[len("owner-"):] if name.startswith("owner-") else "trunk"

    def _wrap(self, fn, step_at):
        @functools.wraps(fn)
        def call(*args):
            first = int(args[step_at]) == 0
            party = self._party()
            spec = self.optimizer["trunk" if party == "trunk" else "owner"]
            before = None
            if first and spec["kind"] == "sgd":
                before = self.copy(args[0])
            out = fn(*args)
            if first:
                if spec["kind"] == "adam":
                    norms, scale = self.norms(_adam_m(out[1])), 1 - spec["b1"]
                else:
                    norms, scale = self.diff_norms(before, out[0]), spec["lr"]
                self.records[party] = {
                    k: float(v) / scale for k, v in _paths(norms).items()}
            return out
        return call


def index_batches(seed: int, n_train: int, batch: int, steps: int):
    """The rows of the first ``steps`` batches of a ``fit(steps=...)``
    call: one permutation of the training rows from the session's seed,
    taken in order."""
    order = np.random.default_rng(seed).permutation(n_train)
    if steps * batch > n_train:
        raise ValueError("the checked steps would reuse rows")
    return [order[t * batch:(t + 1) * batch] for t in range(steps)]


def run(cell, ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.federation import VerticalSession

    from bench import compare
    from bench.harness import BenchError, diff, log
    from bench.reference import train as ref_train
    from bench.reference.numerics import Numerics, seed_key

    cfg, tr, ref = cell.config, cell.traffic, cell.reference
    if cfg.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          cfg["matmul_precision"])
    seed, K, B = ctx.seed, tr["check_steps"], tr["batch"]

    # ------------------------------------------------------------ set-up
    with jax.profiler.TraceAnnotation("bench.setup"):
        data = cell.binding.Data(cfg, tr, seed, ref)
        session = VerticalSession(data.scientist, data.owners, seed=seed)
        session.resolve(group=tr["resolve_group"])
        if list(session.scientist.ids) != data.aligned:
            raise BenchError("the resolved rows are not the shared ids "
                             "in id order")
        session.build(cell.binding.program_config(cfg), seed=0)
        init = jax.jit(functools.partial(ref.init_params, cfg=cfg))
        params0 = init(seed_key(seed))
        want = jax.tree.map(lambda a: (a.shape, a.dtype), session.params)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params0)
        if want != got:
            raise BenchError("the benchmark's weights do not have the "
                             "program's layout")
        session.params = params0
        del params0
        fit_kw = dict(batch_size=B, mode=tr["mode"], backend=tr["backend"],
                      compression=tr["compression"],
                      schedule=tr["schedule"],
                      microbatches=tr["microbatches"], verbose=False)
        rec = FirstUpdate(session.adapter, cfg["optimizer"]).install()
        try:
            hist = session.fit(steps=K, **fit_kw)
        finally:
            rec.uninstall()
        prog = {"losses": [r["loss"] for r in hist["train"]],
                "grad_norms": rec.records}
        change = jax.jit(lambda a, b: ref.segments(
            jax.tree.map(jnp.subtract, a, b), cfg))(
                session.params, init(seed_key(seed)))
        prog["change_norms"] = ref_train.leaf_norms(change)
        prog["moved_rows"] = ref_train.moved_rows(change)
        del change
        # The checked steps read every update and wait for it, so they
        # overstate a step.  A short call made as the window makes it,
        # of about ``PROBE_S``, gives the seconds a step takes (the
        # program's ``wall_s`` runs from the end of the call's warm-up
        # handshake) and those the call takes besides; the window's
        # steps follow from both.
        checked_s = session.transport_stats["wall_s"]
        n_probe = max(K, math.ceil(PROBE_S * K / checked_s))
        t_probe = time.perf_counter()
        session.fit(steps=n_probe, **fit_kw)
        jax.block_until_ready(session.params)
        probe_s = time.perf_counter() - t_probe
        step_s = session.transport_stats["wall_s"] / n_probe
        call_s = max(probe_s - session.transport_stats["wall_s"], 0.0)
        steps = max(K, int(round((ctx.seconds - call_s) / step_s)))
        n_train = len(data.aligned)
    log(phase="setup", checked_steps=K, losses=prog["losses"],
        probe_steps=n_probe, step_s_estimate=step_s, call_s_estimate=call_s,
        window_steps=steps,
        **ctx.clock.snapshot())

    # ------------------------------------------------------------ window
    before, host0 = ctx.clock.snapshot(), ctx.host.snapshot()
    ctx.start_trace()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.fit"):
            hist = session.fit(steps=steps, **fit_kw)
        jax.block_until_ready(session.params)
    t1 = time.perf_counter()
    host = diff(ctx.host.snapshot(), host0)
    trace = ctx.stop_trace()
    window_s = t1 - t0
    setup_s = t0 - ctx.t_start
    in_window = diff(ctx.clock.snapshot(), before)
    losses = [r["loss"] for r in hist["train"]]
    failed = sum(not math.isfinite(v) for v in losses)
    stats = dict(session.transport_stats)
    log(phase="window", steps=steps, window_s=window_s, setup_s=setup_s,
        compiles_in_window=in_window["compiles"],
        cache_in_window={"hits": in_window["hits"],
                         "misses": in_window["misses"]},
        program_step_ms=stats["step_ms"],
        wire_bytes_per_step=stats["total_wire_bytes"] / stats["steps"],
        last_loss=losses[-1] if losses else None, host=host)
    device = ctx.device_info()
    log(phase="memory", memory_peak_bytes=device["memory_peak_bytes"])

    # ---------------------------------------------------- the reference
    del session, hist
    gc.collect()
    t_ref = time.perf_counter()
    batches = [data.reference_batch(pos)
               for pos in index_batches(seed, n_train, B, K)]
    ref_out = ref_train.run_steps(
        ref, cfg, jax.jit(functools.partial(ref.init_params, cfg=cfg))(
            seed_key(seed)), batches, Numerics("float32"))
    readings = compare.train_readings(prog, ref_out)
    log(phase="norms", **{f"{side}_{key}": out[key]
                          for side, out in (("program", prog),
                                            ("reference", ref_out))
                          for key in ("grad_norms", "change_norms")})
    log(phase="reference", seconds=time.perf_counter() - t_ref,
        reference_losses=ref_out["losses"],
        worst_grad_leaves=compare.worst_leaves(prog, ref_out, "grad_norms"),
        worst_change_leaves=compare.worst_leaves(prog, ref_out,
                                                 "change_norms"))

    units = cell.binding.units_per_step(cfg, tr)
    values = {"setup_s": setup_s}
    for unit, per_step in units.items():
        values[f"train_{unit}_per_s"] = steps * per_step / window_s
    counters = {"steps": steps, "units_per_step": units,
                "window_s": window_s, "transport": stats}
    return {"attempted": steps, "failed": failed, "values": values,
            "readings": readings, "device": device, "trace": trace,
            "counters": counters}
