"""Compile the split training programs of ``dsv2lite.train-split-s4k`` at
the cell's shapes for a described TPU v5e chip, and print each one's
``memory_analysis()`` and compile seconds (one JSON line each).  No chip
is needed: the TPU compiler runs here, on shapes, with nothing to run.

    JAX_PLATFORMS=cpu python3 bench/tests/compile_dsv2lite.py

Run by hand: the trunk programs take minutes to compile, too long for the
test suite.  It holds what the chip would refuse for its memory before
any chip time is spent: each program alone, not what the process keeps
besides (parameters and Adam state of every party, the cuts).
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

WORKLOAD = "dsv2lite.train-split-s4k"


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from repro.federation.registry import build_adapter

    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.Cell(harness.load_benchmark(), WORKLOAD)
    cfg, tr = cell.config, cell.traffic
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    adapter = build_adapter(cell.binding.program_config(cfg))
    model = adapter.model

    def spec(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    B, S, P = tr["batch"], tr["seq_len"], cfg["n_owners"]
    S_p, d = S // P, cfg["hidden_size"]
    cdt = jnp.dtype(model.cfg.compute_dtype)
    trunk = spec(params["trunk"])
    head = spec(jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:],
                                                            a.dtype),
                             params["heads"]))
    cuts = tuple(jax.ShapeDtypeStruct((B, S_p, d), cdt, sharding=one)
                 for _ in range(P))
    labels = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=one)
    tokens = jax.ShapeDtypeStruct((B, S_p), jnp.int32, sharding=one)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    cutgrad, weightgrad = adapter.trunk_microbatch_programs()
    head_fwd, _ = adapter.owner_programs(0)
    trunk_opt, trunk_upd = adapter.trunk_update_rule(None)
    owner_opt, _ = adapter.owner_update_rule(None)
    tail = adapter.owner_tail_rule(None, 0)
    trunk_state = spec(jax.eval_shape(trunk_opt.init, params["trunk"]))
    head_state = spec(jax.eval_shape(owner_opt.init, head))
    cut_g = jax.ShapeDtypeStruct((B, S_p, d), cdt, sharding=one)
    programs = {
        "cutgrad": (cutgrad, (trunk, cuts, labels, scalar, scalar)),
        "weightgrad": (weightgrad, (trunk, cuts, labels, scalar, scalar)),
        "upd (trunk)": (trunk_upd, (trunk, trunk_state, trunk, step)),
        "head_fwd": (head_fwd, (head, tokens)),
        "tail (owner)": (tail, (head, head_state, None, tokens, cut_g, step,
                                tokens)),
    }
    only = set(argv if argv is not None else sys.argv[1:])
    for name, (fn, args) in programs.items():
        if only and name.split()[0] not in only:
            continue
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        m = compiled.memory_analysis()
        print(json.dumps({
            "program": name, "compile_s": round(time.perf_counter() - t0, 1),
            "argument_gb": m.argument_size_in_bytes / 1e9,
            "output_gb": m.output_size_in_bytes / 1e9,
            "alias_gb": m.alias_size_in_bytes / 1e9,
            "temp_gb": m.temp_size_in_bytes / 1e9}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
