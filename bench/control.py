"""Readings of the control and of planted faults, for setting the limits
of a training cell's comparison (``bench/limits/<workload>.json``).

    python3 -m bench.control --workload <name> --seeds 1 2 3

Runs on the chip, at the cell's own size, with no program: for each seed
it makes the cell's data and weights, trains the float32 reference for
the checked steps, and then puts in the program's place:

- ``control``: the reference in the configuration's ``control_precision``
  (one step below what it states);
- ``half_batch``: the reference on the first half of each batch's rows,
  its mean taken over them alone;
- ``altered``: the reference with one input altered in every step
  (``reference.alter``).

Each is judged as a run is (``harness.judge``, the cell's limits): the
line for a seed holds, for each variant, its readings, the numbers
compared beside their limits and ``correct``, which has to come out
false.  A state left unchanged reads 1 on ``change_gap`` by
construction and needs no run.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time


def readings_for(cell, seed: int, variants) -> dict:
    import jax
    from bench import compare, harness
    from bench.drivers.train import index_batches
    from bench.reference import train as ref_train
    from bench.reference.numerics import Numerics, seed_key

    cfg, tr, ref = cell.config, cell.traffic, cell.reference
    data = cell.binding.Data(cfg, tr, seed, ref)
    batches = [data.reference_batch(pos) for pos in index_batches(
        seed, len(data.aligned), tr["batch"], tr["check_steps"])]
    init = jax.jit(functools.partial(ref.init_params, cfg=cfg))
    def train(nx, bs):
        return ref_train.run_steps(ref, cfg, init(seed_key(seed)), bs, nx)

    base = train(Numerics("float32"), batches)
    out = {}
    for v in variants:
        t0 = time.perf_counter()
        if v == "control":
            got = train(Numerics(cfg["control_precision"]), batches)
        elif v == "half_batch":
            half = [tuple(a[:len(a) // 2] for a in b) for b in batches]
            got = train(Numerics("float32"), half)
        elif v == "altered":
            got = train(Numerics("float32"),
                        [ref.alter(b, cfg) for b in batches])
        else:
            raise ValueError(f"unknown variant {v!r}")
        r = compare.train_readings(got, base)
        r["checks"], r["correct"] = harness.judge(cell.name, r)
        r["seconds"] = time.perf_counter() - t0
        out[v] = r
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m bench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+",
                    default=["control", "half_batch", "altered"])
    args = ap.parse_args(argv)
    from bench import harness
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(harness.CACHE_DIR)
    sys.path.insert(0, str(harness.ROOT / "src"))
    cell = harness.Cell(harness.load_benchmark(), args.workload)
    harness.require_device("tpu", cell.chips)
    harness.enable_compile_cache()
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **readings_for(cell, seed, args.variants)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
