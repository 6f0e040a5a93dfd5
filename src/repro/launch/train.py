"""End-to-end SplitNN training launcher (runs for real on the host mesh).

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --reduced --steps 50 --batch 8 --seq 256

A thin client of ``VerticalSession``: token streams are vertically
partitioned into sequence-slice owners + a label-holding scientist, the
session resolves/aligns them (DH-PSI), builds the split model through the
registry, and runs the jitted per-segment-optimizer loop with
checkpointing.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.data import make_token_dataset
from repro.federation import VerticalSession, sequence_parties
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--owner-lr", type=float, default=1e-3)
    ap.add_argument("--scientist-lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    if cfg.modality != "text":
        raise SystemExit("train.py drives text archs; see examples/ for "
                         "vlm/audio training")
    toks = make_token_dataset(max(args.batch * 8, 64), args.seq,
                              cfg.vocab, args.seed)
    session = VerticalSession(
        *sequence_parties(toks, cfg.split.n_owners), seed=args.seed)
    session.resolve(group="modp512")
    session.build(cfg, seed=args.seed)

    model = session.adapter.model
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(session.params))
    print(f"arch={cfg.name} reduced={args.reduced} params={n_params/1e6:.1f}M"
          f" owners={cfg.split.n_owners} cut_layer={model.n_head_units}")

    history = session.fit(
        steps=args.steps, batch_size=args.batch,
        owner_lr=args.owner_lr, scientist_lr=args.scientist_lr,
        log_every=args.log_every,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every if args.ckpt_dir else 0)
    return history["final"]["loss"]


if __name__ == "__main__":
    enable_compile_cache()
    main()
