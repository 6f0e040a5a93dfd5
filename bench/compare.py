"""The numbers that decide ``correct``, and how each is read.

Training cells compare the program's first steps with the reference's,
by the worst case; each cell's ``bench/limits/<cell>.json`` names the
numbers it compares and their limits:

- ``loss_gap``: over the first steps, the largest ``|L_prog - L_ref| /
  |L_ref|``;
- ``first_loss_gap``: the same of the first step alone, which no update
  has touched yet;
- ``grad_gap``: over the leaves of every party, the largest gap between
  the norms of the first gradient as each party's optimizer got it,
  ``| |g_prog| - |g_ref| | / max(|g_ref|, median leaf |g_ref|)``;
- ``change_gap``: the same of the parameters' change over all the first
  steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (under Adam they move by round-off
  alone);
- ``moved_rows_gap``: over the leaves of two or more axes, the number of
  rows that moved in one and not in the other after the first steps.  A
  row moves exactly when its gradient is not all zero, so this is an
  exact count: an embedding table moves the rows of the tokens its owner
  was given, and a token altered or a row of the batch left out shows
  here however small its share of the loss.
"""
from __future__ import annotations

import statistics

MOVING = 1e-3


def _flat(norms: dict) -> dict:
    return {(party, leaf): v for party, leaves in norms.items()
            for leaf, v in leaves.items()}


def _gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Each leaf's ``|prog - ref| / max(ref, median ref)``."""
    keys = [k for k in ref if keep is None or keep(k)]
    med = statistics.median(ref[k] for k in keys)
    missing = [k for k in keys if k not in prog]
    if missing:
        raise KeyError(f"the program has no leaf {missing[0]}")
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}


def _worst(gaps: dict):
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def worst_leaves(prog: dict, ref: dict, key: str, k: int = 4) -> list:
    """The ``k`` leaves with the largest gaps of ``key`` (for logs):
    ``[[party/leaf, program norm, reference norm], ...]``."""
    p, r = _flat(prog[key]), _flat(ref[key])
    med = statistics.median(r.values())
    order = sorted(r, key=lambda x: -abs(p[x] - r[x]) / max(r[x], med))
    return [["/".join(x), p[x], r[x]] for x in order[:k]]


def train_readings(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: ``{"losses", "grad_norms", "change_norms",
    "moved_rows"}``."""
    lp, lr = prog["losses"], ref["losses"]
    if len(lp) != len(lr):
        raise ValueError(f"{len(lp)} program losses, {len(lr)} reference")
    loss_gaps = [abs(a - b) / abs(b) for a, b in zip(lp, lr)]
    g_ref = _flat(ref["grad_norms"])
    grad = _gaps(_flat(prog["grad_norms"]), g_ref)
    g_med = statistics.median(g_ref.values())
    change = _gaps(_flat(prog["change_norms"]), _flat(ref["change_norms"]),
                   keep=lambda k: g_ref[k] >= MOVING * g_med)
    (grad_gap, grad_at), (change_gap, change_at) = _worst(grad), _worst(change)
    moved_rows_gap = sum(
        len(set(rows) ^ set(prog["moved_rows"][party][leaf]))
        for party, leaves in ref["moved_rows"].items()
        for leaf, rows in leaves.items())
    return {"loss_gap": max(loss_gaps), "first_loss_gap": loss_gaps[0],
            "grad_gap": grad_gap, "change_gap": change_gap,
            "moved_rows_gap": moved_rows_gap,
            "worst_leaf": {"grad": "/".join(grad_at),
                           "change": "/".join(change_at)}}
