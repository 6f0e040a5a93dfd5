"""The reference's first training steps, with the parties' update rules.

Each party updates its own segment with the configuration's rule
(``optimizer`` in the configuration file): ``sgd`` (``p -= lr g``), or
``adam`` with the gradient first clipped to a global norm of ``clip``
over that party's segment alone, as a party that sees only its own
gradient must.  Readings, per party and per leaf: the gradient as the
optimizer gets it on step 1 (after the clip) and the change of the
parameters after all steps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.numerics import Numerics


def _paths(tree):
    return {jax.tree_util.keystr(p): v
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def leaf_norms(segments) -> dict:
    """``{party: {leaf path: norm}}`` of a ``{party: tree}`` dict."""
    norms = jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), t))
    return {party: {k: float(v) for k, v in _paths(norms(tree)).items()}
            for party, tree in segments.items()}


def moved_rows(segments) -> dict:
    """``{party: {leaf path: [row, ...]}}``: for every leaf of two or
    more axes, the indices on its first axis whose entries are not all
    zero in ``segments`` (a change of the parameters).  Under the
    configurations' optimizers a row moves exactly when its gradient is
    not all zero, so an embedding table moves the rows of the tokens
    seen and no others."""
    rows = jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.any(a.reshape(a.shape[0], -1) != 0, axis=1), t))
    out = {}
    for party, tree in segments.items():
        flat = _paths(tree)
        keep = {k: v for k, v in flat.items() if v.ndim >= 2}
        got = rows(keep)
        out[party] = {k: [int(i) for i in np.flatnonzero(np.asarray(v))]
                      for k, v in got.items()}
    return out


def _rule(spec):
    kind = spec["kind"]
    if kind == "sgd":
        def init(p):
            return ()

        def update(g, s, p, t):
            return jax.tree.map(lambda a, b: a - spec["lr"] * b, p, g), s, g
        return init, update
    if kind == "adam":
        b1, b2, eps, lr = spec["b1"], spec["b2"], spec["eps"], spec["lr"]

        def init(p):
            z = jax.tree.map(jnp.zeros_like, p)
            return {"m": z, "v": z}

        def update(g, s, p, t):
            if spec.get("clip"):
                norm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                    for x in jax.tree.leaves(g)))
                g = jax.tree.map(
                    lambda x: x * jnp.minimum(
                        1.0, spec["clip"] / jnp.maximum(norm, 1e-9)), g)
            m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, s["m"], g)
            v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b,
                             s["v"], g)
            k = t + 1.0
            new = jax.tree.map(
                lambda p_, m_, v_: p_ - lr * (m_ / (1 - b1 ** k))
                / (jnp.sqrt(v_ / (1 - b2 ** k)) + eps), p, m, v)
            return new, {"m": m, "v": v}, g
        return init, update
    raise ValueError(f"unknown optimizer kind {kind!r}")


def join(segments):
    """The parameter tree from the parties' segments (owners stacked on
    a leading axis, as the program lays them out)."""
    owners = sorted((k for k in segments if k != "trunk"),
                    key=lambda k: int(k[len("owner"):]))
    heads = jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[segments[o] for o in owners])
    return {"heads": heads, "trunk": segments["trunk"]}


def run_steps(reference, cfg: dict, params, batches, nx: Numerics):
    """Train ``params`` on ``batches`` (a list of argument tuples for
    ``reference.loss`` after ``params``, each with the batch on axis 0)
    with the parties' rules.  Returns ``{"losses", "grad_norms",
    "change_norms", "moved_rows"}``."""
    rules = {}
    for party in reference.segments(params, cfg):
        spec = cfg["optimizer"]["trunk" if party == "trunk" else "owner"]
        rules[party] = _rule(spec)

    def loss_fn(p, *batch):
        with jax.default_matmul_precision("highest"):
            return reference.loss(p, *batch, cfg, nx)

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    @jax.jit
    def update(segs, grads, states, t):
        grads = reference.segments(grads, cfg)
        out_p, out_s, out_g = {}, {}, {}
        for party, (_, upd) in rules.items():
            out_p[party], out_s[party], out_g[party] = upd(
                grads[party], states[party], segs[party], t)
        return out_p, out_s, out_g

    segs0 = jax.jit(lambda p: reference.segments(p, cfg))(params)
    segs = segs0
    states = jax.jit(lambda ss: {party: rules[party][0](s)
                                 for party, s in ss.items()})(segs)
    joined = jax.jit(join)
    losses, grad_norms = [], None
    for t, batch in enumerate(batches):
        value, g = grad_fn(joined(segs), *batch)
        losses.append(float(value))
        segs, states, given = update(segs, g, states, jnp.float32(t))
        if t == 0:
            grad_norms = leaf_norms(given)
    change = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
        segs, segs0)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": leaf_norms(change),
            "moved_rows": moved_rows(change)}
