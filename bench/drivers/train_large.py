"""Training cells whose reference does not fit one chip under
``bench.reference.train.run_steps``: the ``train`` driver as it is, with
the reference's steps taken by ``run_steps`` below.

``bench.reference.train.run_steps`` holds, beside the parameters it
trains, their first copy, the tree it was given, the gradient, new
parameters, moments and clipped gradient beside the old ones, and the
whole batch's backward pass at once: for DeepSeek-V2-Lite's 642M float32
parameters, 21 GB of state and 7.6 GB of temporaries on a 16 GB chip.
This one takes the same steps with the same update rules
(``bench.reference.train._rule``) and gives the same readings, holding on
the device the parameters once, one gradient, and one batch row's
backward pass: the gradient is summed one row at a time (the references'
``loss`` is a mean over the batch's rows), the first copy of the
parameters and Adam's moments wait on the host between updates, and each
party's update takes its own segment in place.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import train as ref_train


def _leaf_norms(tree):
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))), tree)


def run_steps(reference, cfg: dict, params, batches, nx):
    """``bench.reference.train.run_steps``, one batch row at a time, with
    the optimizer state on the host between updates."""
    parties = jax.eval_shape(lambda p: reference.segments(p, cfg), params)
    rules = {party: ref_train._rule(
        cfg["optimizer"]["trunk" if party == "trunk" else "owner"])
        for party in parties}

    def row_loss(segs, *row):
        with jax.default_matmul_precision("highest"):
            return reference.loss(ref_train.join(segs),
                                  *(a[None] for a in row), cfg, nx)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def add_row(segs, acc, *row):
        value, g = jax.value_and_grad(row_loss)(segs, *row)
        return value, jax.tree.map(jnp.add, acc, g)

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 3))
    def update(party, seg, acc, state, t, n):
        grads = jax.tree.map(lambda a: a / n, acc)
        seg, state, given = rules[party][1](grads, state, seg, t)
        return seg, state, _leaf_norms(given)

    segs = jax.jit(lambda p: reference.segments(p, cfg))(params)
    del params
    segs0 = jax.device_get(segs)
    states = {party: jax.device_get(jax.jit(rules[party][0])(segs[party]))
              for party in segs}
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    losses, grad_norms = [], {}
    for t, batch in enumerate(batches):
        n = len(batch[0])
        acc, values = zeros(segs), []
        for r in range(n):
            value, acc = add_row(segs, acc, *(np.asarray(a)[r]
                                              for a in batch))
            values.append(float(value))
        losses.append(float(np.mean(values)))
        for party in list(segs):
            segs[party], state, norms = update(
                party, segs[party], acc.pop(party),
                jax.device_put(states[party]), jnp.float32(t),
                jnp.float32(n))
            states[party] = jax.device_get(state)
            del state
            if t == 0:
                grad_norms[party] = {k: float(v) for k, v in
                                     ref_train._paths(norms).items()}
    del states
    diff = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))
    change_norms, moved = {}, {}
    for party in list(segs):
        change = {party: diff(segs.pop(party), segs0.pop(party))}
        change_norms.update(ref_train.leaf_norms(change))
        moved.update(ref_train.moved_rows(change))
        del change
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change_norms, "moved_rows": moved}


@contextlib.contextmanager
def lean_reference():
    """Within: ``bench.reference.train.run_steps`` is ``run_steps``."""
    saved = ref_train.run_steps
    ref_train.run_steps = run_steps
    try:
        yield
    finally:
        ref_train.run_steps = saved


def run(cell, ctx) -> dict:
    from bench.drivers import train
    with lean_reference():
        return train.run(cell, ctx)
