"""§Perf optimization levers must be EXACT (or explicitly bounded)
transformations: grouped MoE dispatch, ring-buffer windowed caches,
microbatch gradient accumulation."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.models import moe as moe_mod
from repro.models.model import SplitModel


def test_grouped_dispatch_equals_global_with_ample_capacity():
    cfg = get_config("deepseek-moe-16b", reduced=True)
    mc = dataclasses.replace(cfg.moe, capacity_factor=8.0)
    params = moe_mod.moe_init(jax.random.PRNGKey(0), 64, mc, "swiglu")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    out1, aux1 = moe_mod.moe_apply(params, x, mc, "swiglu")
    out2, aux2 = moe_mod.moe_apply(
        params, x, dataclasses.replace(mc, dispatch_groups=4), "swiglu")
    np.testing.assert_allclose(out1, out2, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(aux1), float(aux2), rtol=1e-6)


def test_grouped_dispatch_gradients_flow():
    cfg = get_config("mixtral-8x7b", reduced=True)
    mc = dataclasses.replace(cfg.moe, dispatch_groups=2)
    params = moe_mod.moe_init(jax.random.PRNGKey(0), 64, mc, "swiglu")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))

    def loss(p):
        out, aux = moe_mod.moe_apply(p, x, mc, "swiglu")
        return jnp.sum(out ** 2) + aux

    g = jax.grad(loss)(params)
    gn = sum(jnp.sum(jnp.square(l)) for l in jax.tree.leaves(g))
    assert jnp.isfinite(gn) and gn > 0


def test_ring_cache_decode_matches_full_cache():
    cfg = get_config("mixtral-8x7b", reduced=True).replace(
        compute_dtype="float32", remat=False, swa_window=16)
    model = SplitModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S, P = 2, 32, 2
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab, (B, S)).astype(np.int32)
    ot = jnp.asarray(toks.reshape(B, P, S // P).transpose(1, 0, 2))
    new = jnp.asarray(rng.integers(0, cfg.vocab, (B, 1)).astype(np.int32))

    outs = {}
    for ring in (False, True):
        caches = model.cache_init(B, S, n_new=4, ring=ring)
        _, c = model.prefill(params, {"owner_tokens": ot}, caches)
        l1, c = model.decode_step(params, c, new, S, S // P)
        t2 = jnp.argmax(l1, -1)[:, None].astype(jnp.int32)
        l2, _ = model.decode_step(params, c, t2, S + 1, S // P + 1)
        outs[ring] = (np.asarray(l1), np.asarray(l2))
    # the ring cache is strictly smaller
    full_b = sum(a.size for a in jax.tree.leaves(
        model.cache_init(B, S, ring=False)))
    ring_b = sum(a.size for a in jax.tree.leaves(
        model.cache_init(B, S, ring=True)))
    assert ring_b < full_b
    for i in range(2):
        np.testing.assert_allclose(outs[False][i], outs[True][i],
                                   atol=2e-3, rtol=2e-3)


def test_microbatch_accumulation_matches_single_batch():
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import build, make_optimizer
    from repro.sharding.specs import make_rules
    cfg = get_config("llama3.2-3b", reduced=True)
    mesh = make_host_mesh()
    shape = ShapeConfig("t", 32, 4, "train")
    rules = make_rules(mesh, cfg)
    model = SplitModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer(cfg)
    state = opt.init(params)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab, (4, 33)).astype(np.int32)
    batch = {"owner_tokens": jnp.asarray(
        toks[:, :-1].reshape(4, 2, 16).transpose(1, 0, 2)),
        "labels": jnp.asarray(toks[:, 1:])}
    losses = {}
    for nm in (1, 4):
        fn, *_ = build(cfg, shape, mesh, rules, n_microbatches=nm)
        _, _, m = jax.jit(fn)(params, state, batch, 0)
        losses[nm] = float(m["loss"])
    assert losses[1] == pytest.approx(losses[4], rel=1e-4)
