"""The paper's dual-headed MLP SplitNN: exactness (claim C3), combine
strategies, per-segment optimizers, and learning (claim C2, small-scale)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro.testing.hypo import given, settings, strategies as st

from repro.configs.pyvertical_mnist import CONFIG as MNIST_CFG, MLPSplitConfig
from repro.core.splitnn import (MLPSplitNN, cut_layer_traffic,
                                make_split_train_step, train_state_init)
from repro.data import make_mnist_like
from repro.optim import multi_segment, sgd


def _batch(n=32, seed=0):
    rng = np.random.default_rng(seed)
    X, y = make_mnist_like(n, seed)
    xs = jnp.asarray(np.stack(np.split(X, 2, axis=1)))    # (P, B, 392)
    return {"x_slices": xs, "labels": jnp.asarray(y)}


def test_paper_architecture_dimensions():
    m = MLPSplitNN(MNIST_CFG)
    params = m.init(jax.random.PRNGKey(0))
    # heads: stacked (2, 392 -> 64); trunk: 128 -> 500 -> 10 (Appendix B)
    assert params["heads"][0]["w"].shape == (2, 392, 64)
    assert params["trunk"][0]["w"].shape == (128, 500)
    assert params["trunk"][1]["w"].shape == (500, 10)
    logits = m.forward(params, _batch()["x_slices"])
    assert logits.shape == (32, 10)


def test_split_equals_monolithic_forward_and_grads():
    """C3: the dual-headed SplitNN with concat combine IS the monolithic
    network whose first layer is block-diagonal.  Forward and gradients
    must match exactly."""
    m = MLPSplitNN(MNIST_CFG)
    params = m.init(jax.random.PRNGKey(1))
    batch = _batch(16, seed=2)

    # monolithic first layer: block-diag(W_h0, W_h1), concat biases
    w0, w1 = params["heads"][0]["w"][0], params["heads"][0]["w"][1]
    b0, b1 = params["heads"][0]["b"][0], params["heads"][0]["b"][1]
    W1 = jnp.zeros((784, 128)).at[:392, :64].set(w0).at[392:, 64:].set(w1)
    B1 = jnp.concatenate([b0, b1])

    def mono_loss(W1, B1, trunk, x_full, labels):
        h = jax.nn.relu(x_full @ W1 + B1)
        for i, layer in enumerate(trunk):
            h = h @ layer["w"] + layer["b"]
            if i < len(trunk) - 1:
                h = jax.nn.relu(h)
        logp = jax.nn.log_softmax(h)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))

    x_full = jnp.concatenate([batch["x_slices"][0], batch["x_slices"][1]], 1)
    loss_mono = mono_loss(W1, B1, params["trunk"], x_full, batch["labels"])
    loss_split, _ = m.loss_fn(params, batch)
    np.testing.assert_allclose(loss_split, loss_mono, rtol=1e-6)

    g_mono = jax.grad(mono_loss)(W1, B1, params["trunk"], x_full,
                                 batch["labels"])
    g_split = jax.grad(lambda p: m.loss_fn(p, batch)[0])(params)
    gh = g_split["heads"][0]["w"]
    np.testing.assert_allclose(gh[0], g_mono[:392, :64], atol=1e-6)
    np.testing.assert_allclose(gh[1], g_mono[392:, 64:], atol=1e-6)
    # C4 structurally: the split model HAS no cross-owner first-layer
    # params (the monolithic net's off-diagonal blocks) — owner p's raw
    # features touch only owner p's segment.
    assert gh.shape == (2, 392, 64)


@pytest.mark.parametrize("combine", ["concat", "sum", "mean", "max"])
def test_combine_strategies(combine):
    import dataclasses
    cfg = dataclasses.replace(
        MNIST_CFG, split=dataclasses.replace(MNIST_CFG.split,
                                             combine=combine))
    m = MLPSplitNN(cfg)
    params = m.init(jax.random.PRNGKey(0))
    logits = m.forward(params, _batch()["x_slices"])
    assert logits.shape == (32, 10)
    assert not jnp.isnan(logits).any()


def test_per_segment_learning_rates_differ():
    """Owners update with lr 0.01, the scientist with lr 0.1 (Appendix B):
    with SGD the update magnitude ratio must match exactly."""
    m = MLPSplitNN(MNIST_CFG)
    params = m.init(jax.random.PRNGKey(0))
    batch = _batch(16)
    grads = jax.grad(lambda p: m.loss_fn(p, batch)[0])(params)
    opt = multi_segment({"heads": sgd(0.01), "trunk": sgd(0.1)})
    state = train_state_init(params, opt)
    updates, _ = opt.update(grads, state, params, 0)
    np.testing.assert_allclose(updates["heads"][0]["w"],
                               -0.01 * grads["heads"][0]["w"], rtol=1e-6)
    np.testing.assert_allclose(updates["trunk"][0]["w"],
                               -0.1 * grads["trunk"][0]["w"], rtol=1e-6)


def test_training_learns():
    """C2 (small scale): a few hundred steps beats chance by a wide margin."""
    m = MLPSplitNN(MNIST_CFG)
    params = m.init(jax.random.PRNGKey(0))
    opt = multi_segment({"heads": sgd(0.01), "trunk": sgd(0.1)})
    state = train_state_init(params, opt)
    step = make_split_train_step(m.loss_fn, opt, donate=False)
    rng = np.random.default_rng(0)
    X, y = make_mnist_like(1024, 5)
    for i in range(200):
        idx = rng.integers(0, 1024, 128)
        b = {"x_slices": jnp.asarray(np.stack(np.split(X[idx], 2, 1))),
             "labels": jnp.asarray(y[idx])}
        params, state, metrics = step(params, state, b, i)
    assert float(metrics["accuracy"]) > 0.5  # chance = 0.1


def test_cut_layer_traffic_accounting():
    t = cut_layer_traffic(n_owners=2, batch=128, tokens_per_owner=1,
                          cut_dim=64, bytes_per_el=4)
    assert t["per_owner_forward_bytes"] == 128 * 64 * 4
    assert t["total_per_step_bytes"] == 2 * 2 * 128 * 64 * 4


@given(st.integers(2, 4), st.sampled_from(["concat", "sum", "mean", "max"]))
@settings(max_examples=8)
def test_n_owner_generalization(n_owners, combine):
    """The paper's future-work axis: >2 owners work out of the box."""
    import dataclasses
    from repro.configs.base import SplitConfig
    if 784 % n_owners:
        n_owners = 2
    cfg = MLPSplitConfig(split=SplitConfig(n_owners=n_owners, combine=combine,
                                           cut_dim=64))
    m = MLPSplitNN(cfg)
    params = m.init(jax.random.PRNGKey(0))
    X, y = make_mnist_like(8, 1)
    xs = jnp.asarray(np.stack(np.split(X, n_owners, axis=1)))
    loss, metrics = m.loss_fn(params, {"x_slices": xs,
                                       "labels": jnp.asarray(y)})
    assert jnp.isfinite(loss)


def test_imbalanced_vertical_split():
    """Paper §5.1 future work: owners with different feature widths."""
    from repro.configs.base import SplitConfig
    cfg = MLPSplitConfig(feature_splits=(588, 196),
                         split=SplitConfig(n_owners=2, combine="concat",
                                           cut_dim=64))
    m = MLPSplitNN(cfg)
    assert not m.symmetric
    params = m.init(jax.random.PRNGKey(0))
    assert params["heads"][0][0]["w"].shape == (588, 64)
    assert params["heads"][1][0]["w"].shape == (196, 64)
    X, y = make_mnist_like(32, 1)
    xs = [jnp.asarray(X[:, :588]), jnp.asarray(X[:, 588:])]
    loss, metrics = m.loss_fn(params, {"x_slices": xs,
                                       "labels": jnp.asarray(y)})
    assert jnp.isfinite(loss)
    grads = jax.grad(lambda p: m.loss_fn(p, {"x_slices": xs,
                                             "labels": jnp.asarray(y)})[0])(
        params)
    gn = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    assert jnp.isfinite(gn) and gn > 0
