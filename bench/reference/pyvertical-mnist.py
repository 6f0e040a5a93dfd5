"""Plain float32 reference of the paper's dual-headed SplitNN.

PyVertical (Romanini et al. 2021), Appendix B: each of two data owners
holds one half of every image (392 features) and runs Linear(392 -> 64)
and ReLU; the data scientist concatenates the two cuts (128) and runs
Linear(128 -> 500), ReLU, Linear(500 -> 10), with softmax cross-entropy
on the labels it holds.  Weights are He-normal with zero biases.  It
imports nothing of the program; parameters are laid out as the
program's tree.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.numerics import Numerics


def _dense_stack(key, dims):
    layers = []
    for a, b in zip(dims[:-1], dims[1:]):
        key, k = jax.random.split(key)
        layers.append({"w": jax.random.normal(k, (a, b), jnp.float32)
                       * np.sqrt(2.0 / a),
                       "b": jnp.zeros((b,), jnp.float32)})
    return layers


def init_params(key, cfg: dict):
    P = cfg["n_owners"]
    f_p = cfg["n_features"] // P
    kh, kt = jax.random.split(key)
    heads = jax.vmap(lambda k: _dense_stack(
        k, (f_p,) + tuple(cfg["head_layers"])))(jax.random.split(kh, P))
    trunk = _dense_stack(kt, (P * cfg["head_layers"][-1],)
                         + tuple(cfg["trunk_layers"]))
    return {"heads": heads, "trunk": trunk}


def _mlp(layers, x, nx: Numerics, relu_last: bool):
    for i, layer in enumerate(layers):
        x = nx.dot(x, layer["w"]) + layer["b"]
        if relu_last or i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def logits(params, owner_x, cfg: dict, nx: Numerics):
    """``owner_x``: (B, P, f_p) the owners' feature slices."""
    P = cfg["n_owners"]
    cuts = [_mlp(jax.tree.map(lambda a: a[p], params["heads"]),
                 owner_x[:, p],
                 nx, relu_last=True) for p in range(P)]
    return _mlp(params["trunk"], jnp.concatenate(cuts, axis=-1), nx,
                relu_last=False)


def loss(params, owner_x, labels, cfg: dict, nx: Numerics):
    lg = logits(params, owner_x, cfg, nx)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[:, None], -1)[:, 0]
    return jnp.mean(lse - picked)


def segments(params, cfg: dict):
    out = {f"owner{p}": jax.tree.map(lambda a: a[p], params["heads"])
           for p in range(cfg["n_owners"])}
    out["trunk"] = params["trunk"]
    return out


def make_data(cfg: dict, n: int, seed: int):
    """MNIST-shaped images (n, 784) float32 in [0, 1] and labels (n,):
    per-class smooth prototypes (outer products of random sinusoids plus
    noise), shifted by up to 2 pixels, plus per-image noise."""
    rng = np.random.default_rng(seed)
    side = int(round(np.sqrt(cfg["n_features"])))
    C = cfg["n_classes"]
    xs = np.linspace(0, 1, side)
    protos = []
    for _ in range(C):
        fx, fy = rng.uniform(1, 4, 2)
        px, py = rng.uniform(0, np.pi, 2)
        img = np.outer(np.sin(2 * np.pi * fx * xs + px),
                       np.cos(2 * np.pi * fy * xs + py))
        protos.append(img + rng.normal(0, 0.3, (side, side)))
    protos = np.stack(protos)
    labels = rng.integers(0, C, n).astype(np.int32)
    shift = rng.integers(-2, 3, (n, 2))
    rows = np.arange(side)
    ri = (rows[None, :] - shift[:, :1]) % side          # np.roll, per image
    ci = (rows[None, :] - shift[:, 1:]) % side
    imgs = protos[labels][np.arange(n)[:, None, None], ri[:, :, None],
                          ci[:, None, :]]
    imgs = imgs + rng.normal(0, 0.22, (n, side, side))
    imgs = (imgs - imgs.min()) / (imgs.max() - imgs.min())
    return imgs.reshape(n, side * side).astype(np.float32), labels


def alter(batch, cfg: dict):
    """The batch with the first row's label altered (to the next class):
    a fault for the comparison to catch."""
    owner_x, labels = np.array(batch[0]), np.array(batch[1])
    labels[0] = (labels[0] + 1) % cfg["n_classes"]
    return owner_x, labels
