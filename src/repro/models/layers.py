"""Basic layers: norms, embeddings, rotary positions, activations.

All modules are functional: ``*_init(key, ...) -> params`` plus a pure
apply function.  Params are stored in the arch's ``param_dtype`` (fp32 by
default) and cast to ``compute_dtype`` (bf16) at use.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def cast(x, dtype):
    return x.astype(dtype) if x.dtype != dtype else x


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_init(d: int, kind: str, dtype=jnp.float32):
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype)}  # gemma-style (1 + scale)
    if kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    raise ValueError(kind)


def norm_apply(params, x, kind: str, eps: float = 1e-5):
    # reductions in fp32 for stability
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
        y = y * (1.0 + params["scale"].astype(jnp.float32))
    elif kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    else:
        raise ValueError(kind)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense / embedding
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}


def dense_apply(params, x):
    w = cast(params["w"], x.dtype)
    return x @ w


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return {"table": jax.random.normal(key, (vocab, d), dtype) * 0.02}


def embed_apply(params, ids, dtype):
    return cast(params["table"], dtype)[ids]


def softcap(x, cap: float):
    """Gemma2 soft-capping: cap * tanh(x / cap)."""
    if cap <= 0.0:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def act(x, kind: str):
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if kind == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE / M-RoPE / sin-cos)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_correction_range(dim: int, theta: float, yarn):
    """YaRN's (low, high) frequency slots: below ``low`` a slot keeps its
    frequency, above ``high`` it is divided by the factor (DeepSeek-V2's
    ``yarn_find_correction_range``)."""
    def slot(n_rot):
        return (dim * np.log(yarn.original_max_position_embeddings
                             / (n_rot * 2 * np.pi))) / (2 * np.log(theta))
    low = int(np.floor(slot(yarn.beta_fast)))
    high = int(np.ceil(slot(yarn.beta_slow)))
    return max(low, 0), min(high, dim - 1)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * float(np.log(factor)) + 1.0


def yarn_freqs(dim: int, theta: float, yarn):
    """YaRN rotary frequencies (dim/2,): a linear ramp over the correction
    range from the plain frequencies to those divided by ``factor``."""
    plain = rope_freqs(dim, theta)
    low, high = yarn_correction_range(dim, theta, yarn)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / yarn.factor * ramp


def apply_rope(x, positions, theta: float, freqs=None):
    """x: (..., S, H, head_dim); positions: broadcastable to (..., S).
    ``freqs`` (head_dim/2,) replaces the plain ``theta`` frequencies
    (YaRN).  Pairs are (i, i + head_dim/2)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                     # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    ang = ang[..., None, :]                               # (..., S, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float, sections=(2, 3, 3)):
    """Qwen2-VL M-RoPE: rotary with 3 position streams (t, h, w).

    ``positions3``: (..., S, 3).  The head_dim/2 frequency slots are split
    into ``sections`` proportional groups fed by the t/h/w position streams.
    """
    hd = x.shape[-1]
    half = hd // 2
    freqs = rope_freqs(hd, theta)                         # (half,)
    total = sum(sections)
    bounds = np.cumsum([int(half * s / total) for s in sections])
    bounds[-1] = half
    slot = np.zeros((half,), np.int32)
    prev = 0
    for i, b in enumerate(bounds):
        slot[prev:b] = i
        prev = b
    slot = jnp.asarray(slot)                              # (half,) in {0,1,2}
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),
        jnp.broadcast_to(slot, positions3.shape[:-1] + (half,)), axis=-1)
    ang = pos * freqs                                     # (..., S, half)
    ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sincos_positions(positions, d: int):
    """Whisper-style fixed sinusoidal position embeddings. (..., S) -> (..., S, d)."""
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (np.log(10000.0) / max(half - 1, 1)))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
