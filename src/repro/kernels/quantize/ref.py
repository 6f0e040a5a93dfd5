"""Pure-jnp oracle for per-row symmetric int8 quantization."""
from __future__ import annotations

import jax.numpy as jnp


def quantize_int8_ref(x):
    """x: (T, K).  Returns (values int8 (T, K), scales f32 (T, 1))."""
    x = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_int8_ref(q, scale):
    return q.astype(jnp.float32) * scale


def quantize_pack_int8_ref(x):
    """Oracle for the quantize+pack wire frame: uint8 (T, K+4) wire
    frame — int8 values bitcast to uint8 plus the 4 little-endian bytes
    of the f32 row scale."""
    import jax
    q, scale = quantize_int8_ref(x)
    qb = jax.lax.bitcast_convert_type(q, jnp.uint8)
    sb = jax.lax.bitcast_convert_type(
        scale.astype(jnp.float32), jnp.uint8).reshape(q.shape[0], 4)
    return jnp.concatenate([qb, sb], axis=-1)


def unpack_int8_ref(packed):
    """Inverse of the wire frame: (values int8 (T, K), scales f32 (T, 1))."""
    import numpy as np
    packed = np.asarray(packed)
    k = packed.shape[-1] - 4
    q = packed[:, :k].view(np.int8)
    scale = np.ascontiguousarray(packed[:, k:]).view("<f4")
    return q, scale
