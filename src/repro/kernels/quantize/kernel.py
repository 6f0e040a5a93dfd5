"""Per-row symmetric int8 quantization as a Pallas TPU kernel.

The cut-layer payload is the only tensor that crosses the party boundary,
so quantizing it on-device before the send is the protocol's bandwidth
lever (transport codec ``int8``).  One grid step handles a (block_m, K)
row block: the row absmax, the scale (absmax / 127), and the rounded int8
values are all produced in a single VMEM pass — the f32 activation never
returns to HBM.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                    # (bm, K)
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)  # (bm, 1)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.astype(jnp.float32)


def quantize_int8_raw(x, *, block_m: int = 256, interpret: bool = False):
    """x: (T, K) float.  Returns (values int8 (T, K), scales f32 (T, 1))
    with per-row symmetric scaling: ``x ~= values * scales``."""
    T, K = x.shape
    bm = min(block_m, T)
    nm = -(-T // bm)
    if nm * bm - T:
        x = jnp.pad(x, ((0, nm * bm - T), (0, 0)))
    q, s = pl.pallas_call(
        _quantize_kernel,
        grid=(nm,),
        in_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bm, K), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nm * bm, K), jnp.int8),
                   jax.ShapeDtypeStruct((nm * bm, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)
    return q[:T], s[:T]


def quantize_pack_int8_raw(x, *, block_m: int = 256,
                           interpret: bool = False):
    """x: (T, K) float.  Returns the wire frame: a uint8 (T, K+4) array
    whose first K columns are the per-row symmetric int8 values and whose
    trailing 4 columns are the little-endian bytes of the f32 row scale
    (the transport's ``int8`` codec ships this buffer as-is).

    The kernel quantizes; the byte packing is plain XLA around it.  The
    TPU's Mosaic compiler refuses a bitwidth-changing bitcast (f32 ->
    4 x uint8) inside a kernel, and the 4-byte scale column would be an
    unaligned lane store, so neither happens in VMEM."""
    q, scale = quantize_int8_raw(x, block_m=block_m, interpret=interpret)
    qb = jax.lax.bitcast_convert_type(q, jnp.uint8)
    sb = jax.lax.bitcast_convert_type(scale, jnp.uint8)    # (T, 1, 4)
    return jnp.concatenate([qb, sb.reshape(q.shape[0], 4)], axis=-1)
