"""jit'd wrapper for the cut-payload int8 quantizer.

``interpret=None`` (the default) resolves to interpreter mode off-TPU so
the transport codec works identically on CPU CI and real hardware.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.quantize.kernel import (quantize_int8_raw,
                                           quantize_pack_int8_raw)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def _quantize_jit(x, *, block_m: int, interpret: bool):
    return quantize_int8_raw(x, block_m=block_m, interpret=interpret)


def quantize_int8(x, *, block_m: int = 256, interpret=None):
    """x: (T, K) float.  Returns (values int8 (T, K), scales f32 (T, 1))."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _quantize_jit(x, block_m=block_m, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def _quantize_pack_jit(x, *, block_m: int, interpret: bool):
    return quantize_pack_int8_raw(x, block_m=block_m, interpret=interpret)


def quantize_pack_int8(x, *, block_m: int = 256, interpret=None):
    """x: (T, K) float.  Returns the uint8 (T, K+4) wire frame: int8
    values + bitcast little-endian f32 row scale, quantized by the
    kernel and packed in the same jitted program."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _quantize_pack_jit(x, block_m=block_m, interpret=interpret)
