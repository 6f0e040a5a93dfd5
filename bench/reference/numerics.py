"""Matrix products of the plain references, in a stated precision.

Every reference computes in float32 with elementwise work in float32.
Its matrix products go through one ``Numerics`` object, so the same
reference can run as the yardstick (``float32``: float32 operands, the
``highest`` matmul precision) or as a control one precision step below
what a configuration states:

- ``fp8``: computing in float8_e4m3fn (saturated at +-448, no
  per-tensor scale), the step below bfloat16: the operands and the
  result of every product rounded to it, and so is every activation a
  reference holds in its configuration's compute type (``act``);
  products are summed in float32;
- ``bfloat16``: the same in bfloat16, the step below float32 at the
  default precision;
- ``high``: the three bfloat16 passes of XLA's ``high`` precision,
  written out: each operand split into a bfloat16 head and a bfloat16
  remainder, and the products head.head + head.rest + rest.head summed
  in float32 -- the step below float32 at ``highest``.  Written out
  rather than asked of the backend, so that it reads the same on every
  platform (a CPU computes ``high`` as float32).

Every rounding passes the gradient straight through in float32: a
control rounds the operands of every product, those the backward pass
saves too, but no cotangent is rounded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

FP8_MAX = 448.0


def _rounded(x, dtype, bound=None):
    """``x`` rounded to ``dtype`` in value; its gradient passes straight
    through in float32, so a control rounds every product's operands
    (the saved ones of the backward pass too) but no cotangent
    underflows in a format without a scale."""
    r = x if bound is None else jnp.clip(x, -bound, bound)
    # the barrier keeps XLA from folding the round trip through the
    # narrow type away, as it may where excess precision is allowed
    # (the TPU compiler does)
    r = lax.optimization_barrier(r.astype(dtype)).astype(jnp.float32)
    return x + jax.lax.stop_gradient(r - x)


def _fp8(x):
    return _rounded(x, jnp.float8_e4m3fn, FP8_MAX)


def _bf16(x):
    return _rounded(x, jnp.bfloat16)


def _split_bf16(x):
    """``x`` as (head, rest): both bfloat16 in value, head + rest within
    2^-16 of ``x``; the gradient passes straight through the head."""
    head = _bf16(x)
    rest = lax.stop_gradient(lax.optimization_barrier(
        (x - head).astype(jnp.bfloat16)).astype(jnp.float32))
    return head, rest


_ROUND = {"float32": None, "high": None, "fp8": _fp8, "bfloat16": _bf16}


class Numerics:
    """``einsum``/``dot`` in one named precision (see the module doc)."""

    def __init__(self, name: str = "float32"):
        if name not in _ROUND:
            raise ValueError(f"unknown precision {name!r}; "
                             f"known: {sorted(_ROUND)}")
        self.name = name
        self._round = _ROUND[name]

    def _operand(self, x):
        x = x.astype(jnp.float32)
        return x if self._round is None else self._round(x)

    def act(self, x):
        """An activation held in the compute type: ``x`` rounded to it
        (float32 for ``float32`` and ``high``)."""
        return self._operand(x)

    def _product(self, f, a, b):
        """``f(a, b)``, a bilinear product, in this precision."""
        if self.name != "high":
            return self.act(f(self._operand(a), self._operand(b)))
        (ah, al), (bh, bl) = (_split_bf16(x.astype(jnp.float32))
                              for x in (a, b))
        return f(ah, bh) + f(ah, bl) + f(al, bh)

    def einsum(self, spec: str, a, b):
        return self._product(lambda x, y: jnp.einsum(
            spec, x, y, precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32), a, b)

    def dot(self, a, b):
        return self._product(lambda x, y: jnp.dot(
            x, y, precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32), a, b)


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (more than 32 bits
    fold in as a second word)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0: {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    hi = seed >> 32
    return jax.random.fold_in(key, hi) if hi else key
