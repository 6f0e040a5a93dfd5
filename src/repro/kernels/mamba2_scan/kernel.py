"""Chunked SSD (Mamba2) scan as a Pallas kernel.

Grid: (batch, ssd_heads, n_chunks); the chunk axis is the innermost,
sequential axis and the inter-chunk SSM state (N, P) f32 lives in VMEM
scratch, carried across chunk iterations — the same sequential-grid +
VMEM-carry structure the flash kernel uses, which is how the recurrence
maps onto the TPU (no HBM round-trip for the state between chunks).

Per-step VMEM: x (L, P) + B,C (L, N) + decay matrix (L, L) f32 + state
(N, P) f32; with L = 128, N = 64, P = 64: ~0.2 MB.  The (L, L) intra-chunk
quadratic term and the (N, P) state updates are MXU matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, dt_ref, b_ref, c_ref, y_ref, state_out_ref,
                state_ref, *, L: int):
    c = pl.program_id(2)
    n_c = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (L, P)
    a = a_ref[0, 0, :, 0]                        # (L,)  = dt * A  (<= 0)
    dt = dt_ref[0, 0, :, 0]                      # (L,)
    Bv = b_ref[0, 0].astype(jnp.float32)         # (L, N)
    Cv = c_ref[0, 0].astype(jnp.float32)         # (L, N)

    cum = jnp.cumsum(a)                          # (L,)
    total = cum[L - 1]
    # intra-chunk: M_ij = (C_i . B_j) exp(cum_i - cum_j) dt_j,  j <= i
    diff = cum[:, None] - cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # mask before exp (j > i diffs are positive and would overflow)
    ldec = jnp.exp(jnp.where(jj <= ii, diff, -jnp.inf))
    scores = jax.lax.dot_general(Cv, Bv, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    M = scores * ldec * dt[None, :]
    y = jax.lax.dot(M, x, preferred_element_type=jnp.float32)
    # inter-chunk: y += (C_i exp(cum_i)) @ state
    y += jax.lax.dot(Cv * jnp.exp(cum)[:, None], state_ref[...],
                     preferred_element_type=jnp.float32)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update: S <- exp(total) S + sum_j exp(total - cum_j) dt_j B_j x_j
    w = jnp.exp(total - cum) * dt                # (L,)
    upd = jax.lax.dot_general(Bv * w[:, None], x, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    state_ref[...] = jnp.exp(total) * state_ref[...] + upd

    @pl.when(c == n_c - 1)
    def _fin():
        state_out_ref[0, 0] = state_ref[...]


def ssd_scan_raw(x, a, dt, B_in, C_in, *, chunk: int = 128,
                 interpret: bool = False):
    """x: (B, H, S, P); a = dt*A: (B, H, S, 1); dt: (B, H, S, 1);
    B_in, C_in: (B, G, S, N) — G groups, head h reads group h // (H//G).

    Returns (y (B, H, S, P), final_state (B, H, N, P) f32)."""
    Bb, H, S, P = x.shape
    G, N = B_in.shape[1], B_in.shape[3]
    rep = H // G
    L = min(chunk, S)
    nc = -(-S // L)
    pad = nc * L - S
    if pad:
        zp = ((0, 0), (0, 0), (0, pad), (0, 0))
        x, a, dt = jnp.pad(x, zp), jnp.pad(a, zp), jnp.pad(dt, zp)
        B_in, C_in = jnp.pad(B_in, zp), jnp.pad(C_in, zp)

    kernel = functools.partial(_ssd_kernel, L=L)
    y, state = pl.pallas_call(
        kernel,
        grid=(Bb, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, L, N),
                         lambda b, h, c, r=rep: (b, h // r, c, 0)),
            pl.BlockSpec((1, 1, L, N),
                         lambda b, h, c, r=rep: (b, h // r, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, nc * L, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, a, dt, B_in, C_in)
    return y[:, :, :S], state
