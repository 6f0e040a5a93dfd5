"""The PyVertical cut layer as one fused Pallas kernel.

The data scientist combines the owners' cut activations and feeds them to
the trunk's input projection:

    concat:   out = concat_feat(z_0 .. z_{P-1}) @ W,  W: (P*k, d)
              = sum_p  z_p @ W_p                      (block-row matmul)
    sum/mean: out = (sum_p z_p) @ W_0  [/ P]

Fusing the combine into the matmul means the (T, P*k) concatenated
representation is never materialized in HBM — on TPU the owner dim folds
into the contraction loop.

Grid: (M_tiles, N_tiles, P * K_tiles); the last axis is sequential and
accumulates into a VMEM f32 scratch tile; owner index p = c // K_tiles
selects both the z block row and the W block row via the index_maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cut_kernel(z_ref, w_ref, o_ref, acc_ref, *, combine: str, n_owners: int,
                inv_p: float):
    c = pl.program_id(2)
    n_c = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    z = z_ref[0]                                  # (Bm, Bk)
    if combine == "mean":
        z = z * inv_p
    acc_ref[...] += jax.lax.dot(z.astype(jnp.float32),
                                w_ref[0].astype(jnp.float32),
                                preferred_element_type=jnp.float32)

    @pl.when(c == n_c - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def cut_fusion_raw(z, w, *, combine: str = "concat",
                   block_m: int = 128, block_n: int = 128,
                   block_k: int = 128, interpret: bool = False):
    """z: (P, T, k) stacked owner cut activations; w: (P, k, d) block rows
    of the trunk input projection (all owners share W_0 for sum/mean).

    Returns (T, d) = combine(z) @ W without materializing the combine.
    """
    P, T, K = z.shape
    D = w.shape[-1]
    bm, bn, bk = min(block_m, T), min(block_n, D), min(block_k, K)
    nm, nn, nk = -(-T // bm), -(-D // bn), -(-K // bk)
    if nm * bm - T or nk * bk - K:
        z = jnp.pad(z, ((0, 0), (0, nm * bm - T), (0, nk * bk - K)))
    if nk * bk - K or nn * bn - D:
        w = jnp.pad(w, ((0, 0), (0, nk * bk - K), (0, nn * bn - D)))

    kernel = functools.partial(_cut_kernel, combine=combine, n_owners=P,
                               inv_p=1.0 / P)
    out = pl.pallas_call(
        kernel,
        grid=(nm, nn, P * nk),
        in_specs=[
            # z block: owner p = c // nk, k block = c % nk
            pl.BlockSpec((1, bm, bk),
                         lambda i, j, c, nk=nk: (c // nk, i, c % nk)),
            # W block row for that owner (sum/mean read row 0)
            pl.BlockSpec((1, bk, bn),
                         (lambda i, j, c, nk=nk: (0, c % nk, j))
                         if combine in ("sum", "mean") else
                         (lambda i, j, c, nk=nk: (c // nk, c % nk, j))),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nm * bm, nn * bn), z.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(z, w[:1] if combine in ("sum", "mean") else w)
    return out[:T, :D]
