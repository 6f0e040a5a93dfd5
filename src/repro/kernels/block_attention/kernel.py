"""Flash-style attention Pallas kernel (TPU target).

Grid: (batch * n_q_heads, n_q_blocks, n_kv_blocks); the kv dim is the
innermost, sequential axis — the online-softmax state (m, l, acc) lives in
VMEM scratch and persists across kv iterations, the standard TPU flash
pattern.  GQA is resolved by the ops wrapper (kv heads broadcast to q
heads via the BlockSpec index_map, no materialized repeat).

VMEM working set per grid step:
    q (1, Bq, hd) + k,v (1, Bk, hd) + acc (Bq, hd) f32 + s (Bq, Bk) f32
with Bq = Bk = 128, hd <= 256 -> ~0.6 MB: comfortably inside the ~16 MB
VMEM budget; all matmul dims are 128-multiples (MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 kind: str, window: int, softcap: float, scale: float,
                 block_q: int, block_k: int, seq_q: int, seq_kv: int):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # structural skip of fully-masked kv blocks (the sparsity that makes
    # owner-local/sliding-window heads sub-quadratic)
    first_q = qb * block_q
    last_q = first_q + block_q - 1
    first_k = kb * block_k
    if kind == "causal":
        live = first_k <= last_q
    elif kind == "local":
        live = (first_k <= last_q) & (first_k + block_k > first_q - window)
    else:
        live = first_k >= 0  # always true, but keeps a traced bool

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale           # (Bq, hd)
        k = k_ref[0].astype(jnp.float32)                   # (Bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = first_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = first_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = (q_pos < seq_q) & (k_pos < seq_kv)
        if kind == "causal":
            mask &= k_pos <= q_pos
        elif kind == "local":
            mask &= (k_pos <= q_pos) & (k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev, acc = m_ref[...], l_ref[...], acc_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        corr = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=1)
        acc_ref[...] = acc * corr[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(kb == n_kb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_flat(q, k, v, *, kind: str = "causal", window: int = 0,
                         softcap: float = 0.0, scale=None, group: int = 1,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False):
    """q: (B*nh, Sq, hd); k, v: (B*nkv, Skv, hd) with nh = group * nkv.

    The kv index_map folds GQA: q row ``b`` reads kv row ``b // group``.
    """
    BH, Sq, hd = q.shape
    Skv = k.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    bq, bk = min(block_q, Sq), min(block_k, Skv)
    nq = -(-Sq // bq)
    nk = -(-Skv // bk)
    if nq * bq - Sq:
        q = jnp.pad(q, ((0, 0), (0, nq * bq - Sq), (0, 0)))
    if nk * bk - Skv:
        k = jnp.pad(k, ((0, 0), (0, nk * bk - Skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, nk * bk - Skv), (0, 0)))

    kernel = functools.partial(
        _attn_kernel, kind=kind, window=window, softcap=softcap,
        scale=scale, block_q=bq, block_k=bk, seq_q=Sq, seq_kv=Skv)

    out = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bk, hd),
                         lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, nq * bq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # m
            pltpu.VMEM((bq,), jnp.float32),       # l
            pltpu.VMEM((bq, hd), jnp.float32),    # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :Sq]
