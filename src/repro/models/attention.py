"""Attention: GQA, causal/local/bidirectional masks, softcap, KV caches.

The core ``attention`` function is flash-style: it never materializes the
full (Sq, Skv) score matrix when Skv is large — it scans over KV chunks
with an online-softmax accumulator.  This is also the jnp oracle for the
Pallas ``block_attention`` kernel (kernels/block_attention/ref.py wraps it).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers

NEG_INF = -2.0 ** 30  # large-but-finite: keeps padded-row softmax NaN-free


def _mask(q_pos, kv_pos, kind: str, window: int, kv_len):
    """Boolean mask (..., Sq, Skv): True = attend."""
    pq = q_pos[..., :, None]
    pk = kv_pos[..., None, :]
    if kind == "bidir":
        m = jnp.ones(jnp.broadcast_shapes(pq.shape, pk.shape), bool)
    elif kind == "causal":
        m = pk <= pq
    elif kind == "local":
        m = (pk <= pq) & (pk > pq - window)
    else:
        raise ValueError(kind)
    if kv_len is not None:
        m = m & (pk < kv_len)
    return m


def attention(q, k, v, *, kind: str = "causal", window: int = 0,
              softcap: float = 0.0, q_offset=0, kv_len=None,
              chunk: int = 1024, scale: Optional[float] = None):
    """GQA attention.

    q, k: (B, Sq|Skv, nh|nkv, hd);  v: (B, Skv, nkv, hv);  nh % nkv == 0
    (the value width ``hv`` may differ from the query/key width, as in
    MLA).  ``scale`` defaults to ``hd ** -0.5``.
    ``q_offset``: position of q[0] (decode: current length-1).
    ``kv_len``: number of valid cache entries (decode), None = all valid.
    """
    B, Sq, nh, hd = q.shape
    Skv, nkv, hv = k.shape[1], k.shape[2], v.shape[-1]
    g = nh // nkv
    scale = scale if scale is not None else hd ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, nkv, g, hd)
    q_pos = q_offset + jnp.arange(Sq)

    # Direct (non-chunked) path: small KV, or decode (Sq == 1, where the
    # score tensor is linear in Skv and chunking would only force XLA to
    # gather a sequence-sharded cache — flash-decode stays sharded here).
    if Skv <= chunk or Sq == 1:
        s = _scores(qf, k, q_pos, jnp.arange(Skv), kind, window, softcap,
                    kv_len)                                # (B,nkv,g,Sq,Skv)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
        return o.reshape(B, Sq, nh, hv).astype(q.dtype)

    # --- online-softmax scan over KV chunks (flash-style) -----------------
    n_chunks = -(-Skv // chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, n_chunks, chunk, nkv, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, nkv, hv).transpose(1, 0, 2, 3, 4)
    if kv_len is None and isinstance(q_offset, int):
        # training and cache-free prefill: the flash backward below
        o = _flash(qf, kc, vc, q_offset, kind, window, softcap)
    else:
        o, _ = _flash_forward(qf, kc, vc, q_pos, kind, window, softcap,
                              kv_len)
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, nh, hv)
    return o.astype(q.dtype)


def _scores(qf, k, q_pos, kv_pos, kind, window, softcap, kv_len):
    """Masked (and soft-capped) scores (B, nkv, g, Sq, Skv) of the scaled
    float32 queries (B, Sq, nkv, g, hd) against keys (B, Skv, nkv, hd)."""
    s = jnp.einsum("bqkgh,bskh->bkgqs", qf, k.astype(jnp.float32))
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    m = _mask(q_pos, kv_pos, kind, window, kv_len)         # (Sq, Skv)
    return jnp.where(m[None, None, None], s, NEG_INF)


def _flash_forward(qf, kc, vc, q_pos, kind, window, softcap, kv_len):
    """The online softmax over KV chunks kc (n, B, chunk, nkv, hd), vc
    (n, B, chunk, nkv, hv).  Returns (out (B, nkv, g, Sq, hv), each row's
    log-sum-exp (B, nkv, g, Sq))."""
    n_chunks, B, chunk, nkv, _ = kc.shape
    Sq, g, hv = qf.shape[1], qf.shape[3], vc.shape[-1]

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        # the barrier ties the chunk's index to its keys: under
        # ``jax.checkpoint`` the mask, which depends on the index alone,
        # would otherwise be computed ahead for every chunk and kept,
        # broadcast to the scores' full shape
        k_i, v_i, idx = jax.lax.optimization_barrier(xs)
        s = _scores(qf, k_i, q_pos, idx * chunk + jnp.arange(chunk), kind,
                    window, softcap, kv_len)               # (B,nkv,g,Sq,chunk)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_cur[..., None])
        corr = jnp.exp(m_prev - m_cur)
        l_cur = l_prev * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskh->bkgqh", p, v_i.astype(jnp.float32))
        acc = acc * corr[..., None] + pv
        return (m_cur, l_cur, acc), None

    init = (jnp.full((B, nkv, g, Sq), NEG_INF, jnp.float32),
            jnp.zeros((B, nkv, g, Sq), jnp.float32),
            jnp.zeros((B, nkv, g, Sq, hv), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(body, init, (kc, vc, jnp.arange(n_chunks)))
    l = jnp.maximum(l, 1e-30)
    return acc / l[..., None], m + jnp.log(l)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(qf, kc, vc, q_offset, kind, window, softcap):
    """``_flash_forward`` with a flash backward: it keeps the output and
    each row's log-sum-exp, not the chunks' scores and probabilities
    (B x nh x Sq x Skv float32 a layer), and recomputes those chunk by
    chunk."""
    q_pos = q_offset + jnp.arange(qf.shape[1])
    return _flash_forward(qf, kc, vc, q_pos, kind, window, softcap, None)[0]


def _flash_fwd(qf, kc, vc, q_offset, kind, window, softcap):
    q_pos = q_offset + jnp.arange(qf.shape[1])
    o, lse = _flash_forward(qf, kc, vc, q_pos, kind, window, softcap, None)
    return o, (qf, kc, vc, o, lse)


#: KV positions per step of the flash backward, which holds a few
#: (B, nh, Sq, BWD_CHUNK) float32 arrays at once
BWD_CHUNK = 512


def _flash_bwd(q_offset, kind, window, softcap, res, do):
    qf, kc, vc, o, lse = res
    n, B, chunk, nkv, _ = kc.shape
    q_pos = q_offset + jnp.arange(qf.shape[1])
    delta = jnp.sum(do * o, axis=-1)                       # (B,nkv,g,Sq)
    if chunk > BWD_CHUNK and chunk % BWD_CHUNK == 0:
        split = chunk // BWD_CHUNK

        def sub(a):
            return a.reshape(n, B, split, BWD_CHUNK, *a.shape[3:]).transpose(
                0, 2, 1, 3, *range(4, a.ndim + 1)).reshape(
                    n * split, B, BWD_CHUNK, *a.shape[3:])

        def unsub(a):
            return a.reshape(n, split, B, BWD_CHUNK, *a.shape[3:]).transpose(
                0, 2, 1, 3, *range(4, a.ndim + 1)).reshape(
                    n, B, chunk, *a.shape[3:])
        kc, vc = sub(kc), sub(vc)
    else:
        unsub = None

    step = kc.shape[2]

    def body(dq, xs):
        k_i, v_i, idx = xs
        kf, vf = k_i.astype(jnp.float32), v_i.astype(jnp.float32)
        raw = jnp.einsum("bqkgh,bskh->bkgqs", qf, kf)
        s = softcap * jnp.tanh(raw / softcap) if softcap > 0.0 else raw
        m = _mask(q_pos, idx * step + jnp.arange(step), kind, window, None)
        s = jnp.where(m[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])
        dv = jnp.einsum("bkgqs,bkgqh->bskh", p, do)
        ds = p * (jnp.einsum("bkgqh,bskh->bkgqs", do, vf)
                  - delta[..., None])
        if softcap > 0.0:
            ds = ds * (1.0 - jnp.square(jnp.tanh(raw / softcap)))
        dq = dq + jnp.einsum("bkgqs,bskh->bqkgh", ds, kf)
        dk = jnp.einsum("bkgqs,bqkgh->bskh", ds, qf)
        return dq, (dk.astype(k_i.dtype), dv.astype(v_i.dtype))

    dq, (dk, dv) = jax.lax.scan(body, jnp.zeros_like(qf),
                                (kc, vc, jnp.arange(kc.shape[0])))
    if unsub is not None:
        dk, dv = unsub(dk), unsub(dv)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + GQA) and KV cache
# ---------------------------------------------------------------------------


def attn_init(key, cfg, dtype=jnp.float32):
    if cfg.mla is not None:
        return mla_init(key, cfg, dtype)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": layers.dense_init(ks[0], d, qd, dtype),
        "wk": layers.dense_init(ks[1], d, kvd, dtype),
        "wv": layers.dense_init(ks[2], d, kvd, dtype),
        "wo": layers.dense_init(ks[3], qd, d, dtype),
    }


def init_kv_cache(batch: int, s_max: int, n_kv: int, head_dim: int,
                  dtype=jnp.bfloat16, v_dim: int = 0):
    return {"k": jnp.zeros((batch, s_max, n_kv, head_dim), dtype),
            "v": jnp.zeros((batch, s_max, n_kv, v_dim or head_dim), dtype)}


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2, no query compression)
# ---------------------------------------------------------------------------


def mla_init(key, cfg, dtype=jnp.float32):
    m, d, h = cfg.mla, cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 4)
    return {
        "wq": layers.dense_init(ks[0], d, h * m.qk_head_dim, dtype),
        "wkv_a": layers.dense_init(ks[1], d,
                                   m.kv_lora_rank + m.qk_rope_head_dim,
                                   dtype),
        "kv_norm": layers.norm_init(m.kv_lora_rank, "rmsnorm", dtype),
        "wkv_b": layers.dense_init(ks[2], m.kv_lora_rank,
                                   h * (m.qk_nope_head_dim + m.v_head_dim),
                                   dtype),
        "wo": layers.dense_init(ks[3], h * m.v_head_dim, d, dtype),
    }


def mla_scale(cfg) -> float:
    """The softmax scale: qk_head_dim^-0.5, times YaRN's mscale squared."""
    scale = cfg.mla.qk_head_dim ** -0.5
    if cfg.yarn is not None and cfg.yarn.mscale_all_dim:
        scale *= layers.yarn_mscale(cfg.yarn.factor,
                                    cfg.yarn.mscale_all_dim) ** 2
    return scale


def _mla_rope(x, positions, cfg):
    """The rotary part of queries or the shared key, YaRN-scaled."""
    y = cfg.yarn
    if y is None:
        return layers.apply_rope(x, positions, cfg.rope_theta)
    freqs = layers.yarn_freqs(x.shape[-1], cfg.rope_theta, y)
    x = layers.apply_rope(x, positions, cfg.rope_theta, freqs)
    # the cos/sin factor (1 where mscale == mscale_all_dim)
    ms = (layers.yarn_mscale(y.factor, y.mscale)
          / layers.yarn_mscale(y.factor, y.mscale_all_dim))
    return x if ms == 1.0 else (x * ms).astype(x.dtype)


def mla_qkv(params, x, positions, cfg):
    """Per-head q, k (B, S, nh, nope + rope) and v (B, S, nh, v_head_dim):
    the latent down-projection and its norm, the one rotary key shared by
    every head, and the up-projection to per-head keys and values."""
    m, h = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    q = layers.dense_apply(params["wq"], x).reshape(B, S, h, m.qk_head_dim)
    q_nope, q_pe = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    ckv = layers.dense_apply(params["wkv_a"], x)
    latent, k_pe = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    latent = layers.norm_apply(params["kv_norm"], latent, "rmsnorm",
                               cfg.norm_eps)
    kv = layers.dense_apply(params["wkv_b"], latent).reshape(
        B, S, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    k_pe = k_pe[:, :, None, :]                            # (B, S, 1, rope)
    if positions is not None:
        q_pe = _mla_rope(q_pe, positions, cfg)
        k_pe = _mla_rope(k_pe, positions, cfg)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (B, S, h, m.qk_rope_head_dim))],
        axis=-1)
    return q, k, v


def update_kv_cache(cache, k_new, v_new, pos):
    """Write k/v (B, Sq, nkv, hd) at position ``pos`` (scalar)."""
    idx = (0, pos, 0, 0)
    return {"k": jax.lax.dynamic_update_slice(cache["k"],
                                              k_new.astype(cache["k"].dtype), idx),
            "v": jax.lax.dynamic_update_slice(cache["v"],
                                              v_new.astype(cache["v"].dtype), idx)}


def update_kv_cache_ring(cache, k_new, v_new, pos):
    """Ring-buffer write for window-trimmed caches (W slots, W = window):
    slot(p) = p mod W.  Sliding-window layers never need more than the
    last W tokens, so the cache holds exactly the window — the §Perf
    memory-term optimization for decode shapes.

    Decode (Sq == 1): write at slot pos %% W.
    Prefill (Sq >= W, pos == 0): keep only the last W tokens, rolled so
    element at slot i has position p ≡ i (mod W).
    """
    W = cache["k"].shape[1]
    Sq = k_new.shape[1]
    if Sq == 1:
        slot = jnp.asarray(pos) % W
        idx = (0, slot, 0, 0)
        return {"k": jax.lax.dynamic_update_slice(
                    cache["k"], k_new.astype(cache["k"].dtype), idx),
                "v": jax.lax.dynamic_update_slice(
                    cache["v"], v_new.astype(cache["v"].dtype), idx)}
    if Sq >= W:
        kt = jnp.roll(k_new[:, -W:], Sq % W, axis=1)
        vt = jnp.roll(v_new[:, -W:], Sq % W, axis=1)
        return {"k": kt.astype(cache["k"].dtype),
                "v": vt.astype(cache["v"].dtype)}
    # short prefill from position `pos` (assumed no wrap)
    return update_kv_cache(cache, k_new, v_new, pos)


def attn_apply(params, x, *, cfg, kind: str, positions=None, window: int = 0,
               cache=None, pos=None, kv_x=None, chunk: int = 1024):
    """Full attention sub-layer (no norm/residual — caller owns those).

    x: (B, Sq, d).  ``kv_x``: cross-attention source (B, Skv, d) — when
    given, k/v come from it and the mask is bidirectional.
    ``cache``/``pos``: decode-mode KV cache handling.
    Returns (out, new_cache).
    """
    B, Sq, _ = x.shape
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    scale = None
    if cfg.mla is not None:
        if kv_x is not None:
            raise ValueError("MLA has no cross-attention")
        with jax.named_scope("mla"):
            q, k, v = mla_qkv(params, x, positions, cfg)
        scale = mla_scale(cfg)
    else:
        q = layers.dense_apply(params["wq"], x).reshape(B, Sq, nh, hd)
        src = kv_x if kv_x is not None else x
        k = layers.dense_apply(params["wk"], src).reshape(
            B, src.shape[1], nkv, hd)
        v = layers.dense_apply(params["wv"], src).reshape(
            B, src.shape[1], nkv, hd)

    if kv_x is not None:
        kind = "bidir"

    if (positions is not None and cfg.rope != "none" and kv_x is None
            and cfg.mla is None):
        if cfg.rope == "mrope":
            q = layers.apply_mrope(q, positions, cfg.rope_theta)
            k = layers.apply_mrope(k, positions, cfg.rope_theta)
        elif cfg.rope == "rope":
            q = layers.apply_rope(q, positions, cfg.rope_theta)
            k = layers.apply_rope(k, positions, cfg.rope_theta)
        # sincos positions are added at the embedding, not rotary.

    q_offset, kv_len = 0, None
    if cache is not None:
        # window-trimmed ring cache: a local-attention layer whose cache
        # holds exactly `window` slots (slot = position mod W)
        ring = (kind == "local" and window > 0
                and cache["k"].shape[1] <= window)
        if ring:
            W = cache["k"].shape[1]
            cache = update_kv_cache_ring(cache, k, v, pos)
            if Sq == 1:
                # ring slots are an arbitrary permutation of the last
                # min(pos+1, W) positions — all inside the window, so the
                # mask is just slot validity (RoPE was applied at write).
                k, v = cache["k"], cache["v"]
                kind, window = "bidir", 0
                kv_len = jnp.minimum(pos + 1, W)
            # prefill: attend over the in-call k/v with the plain local
            # mask; the ring cache is storage for later decode steps.
        else:
            cache = update_kv_cache(cache, k, v, pos)
            k, v = cache["k"], cache["v"]
            q_offset = pos
            kv_len = pos + Sq

    with jax.named_scope("mla" if cfg.mla is not None else "attention"):
        out = attention(q, k.astype(q.dtype), v.astype(q.dtype), kind=kind,
                        window=window, softcap=cfg.attn_softcap,
                        q_offset=q_offset, kv_len=kv_len, chunk=chunk,
                        scale=scale)
        out = layers.dense_apply(params["wo"],
                                 out.reshape(B, Sq, nh * out.shape[-1]))
    return out, cache
