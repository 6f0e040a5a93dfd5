"""Mixture-of-experts FFN: one router, and two ways to compute the routed
experts' part.

The router (``route``): softmax over every routed expert (float32 at the
highest precision), greedy top-k, the weights renormalized or not as the
configuration says.

Capacity layer (every expert held): GShard/Switch-style dispatch adapted
for TPU: per-expert capacity ``C = ceil(T * k / E * capacity_factor)``,
scatter dispatch to an (E, C, d) buffer, batched expert matmuls (einsum
over the expert dim — this is what expert-parallel sharding over the
"model" axis partitions), gather combine.  Overflowing tokens are dropped
(their choice contributes zero), the standard capacity trade-off.

Share layer (``moe_cfg.n_held > 0``): the layer holds a contiguous block
of the routed experts, as one chip of an expert-parallel deployment
does.  It routes over all of them and computes, for every choice that
lands on a held expert, that expert's weighted output: the choices are
sorted by expert and the grouped SwiGLU runs as ``jax.lax.ragged_dot``,
with no capacity and no dropped choice, one block of tokens at a time
(``SHARE_BLOCK_TOKENS``, each block recomputed in the backward pass).
Choices routed to experts held elsewhere add nothing here.  Holding every expert, it is the uncut,
no-drop layer.

Shared experts run on every token.  Also returns the balance loss:
Switch-style (f_e of first choices x P_e x E) or DeepSeek-V2's
sequence-wise one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers, mlp as mlp_mod
from repro.sharding.specs import constrain

#: Tokens of a block of the share layer: its buffers are those of one
#: block at a time (at 4,096 tokens and top-6, 24,576 rows of the hidden
#: width).
SHARE_BLOCK_TOKENS = 4096

#: The share layer's counters, summed over layers: choices routed to a
#: held expert, rows of the buffer that the gather, the masks, the
#: activation and the scatter run over (``T * min(K, H)``, however many
#: choices land here), and each layer's largest and mean held-expert
#: load.
MOE_STATS = ("moe_routed", "moe_rows", "moe_peak", "moe_mean")


def moe_stats_zero():
    return {k: jnp.zeros((), jnp.float32) for k in MOE_STATS}


def moe_init(key, d: int, moe_cfg, mlp_kind: str, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    e, de = moe_cfg.n_local, moe_cfg.d_expert
    s_in = 1.0 / np.sqrt(d)
    s_out = 1.0 / np.sqrt(de)
    p = {
        "router": layers.dense_init(ks[0], d, moe_cfg.n_experts, dtype,
                                    scale=0.02),
        "w_in": jax.random.normal(ks[1], (e, d, de), dtype) * s_in,
        "w_out": jax.random.normal(ks[2], (e, de, d), dtype) * s_out,
    }
    if mlp_kind in ("swiglu", "geglu"):
        p["w_gate"] = jax.random.normal(ks[3], (e, d, de), dtype) * s_in
    if moe_cfg.n_shared:
        d_sh = moe_cfg.n_shared * moe_cfg.d_shared
        p["shared"] = mlp_mod.mlp_init(ks[4], d, d_sh, mlp_kind, dtype)
    return p


def capacity(n_tokens: int, moe_cfg) -> int:
    c = int(n_tokens * moe_cfg.top_k / moe_cfg.n_experts
            * moe_cfg.capacity_factor)
    # large capacities round to 2048 so the capacity dim shards cleanly
    # over the 16-way data axis (expert-parallel x capacity-parallel)
    if c > 2048:
        return -(-c // 2048) * 2048
    return max(8, -(-c // 8) * 8)


def route(x, router, moe_cfg):
    """THE router of every expert layer.  x: (T, d) -> (probs (T, E),
    top_w (T, K), top_e (T, K)): a softmax over all ``n_experts`` in
    float32 at the highest precision, greedy top-k, the weights
    renormalized over the k only where ``norm_topk``."""
    with jax.named_scope("router"):
        logits = jnp.dot(x.astype(jnp.float32),
                         router["w"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, moe_cfg.top_k)
        if moe_cfg.norm_topk:
            top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True),
                                        1e-9)
    return probs, top_w, top_e


def balance_loss(probs, top_e, moe_cfg, batch: int):
    """The balance loss from the router's outputs over ``batch``
    sequences of T / batch tokens.

    ``switch``: E * sum_e f_e P_e with f_e the share of first choices.
    ``seq`` (DeepSeek-V2): per sequence, f_i = E / (K S) * #{t : i in
    top-K(t)} and P_i = mean_t s_{i,t}; alpha * sum_i f_i P_i, averaged
    over the sequences."""
    E, K = moe_cfg.n_experts, moe_cfg.top_k
    if moe_cfg.aux_kind == "seq":
        S = probs.shape[0] // batch
        counts = jnp.sum(jax.nn.one_hot(top_e.reshape(batch, S * K), E,
                                        dtype=jnp.float32), axis=1)
        f = counts * (E / (K * S))                        # (batch, E)
        p = jnp.mean(probs.reshape(batch, S, E), axis=1)
        return moe_cfg.aux_loss_weight * jnp.mean(jnp.sum(f * p, -1))
    if moe_cfg.aux_kind != "switch":
        raise ValueError(f"unknown balance loss {moe_cfg.aux_kind!r}")
    f_e = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32),
                   axis=0)
    p_e = jnp.mean(probs, axis=0)
    return E * jnp.sum(f_e * p_e) * moe_cfg.aux_loss_weight


def _expert_act(h, g, mlp_kind: str):
    if mlp_kind in ("swiglu", "geglu"):
        g = jax.nn.silu(g) if mlp_kind == "swiglu" else jax.nn.gelu(
            g, approximate=True)
        return g * h
    if mlp_kind == "gelu":
        return jax.nn.gelu(h, approximate=True)
    if mlp_kind == "relu2":
        r = jax.nn.relu(h)
        return r * r
    raise ValueError(mlp_kind)


def moe_apply(params, x, moe_cfg, mlp_kind: str, with_stats: bool = False):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar), and the share
    layer's counters (``MOE_STATS``) when ``with_stats``."""
    if moe_cfg.share:
        out, aux, stats = _moe_apply_share(params, x, moe_cfg, mlp_kind)
        return (out, aux, stats) if with_stats else (out, aux)
    if moe_cfg.dispatch_groups > 1:
        out, aux = _moe_apply_grouped(params, x, moe_cfg, mlp_kind)
    else:
        out, aux = _moe_apply_capacity(params, x, moe_cfg, mlp_kind)
    return (out, aux, {}) if with_stats else (out, aux)


def _held_block(params, xf, top_w, top_e, moe_cfg, mlp_kind: str):
    """The held experts' weighted outputs for one block of tokens.
    xf: (T, d), top_w/top_e: (T, K) -> (out (T, d), sizes (H,))."""
    T, d = xf.shape
    K, H = moe_cfg.top_k, moe_cfg.n_held
    # each choice's held expert (0..H-1), H where it is held elsewhere;
    # a stable sort groups the held choices by expert, in front
    local = top_e - moe_cfg.held_first
    key = jnp.where((local >= 0) & (local < H), local, H).reshape(T * K)
    order = jnp.argsort(key, stable=True)
    # a token chooses an expert once: at most T * min(K, H) choices land
    # here, so no choice is dropped
    m = T * min(K, H)
    sel = order[:m]
    sizes = jnp.bincount(key, length=H + 1)[:H].astype(jnp.int32)
    valid = (jnp.arange(m) < jnp.sum(sizes))[:, None]
    tok = sel // K
    # rows past the groups are outside every expert: the grouped matmul
    # leaves them unwritten, so they are masked on the way in and out
    # (and so are their gradients)
    xs = jnp.where(valid, xf[tok], 0)
    cdt = xs.dtype

    def grouped(a, w):
        return jax.lax.ragged_dot(a, layers.cast(w, cdt), sizes)

    h = grouped(xs, params["w_in"])
    g = (grouped(xs, params["w_gate"])
         if mlp_kind in ("swiglu", "geglu") else None)
    a = jnp.where(valid, _expert_act(h, g, mlp_kind), 0)
    y = jnp.where(valid, grouped(a, params["w_out"]), 0)
    w = top_w.reshape(T * K)[sel][:, None].astype(cdt)
    # back to choice order, then each token's choices summed
    out = jnp.zeros((T * K, d), cdt).at[sel].set(
        y * w, unique_indices=True).reshape(T, K, d).sum(1)
    return out, sizes


def _moe_apply_share(params, x, moe_cfg, mlp_kind: str):
    B, S, d = x.shape
    T = B * S
    K, H = moe_cfg.top_k, moe_cfg.n_held
    xf = x.reshape(T, d)
    probs, top_w, top_e = route(xf, params["router"], moe_cfg)

    with jax.named_scope("held_experts"):
        # the buffer holds T * min(K, H) rows, min(K, H) for each token
        # however few choices land here: blocks of at most
        # SHARE_BLOCK_TOKENS tokens, each recomputed in the backward pass,
        # keep one block's buffer live at a time
        n = -(-T // SHARE_BLOCK_TOKENS)
        if T % n:
            n = 1

        @jax.checkpoint
        def block(args):
            return _held_block(params, *args, moe_cfg, mlp_kind)

        out, sizes = jax.lax.map(block, (
            xf.reshape(n, T // n, d), top_w.reshape(n, T // n, K),
            top_e.reshape(n, T // n, K)))
        out, sizes = out.reshape(T, d), jnp.sum(sizes, axis=0)
        routed = jnp.sum(sizes)

    if moe_cfg.n_shared:
        with jax.named_scope("shared_experts"):
            out = out + mlp_mod.mlp_apply(params["shared"], xf, mlp_kind)
    aux = balance_loss(probs, top_e, moe_cfg, B)

    stats = {"moe_routed": routed.astype(jnp.float32),
             "moe_rows": jnp.float32(T * min(K, H)),
             "moe_peak": jnp.max(sizes).astype(jnp.float32),
             "moe_mean": routed.astype(jnp.float32) / H}
    return out.reshape(B, S, d), aux, stats


def _moe_apply_capacity(params, x, moe_cfg, mlp_kind: str):
    B, S, d = x.shape
    T = B * S
    E, K = moe_cfg.n_experts, moe_cfg.top_k
    C = capacity(T, moe_cfg)
    xf = x.reshape(T, d)
    probs, top_w, top_e = route(xf, params["router"], moe_cfg)

    # -- position of each (choice, token) within its expert ----------------
    # choice-major order: all first choices, then all second choices, ...
    e_flat = top_e.T.reshape(T * K)                     # (T*K,)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)  # (T*K, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.sum(pos_in_e * onehot, axis=-1)           # (T*K,)
    keep = pos < C
    pos_safe = jnp.where(keep, pos, C)                  # overflow slot C

    # -- dispatch: (E, C+1, d) scatter-add ---------------------------------
    tok = jnp.tile(jnp.arange(T), K)
    buf = jnp.zeros((E, C + 1, d), xf.dtype)
    buf = buf.at[e_flat, pos_safe].add(xf[tok])
    buf = buf[:, :C]                                    # drop overflow slot
    buf = constrain(buf, "moe_buffer")                  # (E/mdl, C/data, d)

    # -- expert compute (the expert-parallel einsums) -----------------------
    h = jnp.einsum("ecd,edf->ecf", buf,
                   layers.cast(params["w_in"], buf.dtype))
    g = (jnp.einsum("ecd,edf->ecf", buf,
                    layers.cast(params["w_gate"], buf.dtype))
         if mlp_kind in ("swiglu", "geglu") else None)
    h = _expert_act(h, g, mlp_kind)
    out_buf = jnp.einsum("ecf,efd->ecd", h,
                         layers.cast(params["w_out"], h.dtype))
    out_buf = constrain(out_buf, "moe_buffer")

    # -- combine: gather each kept choice back to its token -----------------
    pos_g = jnp.where(keep, pos, 0)
    gathered = out_buf[e_flat, pos_g]                   # (T*K, d)
    w_flat = (top_w.T.reshape(T * K, 1) * keep[:, None]).astype(gathered.dtype)
    contrib = (gathered * w_flat).reshape(K, T, d).sum(0)
    out = contrib.reshape(B, S, d)

    if moe_cfg.n_shared:
        out = out + mlp_mod.mlp_apply(params["shared"], x, mlp_kind)
    return out, balance_loss(probs, top_e, moe_cfg, B)


# ---------------------------------------------------------------------------
# Group-local dispatch (§Perf): tokens are dispatched WITHIN G groups that
# align with the data-axis shards, so the (G, E, C_g, d) buffer is sharded
# on G and the scatter never crosses token shards — removing the giant
# cross-shard all-reduce the global scatter induces (the dominant term of
# the baseline MoE roofline).  Capacity is per-group (same drop trade-off
# structure, granularity G-times finer).
# ---------------------------------------------------------------------------


def _moe_apply_grouped(params, x, moe_cfg, mlp_kind: str):
    B, S, d = x.shape
    T = B * S
    G = moe_cfg.dispatch_groups
    E, K = moe_cfg.n_experts, moe_cfg.top_k
    Tg = T // G
    Cg = capacity(Tg, moe_cfg)
    xg = x.reshape(G, Tg, d)

    def route_one(xt):
        """xt: (Tg, d) -> (buf (E, Cg, d), combine metadata)."""
        probs, top_w, top_e = route(xt, params["router"], moe_cfg)
        e_flat = top_e.T.reshape(Tg * K)
        onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
        pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, -1)
        keep = pos < Cg
        pos_safe = jnp.where(keep, pos, Cg)
        tok = jnp.tile(jnp.arange(Tg), K)
        buf = jnp.zeros((E, Cg + 1, d), xt.dtype)
        buf = buf.at[e_flat, pos_safe].add(xt[tok])
        return (buf[:, :Cg], e_flat, jnp.where(keep, pos, 0),
                (top_w.T.reshape(Tg * K, 1) * keep[:, None]), probs, top_e)

    buf, e_flat, pos_g, w_flat, probs, top_e = jax.vmap(route_one)(xg)
    buf = constrain(buf, "moe_buffer_grouped")            # (G, E, Cg, d)

    h = jnp.einsum("gecd,edf->gecf", buf,
                   layers.cast(params["w_in"], buf.dtype))
    g = (jnp.einsum("gecd,edf->gecf", buf,
                    layers.cast(params["w_gate"], buf.dtype))
         if mlp_kind in ("swiglu", "geglu") else None)
    h = _expert_act(h, g, mlp_kind)
    out_buf = jnp.einsum("gecf,efd->gecd", h,
                         layers.cast(params["w_out"], h.dtype))
    out_buf = constrain(out_buf, "moe_buffer_grouped")

    def combine_one(ob, ef, pg, wf):
        gathered = ob[ef, pg]                             # (Tg*K, d)
        return (gathered * wf.astype(gathered.dtype)).reshape(
            K, Tg, d).sum(0)

    out = jax.vmap(combine_one)(out_buf, e_flat, pos_g, w_flat)
    out = out.reshape(B, S, d)

    if moe_cfg.n_shared:
        out = out + mlp_mod.mlp_apply(params["shared"], x, mlp_kind)

    return out, balance_loss(probs.reshape(T, E), top_e.reshape(T, K),
                             moe_cfg, B)
