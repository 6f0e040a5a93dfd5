"""Plain float32 reference of DeepSeek-V2-Lite as the cell runs it.

DeepSeek-V2 (arXiv:2405.04434) with DeepSeek-V2-Lite's sizes
(``bench/configs/deepseek-v2-lite.json``), cut to one chip's share of an
8-way expert- and vocabulary-parallel deployment, in a vertically split
network.  It imports nothing of the program; parameters are laid out as
the program's tree (owners' heads stacked on a leading axis).

Per layer (pre-norm, RMSNorm gain ``1 + scale``, eps ``rms_norm_eps``):

- attention is multi-head latent attention with no query compression:
  ``q = x Wq`` (per head ``qk_nope + qk_rope`` wide); ``[c, k_rope] =
  x Wkv_a``; ``[k_nope, v] = RMSNorm(c) Wkv_b`` per head; the one
  ``k_rope`` is shared by every head; RoPE on ``q_rope`` and ``k_rope``
  with YaRN frequencies; softmax scale ``qk_head_dim^-0.5 * mscale^2``;
  causal; ``out = concat_h(o_h) Wo``;
- the first ``first_k_dense_replace`` layers: SwiGLU of width
  ``intermediate_size``; the others: the router's softmax over all
  ``router_experts``, greedy top-k, weights not renormalized; the held
  experts' part written densely (each held expert on every token, weighted
  by the token's router weight where it chose that expert and 0
  elsewhere); the shared experts (one SwiGLU of ``n_shared * width``) on
  every token; the sequence-wise balance loss, per sequence ``alpha
  sum_i f_i P_i`` with ``f_i = E / (K S) #{t : i in top-K(t)}`` and
  ``P_i = mean_t s_{i,t}``, averaged over the sequences.

Departures from the published model, each also the program's:

- SplitNN: owner p embeds and runs the dense layer on its own half of
  every sequence (global positions), so the dense layer sees half a
  sequence; the scientist concatenates the halves and runs the MoE layers,
  the final norm and the LM head on the whole sequence;
- the chip's share: 8 of 64 routed experts per layer (choices routed to
  the other 56 add nothing), the vocabulary's first 12,800 ids, 5 of 27
  layers;
- rotary pairs (i, i + rope/2), where DeepSeek's checkpoint interleaves
  them: a fixed permutation of the rope columns.

Blocks: the loss is taken one sequence at a time and attention one block
of queries at a time, under ``jax.checkpoint``, so that the reference's
steps fit one chip beside nothing else.  The loss value is the mean
next-token cross-entropy (what the program reports); the balance loss
enters its gradient alone, as ``aux - stop_gradient(aux)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.numerics import Numerics

#: queries per attention block
Q_BLOCK = 1024


def _dims(cfg: dict) -> dict:
    dep = cfg["deployment"]
    return dict(d=cfg["hidden_size"], H=cfg["num_attention_heads"],
                nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
                vd=cfg["v_head_dim"], r=cfg["kv_lora_rank"],
                ff=cfg["intermediate_size"], de=cfg["moe_intermediate_size"],
                E=dep["router_experts"], held=cfg["n_routed_experts"],
                first=dep["held_first"], K=cfg["num_experts_per_tok"],
                sh=cfg["n_shared_experts"], V=cfg["vocab_size"],
                P=cfg["n_owners"], eps=cfg["rms_norm_eps"],
                n_dense=cfg["first_k_dense_replace"],
                n_moe=cfg["num_hidden_layers"]
                - cfg["first_k_dense_replace"])


# ------------------------------------------------------------------ init


def _normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def _dense(key, a, b, std=None):
    return {"w": _normal(key, (a, b), a ** -0.5 if std is None else std)}


def _swiglu_init(key, d, f):
    k = jax.random.split(key, 3)
    return {"w_in": _dense(k[0], d, f), "w_out": _dense(k[1], f, d),
            "w_gate": _dense(k[2], d, f)}


def _block_init(key, m, moe: bool):
    d, H = m["d"], m["H"]
    k = jax.random.split(key, 9)
    attn = {"wq": _dense(k[0], d, H * (m["nope"] + m["rope"])),
            "wkv_a": _dense(k[1], d, m["r"] + m["rope"]),
            "kv_norm": {"scale": jnp.zeros((m["r"],), jnp.float32)},
            "wkv_b": _dense(k[2], m["r"], H * (m["nope"] + m["vd"])),
            "wo": _dense(k[3], H * m["vd"], d)}
    if moe:
        n, de = m["held"], m["de"]
        ffn = {"router": _dense(k[4], d, m["E"], 0.02),
               "w_in": _normal(k[5], (n, d, de), d ** -0.5),
               "w_gate": _normal(k[6], (n, d, de), d ** -0.5),
               "w_out": _normal(k[7], (n, de, d), de ** -0.5),
               "shared": _swiglu_init(k[8], d, m["sh"] * de)}
    else:
        ffn = _swiglu_init(k[4], d, m["ff"])
    zeros = jnp.zeros((d,), jnp.float32)
    return {"norm1": {"scale": zeros}, "attn": attn,
            "norm2": {"scale": zeros}, "ffn": ffn}


def _stack(key, m, n: int, moe: bool):
    units = jax.vmap(lambda k: {"b0": _block_init(k, m, moe)})(
        jax.random.split(key, n))
    return {"units": units, "shared": {}}


def init_params(key, cfg: dict):
    m = _dims(cfg)
    kh, kt = jax.random.split(key)

    def head(k):
        ka, kb = jax.random.split(k)
        return {"blocks": _stack(ka, m, m["n_dense"], moe=False),
                "embed": {"table": _normal(kb, (m["V"], m["d"]), 0.02)}}

    heads = jax.vmap(head)(jax.random.split(kh, m["P"]))
    ka, kb = jax.random.split(kt)
    trunk = {"blocks": _stack(ka, m, m["n_moe"], moe=True),
             "out_norm": {"scale": jnp.zeros((m["d"],), jnp.float32)},
             "lm_head": _dense(kb, m["d"], m["V"], 0.02)}
    return {"heads": heads, "trunk": trunk}


# --------------------------------------------------------------- forward


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def yarn(cfg: dict):
    """(rope frequencies (rope/2,), cos/sin factor, softmax scale), from
    DeepSeek-V2's YaRN: slots below ``low`` keep theta^(-2i/rope), slots
    above ``high`` take it divided by ``factor``, a linear ramp between;
    ``low``/``high`` the floor/ceil of ``rope ln(L / (2 pi beta)) / (2 ln
    theta)`` at beta_fast/beta_slow."""
    rs, rope = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    theta, f = float(cfg["rope_theta"]), float(rs["factor"])
    L = rs["original_max_position_embeddings"]

    def slot(beta):
        return rope * math.log(L / (2 * math.pi * beta)) / (2 * math.log(theta))

    low = max(math.floor(slot(rs["beta_fast"])), 0)
    high = min(math.ceil(slot(rs["beta_slow"])), rope - 1)
    i = np.arange(rope // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / rope)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    freqs = plain * (1 - ramp) + plain / f * ramp

    def mscale(m):
        return 0.1 * m * math.log(f) + 1.0 if f > 1 else 1.0

    cos_sin = mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"])
    scale = (cfg["qk_nope_head_dim"] + rope) ** -0.5 \
        * mscale(rs["mscale_all_dim"]) ** 2
    return jnp.asarray(freqs, jnp.float32), cos_sin, scale


def _rope(x, pos, freqs, factor):
    """x (S, h, rope), pairs (i, i + rope/2)."""
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mla(p, x, pos, m, cfg, nx: Numerics):
    """x (S, d) at positions ``pos`` -> (S, d)."""
    S, H = x.shape[0], m["H"]
    freqs, factor, scale = yarn(cfg)
    q = nx.dot(x, p["wq"]["w"]).reshape(S, H, m["nope"] + m["rope"])
    ckv = nx.dot(x, p["wkv_a"]["w"])
    c, k_rope = ckv[:, :m["r"]], ckv[:, m["r"]:]
    c = nx.act(_rms(c, p["kv_norm"]["scale"], m["eps"]))
    kv = nx.dot(c, p["wkv_b"]["w"]).reshape(S, H, m["nope"] + m["vd"])
    k_nope, v = kv[..., :m["nope"]], kv[..., m["nope"]:]
    q = jnp.concatenate([q[..., :m["nope"]],
                         _rope(q[..., m["nope"]:], pos, freqs, factor)], -1)
    k_rope = _rope(k_rope[:, None, :], pos, freqs, factor)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (S, H, m["rope"]))], -1)
    q, k, v = nx.act(q), nx.act(k), nx.act(v)

    @jax.checkpoint
    def block(q_b, pos_b):
        s = nx.einsum("qhd,khd->hqk", q_b, k) * scale
        s = jnp.where(pos[None, None, :] <= pos_b[None, :, None], s,
                      -jnp.inf)
        return nx.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    qb = min(Q_BLOCK, S)
    o = jnp.concatenate([block(q[i:i + qb], pos[i:i + qb])
                         for i in range(0, S, qb)])
    return nx.dot(o.reshape(S, H * m["vd"]), p["wo"]["w"])


def _swiglu(p, x, nx):
    h = nx.dot(x, p["w_in"]["w"])
    g = nx.dot(x, p["w_gate"]["w"])
    return nx.dot(nx.act(jax.nn.silu(g) * h), p["w_out"]["w"])


def _moe(p, x, m, nx):
    """x (S, d) -> (out (S, d), this sequence's balance loss)."""
    S, E, K = x.shape[0], m["E"], m["K"]
    probs = jax.nn.softmax(nx.dot(x, p["router"]["w"]), -1)
    top_w, top_e = jax.lax.top_k(probs, K)
    held = m["first"] + jnp.arange(m["held"])
    # (S, held): the token's weight where it chose the held expert, else 0
    weight = jnp.sum(jnp.where(top_e[:, :, None] == held, top_w[:, :, None],
                               0.0), axis=1)
    h = nx.einsum("sd,jdf->jsf", x, p["w_in"])
    g = nx.einsum("sd,jdf->jsf", x, p["w_gate"])
    y = nx.einsum("jsf,jfd->jsd", nx.act(jax.nn.silu(g) * h), p["w_out"])
    out = jnp.einsum("sj,jsd->sd", weight, y,
                     precision=jax.lax.Precision.HIGHEST)
    out = out + _swiglu(p["shared"], x, nx)
    counts = jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), (0, 1))
    f = counts * E / (K * S)
    aux = m["alpha"] * jnp.sum(f * jnp.mean(probs, 0))
    return out, aux


def _block(p, x, pos, *, m, cfg, nx, moe: bool):
    x = x + _mla(p["attn"], _rms(x, p["norm1"]["scale"], m["eps"]), pos, m,
                 cfg, nx)
    h = _rms(x, p["norm2"]["scale"], m["eps"])
    if moe:
        f, aux = _moe(p["ffn"], h, m, nx)
    else:
        f, aux = _swiglu(p["ffn"], h, nx), 0.0
    return nx.act(x + f), aux


def _layers(stack, i):
    return jax.tree.map(lambda a: a[i], stack["units"]["b0"])


def _sequence_loss(params, tokens, labels, cfg, nx):
    """One sequence (S,) -> (mean cross-entropy, balance loss)."""
    m = dict(_dims(cfg), alpha=cfg["aux_loss_alpha"])
    S = tokens.shape[0]
    S_p = S // m["P"]

    def layer(moe):
        return jax.checkpoint(functools.partial(_block, m=m, cfg=cfg, nx=nx,
                                                moe=moe))

    cuts = []
    for o in range(m["P"]):
        head = jax.tree.map(lambda a: a[o], params["heads"])
        pos = o * S_p + jnp.arange(S_p)
        x = nx.act(head["embed"]["table"][tokens[o * S_p:(o + 1) * S_p]])
        for i in range(m["n_dense"]):
            x, _ = layer(False)(_layers(head["blocks"], i), x, pos)
        cuts.append(nx.act(x))
    x, pos, aux = jnp.concatenate(cuts), jnp.arange(S), 0.0
    trunk = params["trunk"]
    for i in range(m["n_moe"]):
        x, a = layer(True)(_layers(trunk["blocks"], i), x, pos)
        aux = aux + a
    x = _rms(x, trunk["out_norm"]["scale"], m["eps"])
    logits = nx.dot(x, trunk["lm_head"]["w"])
    lse = jax.scipy.special.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
    return jnp.mean(lse - picked), aux


def loss(params, tokens, labels, cfg: dict, nx: Numerics):
    """tokens, labels: (B, S) int.  The mean next-token cross-entropy over
    the batch; its gradient is that of cross-entropy plus the balance
    loss (averaged over sequences), as the program trains."""
    def one(carry, xs):
        ce, aux = _sequence_loss(params, xs[0], xs[1], cfg, nx)
        return carry, (ce, aux)

    _, (ce, aux) = jax.lax.scan(one, None, (jnp.asarray(tokens),
                                            jnp.asarray(labels)))
    aux = jnp.mean(aux)
    return jnp.mean(ce) + (aux - jax.lax.stop_gradient(aux))


def segments(params, cfg: dict):
    out = {f"owner{p}": jax.tree.map(lambda a: a[p], params["heads"])
           for p in range(cfg["n_owners"])}
    out["trunk"] = params["trunk"]
    return out


def make_data(cfg: dict, n: int, seed: int, seq_len: int, zipf_s: float):
    """``n`` sequences of ``seq_len`` tokens, i.i.d. Zipf(``zipf_s``) over
    the ``vocab_size`` ids (id 0 the most frequent), and their next-token
    labels: ``(tokens (n, S), labels (n, S))`` int32."""
    V = cfg["vocab_size"]
    p = 1.0 / np.arange(1, V + 1, dtype=np.float64) ** zipf_s
    rng = np.random.default_rng([seed, 7])
    seq = rng.choice(V, size=(n, seq_len + 1), p=p / p.sum()).astype(np.int32)
    return seq[:, :-1], seq[:, 1:]


def alter(batch, cfg: dict):
    """The batch with one token altered, a fault for the comparison to
    catch: the middle token of the middle sequence changed to the next
    id."""
    tokens, labels = np.array(batch[0]), np.array(batch[1])
    b, t = tokens.shape[0] // 2, tokens.shape[1] // 2
    tokens[b, t] = (tokens[b, t] + 1) % cfg["vocab_size"]
    return tokens, labels
