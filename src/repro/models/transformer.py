"""Block assembly: heterogeneous super-blocks + scan-over-superblocks.

An architecture is ``n_superblocks`` repetitions of ``cfg.block_pattern``
(e.g. zamba2: 5x mamba2 + 1 shared_attn).  Parameters of the units are
stacked on a leading dim and the stack is applied with ``jax.lax.scan`` so
compiled HLO size is independent of depth; each super-block is optionally
rematerialized (``cfg.remat``).

Block kinds: attn:global, attn:local, shared_attn (zamba2 weight sharing),
mamba2, slstm, mlstm, dec (whisper decoder block with cross-attention).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention, layers, mlp as mlp_mod, moe as moe_mod
from repro.models import ssm, xlstm


def _has_ffn(cfg) -> bool:
    return cfg.moe is not None or (cfg.d_ff > 0 and cfg.mlp != "none")


def _use_moe(cfg, ffn) -> bool:
    """``ffn``: "moe" | "dense" | None (MoE wherever the config has it)."""
    return cfg.moe is not None and ffn != "dense"


def _ffn_init(key, cfg, dtype, ffn=None):
    if _use_moe(cfg, ffn):
        return moe_mod.moe_init(key, cfg.d_model, cfg.moe, cfg.mlp, dtype)
    return mlp_mod.mlp_init(key, cfg.d_model, cfg.d_ff, cfg.mlp, dtype)


def _ffn_apply(params, x, cfg, ffn=None):
    """(out, aux, share-layer counters or {})."""
    if _use_moe(cfg, ffn):
        return moe_mod.moe_apply(params, x, cfg.moe, cfg.mlp,
                                 with_stats=True)
    return mlp_mod.mlp_apply(params, x, cfg.mlp), 0.0, {}


def stats_zero(cfg, ffn=None) -> dict:
    """The counters a stack of this FFN kind accumulates (the share
    layer's; none for the others)."""
    if _use_moe(cfg, ffn) and cfg.moe.share:
        return moe_mod.moe_stats_zero()
    return {}


# ---------------------------------------------------------------------------
# Single block
# ---------------------------------------------------------------------------


def block_init(key, cfg, kind: str, dtype=jnp.float32, ffn=None):
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    p = {"norm1": layers.norm_init(d, cfg.norm, dtype)}
    if kind in ("attn:global", "attn:local", "shared_attn", "dec"):
        p["attn"] = attention.attn_init(ks[0], cfg, dtype)
        if kind == "dec":
            p["norm_x"] = layers.norm_init(d, cfg.norm, dtype)
            p["xattn"] = attention.attn_init(ks[1], cfg, dtype)
        if _has_ffn(cfg):
            p["norm2"] = layers.norm_init(d, cfg.norm, dtype)
            p["ffn"] = _ffn_init(ks[2], cfg, dtype, ffn)
        if cfg.post_block_norm:
            p["post1"] = layers.norm_init(d, cfg.norm, dtype)
            if _has_ffn(cfg):
                p["post2"] = layers.norm_init(d, cfg.norm, dtype)
    elif kind == "mamba2":
        p["mamba"] = ssm.mamba2_init(ks[0], cfg, dtype)
    elif kind == "slstm":
        p["cell"] = xlstm.slstm_init(ks[0], cfg, dtype)
    elif kind == "mlstm":
        p["cell"] = xlstm.mlstm_init(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    return p


def block_cache_init(batch: int, cfg, kind: str, s_max: int,
                     dtype=jnp.bfloat16, window_slots: int = 0):
    if kind in ("attn:global", "attn:local", "shared_attn", "dec"):
        s_eff = min(s_max, window_slots) if window_slots else s_max
        # MLA: per-head keys and values as attention takes them (values
        # v_head_dim wide), not the latent
        v_dim = cfg.mla.v_head_dim if cfg.mla is not None else 0
        return attention.init_kv_cache(batch, s_eff, cfg.n_kv_heads,
                                       cfg.head_dim, dtype, v_dim=v_dim)
    if kind == "mamba2":
        return ssm.mamba2_cache_init(batch, cfg, jnp.float32)
    if kind == "slstm":
        return xlstm.slstm_cache_init(batch, cfg, jnp.float32)
    if kind == "mlstm":
        return xlstm.mlstm_cache_init(batch, cfg, jnp.float32)
    raise ValueError(kind)


def block_apply(params, x, *, cfg, kind: str, positions=None,
                attn_kind: str = "causal", window: int = 0, cache=None,
                pos=None, enc_out=None, chunk: int = 1024, ffn=None):
    """Returns (x_out, new_cache, aux_loss, share-layer counters or {})."""
    aux, stats = 0.0, {}
    h = layers.norm_apply(params["norm1"], x, cfg.norm, cfg.norm_eps)
    if kind in ("attn:global", "attn:local", "shared_attn", "dec"):
        a, new_cache = attention.attn_apply(
            params["attn"], h, cfg=cfg, kind=attn_kind, positions=positions,
            window=window, cache=cache, pos=pos, chunk=chunk)
        if cfg.post_block_norm:
            a = layers.norm_apply(params["post1"], a, cfg.norm, cfg.norm_eps)
        x = x + a
        if kind == "dec" and enc_out is not None:
            h = layers.norm_apply(params["norm_x"], x, cfg.norm, cfg.norm_eps)
            a, _ = attention.attn_apply(params["xattn"], h, cfg=cfg,
                                        kind="bidir", kv_x=enc_out,
                                        chunk=chunk)
            x = x + a
        if _has_ffn(cfg):
            h = layers.norm_apply(params["norm2"], x, cfg.norm, cfg.norm_eps)
            f, aux, stats = _ffn_apply(params["ffn"], h, cfg, ffn)
            if cfg.post_block_norm:
                f = layers.norm_apply(params["post2"], f, cfg.norm,
                                      cfg.norm_eps)
            x = x + f
    elif kind == "mamba2":
        y, new_cache = ssm.mamba2_apply(params["mamba"], h, cfg, cache)
        x = x + y.astype(x.dtype)
    elif kind == "slstm":
        y, new_cache = xlstm.slstm_apply(params["cell"], h, cfg, cache)
        x = x + y.astype(x.dtype)
    elif kind == "mlstm":
        y, new_cache = xlstm.mlstm_apply(params["cell"], h, cfg, cache)
        x = x + y.astype(x.dtype)
    else:
        raise ValueError(kind)
    return x, new_cache, aux, stats


# ---------------------------------------------------------------------------
# Stack of super-blocks
# ---------------------------------------------------------------------------


def stack_init(key, cfg, n_units: int, pattern=None, dtype=jnp.float32,
               ffn=None):
    """Returns {"units": unit-stacked params, "shared": shared params}.
    ``ffn``: "dense" | "moe" | None (the config's) for every block."""
    pattern = pattern if pattern is not None else cfg.block_pattern
    shared = {}
    if "shared_attn" in pattern:
        key, sk = jax.random.split(key)
        shared["shared_attn"] = block_init(sk, cfg, "shared_attn", dtype,
                                           ffn)

    def unit_init(k):
        ks = jax.random.split(k, len(pattern))
        unit = {}
        for i, kind in enumerate(pattern):
            if kind == "shared_attn":
                unit[f"b{i}"] = {}        # params live in `shared`
            else:
                unit[f"b{i}"] = block_init(ks[i], cfg, kind, dtype, ffn)
        return unit

    unit_keys = jax.random.split(key, n_units)
    units = jax.vmap(unit_init)(unit_keys)
    return {"units": units, "shared": shared}


def stack_cache_init(batch: int, cfg, n_units: int, s_max: int,
                     pattern=None, dtype=jnp.bfloat16, ring: bool = False,
                     swa_override: int = 0):
    """``ring=True`` trims sliding-window layers' caches to their window
    (ring-buffer slots): attn:local uses cfg.swa_window; when
    ``swa_override`` is set (the explicit long-context variant) global
    layers are windowed too."""
    pattern = pattern if pattern is not None else cfg.block_pattern

    def slots(kind):
        if not ring:
            return 0
        if kind == "attn:local":
            return cfg.swa_window
        if kind in ("attn:global", "shared_attn") and swa_override:
            return swa_override
        return 0

    def one_unit(_):
        return {f"b{i}": block_cache_init(batch, cfg, kind, s_max, dtype,
                                          window_slots=slots(kind))
                for i, kind in enumerate(pattern)}

    unit = one_unit(None)
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n_units,) + a.shape),
                        unit)


def stack_apply(params, x, *, cfg, pattern=None, positions=None,
                caches=None, pos=None, enc_out=None, chunk: int = 1024,
                swa_override: Optional[int] = None, bidir: bool = False,
                ffn=None, with_stats: bool = False):
    """Apply all super-blocks.  Returns (x, new_caches, aux_total), and
    the share layer's counters summed over the blocks (``{}`` for other
    FFNs) as a fourth element when ``with_stats``.

    ``swa_override``: when set, every attn:global runs as sliding-window
    with this window (the explicit long-context variant, see DESIGN.md).
    ``bidir``: bidirectional self-attention (whisper encoder).
    ``ffn``: the blocks' FFN kind, as ``stack_init`` took it.
    """
    pattern = pattern if pattern is not None else cfg.block_pattern
    shared = params["shared"]

    def superblock(x_aux, unit_params, unit_caches):
        x, aux, stats = x_aux
        new_caches = {}
        for i, kind in enumerate(pattern):
            bp = (shared["shared_attn"] if kind == "shared_attn"
                  else unit_params[f"b{i}"])
            cache_i = None if unit_caches is None else unit_caches[f"b{i}"]
            attn_kind, window = "causal", 0
            if kind == "attn:local":
                attn_kind, window = "local", cfg.swa_window
            elif kind in ("attn:global", "shared_attn", "dec"):
                if swa_override:
                    attn_kind, window = "local", swa_override
            if bidir and kind.startswith("attn"):
                attn_kind, window = "bidir", 0
            if kind == "dec" and enc_out is None:
                raise ValueError("dec block needs enc_out")
            x, nc, aux_i, stats_i = block_apply(
                bp, x, cfg=cfg, kind=kind, positions=positions,
                attn_kind=attn_kind, window=window, cache=cache_i, pos=pos,
                enc_out=enc_out, chunk=chunk, ffn=ffn)
            new_caches[f"b{i}"] = nc
            aux = aux + aux_i
            stats = {k: v + stats_i.get(k, 0.0) for k, v in stats.items()}
        return (x, aux, stats), new_caches

    if cfg.remat:
        superblock = jax.checkpoint(superblock)

    carry0 = (x, jnp.zeros((), jnp.float32), stats_zero(cfg, ffn))
    if caches is None:
        def body(carry, unit_params):
            carry, _ = superblock(carry, unit_params, None)
            return carry, None
        (x, aux, stats), _ = jax.lax.scan(body, carry0, params["units"])
        new_caches = None
    else:
        def body(carry, xs):
            unit_params, unit_caches = xs
            carry, new_caches = superblock(carry, unit_params, unit_caches)
            return carry, new_caches

        (x, aux, stats), new_caches = jax.lax.scan(
            body, carry0, (params["units"], caches))
    if with_stats:
        return x, new_caches, aux, stats
    return x, new_caches, aux
