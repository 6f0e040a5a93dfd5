"""Partitioning rules for the SplitNN system on the production meshes.

Single-pod mesh (16, 16) = ("data", "model"); multi-pod (2, 16, 16) =
("pod", "data", "model").  The owner (data-owner) dimension of head
params/activations maps onto "pod" — PyVertical's parties at datacenter
scale; the cut-layer all-gather is then the only *protocol* cross-pod
collective (trunk-internal data parallelism is scientist-internal).

``trunk_dp_over_pod`` is the beyond-paper optimization lever: the baseline
(paper-faithful) deployment replicates trunk compute across pods (the
scientist owns the trunk); the optimized variant lets the trunk
data-parallelize over ("pod", "data") after the cut.
"""
from __future__ import annotations

import contextlib
import contextvars
import math
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class ShardingRules:
    multi_pod: bool = False
    model_axis: str = "model"
    data_axis: str = "data"
    pod_axis: Optional[str] = None              # None on the single-pod mesh
    fsdp: bool = False                          # ZeRO param sharding
    trunk_dp_over_pod: bool = False             # beyond-paper lever
    # decode-cache context parallelism: shard cache sequence dim
    cache_seq_axes: Tuple[str, ...] = ("model",)

    @property
    def owner_axis(self):
        return self.pod_axis

    @property
    def trunk_batch(self):
        if self.multi_pod and self.trunk_dp_over_pod:
            return (self.pod_axis, self.data_axis)
        return (self.data_axis,)


def abstract_mesh(axis_sizes, axis_names):
    """A device-free ``jax.sharding.AbstractMesh``: spec construction
    only consults ``mesh.shape`` / ``axis_names``."""
    return jax.sharding.AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def make_rules(mesh, cfg, **kw) -> ShardingRules:
    multi = "pod" in mesh.axis_names
    return ShardingRules(multi_pod=multi, pod_axis="pod" if multi else None,
                         fsdp=cfg.zero_sharding, **kw)


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

# logical trailing-dims spec per param name: tokens are placeholders
# resolved against the rules ("model" -> model axis, "fsdp" -> data axis
# when zero-sharding, else replicated).
_PARAM_RULES = [
    # (suffix, logical_ndim or None, spec template)
    ("embed/table", 2, ("model", "fsdp")),
    ("lm_head/w", 2, (None, "model")),
    ("front_proj/w", 2, (None, "model")),
    ("cut_proj/w", 2, (None, None)),
    ("in_proj/w", 2, ("fsdp", "model")),        # trunk in_proj & mamba in_proj
    ("attn/wq/w", 2, ("fsdp", "model")),
    ("attn/wk/w", 2, ("fsdp", "model")),
    ("attn/wv/w", 2, ("fsdp", "model")),
    ("xattn/wq/w", 2, ("fsdp", "model")),
    ("xattn/wk/w", 2, ("fsdp", "model")),
    ("xattn/wv/w", 2, ("fsdp", "model")),
    ("attn/wo/w", 2, ("model", "fsdp")),
    ("xattn/wo/w", 2, ("model", "fsdp")),
    ("ffn/w_in/w", 2, ("fsdp", "model")),
    ("ffn/w_gate/w", 2, ("fsdp", "model")),
    ("ffn/w_out/w", 2, ("model", "fsdp")),
    ("shared/w_in/w", 2, ("fsdp", "model")),
    ("shared/w_gate/w", 2, ("fsdp", "model")),
    ("shared/w_out/w", 2, ("model", "fsdp")),
    ("router/w", 2, (None, None)),
    # MoE experts: expert-parallel over the model axis when E divides it,
    # else fall back to tensor-parallel experts (shard d_expert) — the
    # mixtral case (8 experts on a 16-way model axis).
    ("w_in", 3, ("expert", None, "expert_alt")),   # (E, d, d_e)
    ("w_gate", 3, ("expert", None, "expert_alt")),
    ("w_out", 3, ("expert", "expert_alt", None)),  # (E, d_e, d)
    ("conv_w", 2, (None, "model")),
    ("mamba/out_proj/w", 2, ("model", "fsdp")),
    ("up_x/w", 2, ("fsdp", "model")),
    ("up_z/w", 2, ("fsdp", "model")),
    ("cell/wq/w", 2, (None, "model")),
    ("cell/wk/w", 2, (None, "model")),
    ("cell/wv/w", 2, (None, "model")),
    ("w_if/w", 2, ("model", None)),
    ("cell/down/w", 2, ("model", "fsdp")),
    ("w_gates/w", 2, ("fsdp", "model")),
    ("r_gates", 3, (None, None, None)),
    ("cell/up/w", 2, ("fsdp", "model")),
    ("up/w", 2, ("fsdp", "model")),
    ("down/w", 2, ("model", "fsdp")),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(f"#{k.idx}")
        else:
            parts.append(str(k))
    return "/".join(parts)


def _divisible(dim: int, axes, mesh) -> bool:
    if axes is None:
        return True
    names = axes if isinstance(axes, tuple) else (axes,)
    size = int(np.prod([mesh.shape[a] for a in names]))
    return dim % size == 0


def _resolve(template, rules: ShardingRules, cfg, mesh, shape, offset):
    """Template tokens -> mesh axes, with divisibility guards."""
    out = []
    expert_sharded = False
    if "expert" in template:
        e_dim = shape[offset + template.index("expert")]
        expert_sharded = _divisible(e_dim, rules.model_axis, mesh)
    for i, tok in enumerate(template):
        dim = shape[offset + i]
        ax: Any = None
        if tok == "model":
            ax = rules.model_axis
        elif tok == "fsdp":
            ax = rules.data_axis if rules.fsdp else None
        elif tok == "expert":
            ax = rules.model_axis if expert_sharded else None
        elif tok == "expert_alt":
            ax = None if expert_sharded else rules.model_axis
        if ax is not None and not _divisible(dim, ax, mesh):
            ax = None
        out.append(ax)
    return out


def param_specs(param_shapes, cfg, mesh, rules: ShardingRules):
    """PartitionSpec tree matching an eval_shape'd param tree."""

    def leaf(path, x):
        ps = _path_str(path)
        ndim = len(x.shape)
        for suffix, lnd, template in _PARAM_RULES:
            if ps.endswith(suffix) and (lnd is None or lnd <= ndim):
                # count stacking prefixes: owner dim (heads/...), unit dim
                n_prefix = ndim - lnd
                spec = [None] * n_prefix
                if ("heads/" in ps and n_prefix >= 1
                        and rules.owner_axis
                        and _divisible(x.shape[0], rules.owner_axis, mesh)):
                    spec[0] = rules.owner_axis
                spec += _resolve(template, rules, cfg, mesh, x.shape,
                                 n_prefix)
                return P(*spec)
        # default: replicate (norm scales, biases, scalars)
        spec = [None] * ndim
        if ("heads/" in ps and ndim >= 1 and rules.owner_axis
                and _divisible(x.shape[0], rules.owner_axis, mesh)):
            spec[0] = rules.owner_axis
        return P(*spec)

    return jax.tree_util.tree_map_with_path(leaf, param_shapes)


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------


def batch_specs(batch_shapes, cfg, mesh, rules: ShardingRules):
    """Specs for a training/prefill batch dict (owner inputs + labels)."""

    def leaf(path, x):
        name = _path_str(path)
        d = rules.data_axis
        if name == "owner_tokens":                 # (P, B, S_p)
            pod = (rules.owner_axis if rules.owner_axis
                   and _divisible(x.shape[0], rules.owner_axis, mesh)
                   else None)
            db = d if _divisible(x.shape[1], d, mesh) else None
            return P(pod, db, None)
        if name in ("patches", "frames"):          # (B, S_p, d_f)
            db = d if _divisible(x.shape[0], d, mesh) else None
            return P(db, None, None)
        if name in ("tokens", "labels"):           # (B, S)
            db = d if _divisible(x.shape[0], d, mesh) else None
            return P(db, *([None] * (len(x.shape) - 1)))
        if name in ("token",):                     # decode (B, 1)
            db = d if _divisible(x.shape[0], d, mesh) else None
            return P(db, None)
        return P(*([None] * len(x.shape)))

    return jax.tree_util.tree_map_with_path(leaf, batch_shapes)


def cache_specs(cache_shapes, cfg, mesh, rules: ShardingRules):
    """Decode-cache specs.  KV caches (units, B, S, n_kv, hd): batch over
    data when divisible, sequence over ``cache_seq_axes`` (context
    parallelism — essential at 500k); recurrent states: batch over data."""

    def leaf(path, x):
        ps = _path_str(path)
        d = rules.data_axis
        shape = x.shape
        spec = [None] * len(shape)
        # find the batch dim: KV caches are (units, B, S, n_kv, hd);
        # ssm states (units, B, ...); stacked-owner versions have a
        # leading P dim.
        b_dim = 0
        if ps.startswith("heads") and not ps.startswith("heads/patches") \
                and not ps.startswith("heads/tokens"):
            if rules.owner_axis and _divisible(shape[0], rules.owner_axis,
                                               mesh):
                spec[0] = rules.owner_axis
            b_dim = 2                              # (P, units, B, ...)
        else:
            b_dim = 1                              # (units, B, ...)
        if ps.startswith("enc"):                   # (B, S_enc, d)
            if _divisible(shape[0], d, mesh):
                spec[0] = d
            return P(*spec)
        if b_dim < len(shape) and _divisible(shape[b_dim], d, mesh):
            spec[b_dim] = d
        # kv-cache sequence dim: (.., B, S, n_kv, hd) with ndim-b_dim == 4
        if len(shape) - b_dim == 4 and (ps.endswith("/k")
                                        or ps.endswith("/v")):
            s_dim = b_dim + 1
            axes = tuple(a for a in rules.cache_seq_axes
                         if a in mesh.axis_names)
            if spec[b_dim] is None:
                # batch unshardable (B=1): context-parallel over data too
                axes = tuple(dict.fromkeys((rules.data_axis,) + axes))
            if axes and _divisible(shape[s_dim], axes, mesh):
                spec[s_dim] = axes if len(axes) > 1 else axes[0]
        return P(*spec)

    return jax.tree_util.tree_map_with_path(leaf, cache_shapes)


def named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# Activation constraints (hooked from model code)
# ---------------------------------------------------------------------------

_CTX: contextvars.ContextVar = contextvars.ContextVar("sharding_ctx",
                                                      default=None)


@contextlib.contextmanager
def sharding_context(mesh, rules: ShardingRules):
    tok = _CTX.set((mesh, rules))
    try:
        yield
    finally:
        _CTX.reset(tok)


def constrain(x, name: str):
    """Annotate a model-internal activation.  No-op without a context."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    d, m = rules.data_axis, rules.model_axis
    tb = rules.trunk_batch
    tb = tuple(a for a in tb if a)

    def guard(spec):
        fixed = []
        for dim, ax in zip(x.shape, spec):
            fixed.append(ax if ax is None or _divisible(dim, ax, mesh)
                         else None)
        return P(*fixed)

    if name == "cut_stacked":        # (P, B, S_p, k)
        pod = rules.owner_axis
        spec = (pod, d, None, None)
    elif name == "combined":         # (B, S, k) — trunk input, post-combine
        spec = (tb if len(tb) > 1 else (tb[0] if tb else None), None, None)
    elif name == "trunk_hidden":     # (B, S, d)
        spec = (tb if len(tb) > 1 else (tb[0] if tb else None), None, None)
    elif name == "logits":           # (B, S, vocab)
        spec = (tb if len(tb) > 1 else (tb[0] if tb else None), None, m)
    elif name == "moe_buffer":       # (E, C, d) dispatch/combine buffer
        spec = (m, d, None)
    elif name == "moe_buffer_grouped":  # (G, E, C_g, d): G rides data
        spec = (d, m, None, None)
    else:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, guard(spec)))
