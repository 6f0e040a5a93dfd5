"""Model registry: one ``session.build(config)`` for every split model.

A config object (``MLPSplitConfig``, ``ArchConfig``, or anything a later
PR registers) dispatches to an *adapter* that gives the session a uniform
surface: ``init``, ``loss_fn``, batch assembly in the right layout
(``federation/batching.py``), default per-segment optimizers, and —
where supported — the serving engine.  New architectures and combine
strategies land as a registry entry + config, not a new training script.

Adapters with ``supports_split = True`` additionally expose the
per-segment surface that true split execution (``fit(mode="split")``)
runs over the transport layer:

  ``owner_programs(p)``      -> (head_fwd, head_bwd) jitted owner programs
  ``trunk_program()``        -> jitted scientist step
                                 (trunk_params, cut, labels) ->
                                 (metrics, trunk_grads, cut_grads)
  ``owner_param_slice`` / ``stack_head_params``
                             -> move one owner's head segment in/out of
                                the joint param tree
  ``owner_optimizer`` / ``trunk_optimizer``
                             -> the per-party update rules (the joint
                                ``default_optimizer`` split at the same
                                boundary)

Adapters with ``supports_microbatch = True`` add the GPipe surface used
by ``fit(..., microbatches=M)``:

  ``trunk_microbatch_programs()`` -> (cutgrad, weightgrad) per-chunk
                                 scientist programs (sum/denom seeding)
  ``gather_program()``       -> jitted device-side row gather
                                 (feats, idx) -> rows, so the dispatch
                                 loop never blocks on a host transfer
  ``owner_update_rule(lr)`` / ``trunk_update_rule(lr)``
                             -> (optimizer, jitted update+apply with
                                buffer donation), cached so the split
                                workers and the microbatched joint
                                oracle run the *same* compiled programs

Every program accessor is cached on the adapter: the microbatched joint
oracle and the transport-backed split schedule must execute identical
compiled programs for the bit-for-bit equivalence contract to be about
the *protocol* rather than about XLA codegen stability.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.federation import batching
from repro.optim import (adam, apply_updates, chain, clip_by_global_norm,
                         multi_segment, sgd)


class _ProgramCache:
    """Mixin: build-once accessors for jitted segment programs."""

    def _cached(self, key, builder):
        cache = getattr(self, "_progs", None)
        if cache is None:
            cache = self._progs = {}
        if key not in cache:
            cache[key] = builder()
        return cache[key]

    def gather_program(self):
        """Device-side row gather: (staged feature array, idx) -> rows.
        One jitted program shared by owner workers and the joint oracle —
        feature matrices are staged on device once, so per-step batch
        assembly never round-trips through the host."""
        return self._cached(
            "gather", lambda: jax.jit(lambda feats, idx: feats[idx]))

    def _update_rule(self, key, optimizer):
        def build():
            def upd(params, state, grads, step):
                updates, state_ = optimizer.update(grads, state, params,
                                                   step)
                return apply_updates(params, updates), state_
            return optimizer, jax.jit(upd, donate_argnums=(0, 1))
        return self._cached(key, build)

    def owner_update_rule(self, owner_lr: Optional[float] = None):
        """(optimizer, jitted update+apply) for one owner's head segment.
        update+apply compile together — the joint step's fusion
        granularity (bit-for-bit equivalence depends on it) — and donate
        the param/state buffers."""
        return self._update_rule(("owner_upd", owner_lr),
                                 self.owner_optimizer(owner_lr))

    def trunk_update_rule(self, scientist_lr: Optional[float] = None):
        return self._update_rule(("trunk_upd", scientist_lr),
                                 self.trunk_optimizer(scientist_lr))

    def owner_tail_rule(self, owner_lr: Optional[float] = None,
                        owner_index: int = 0):
        """The owner's latency-critical tail as ONE compiled program:
        backward for the step's final gradient chunk (+ fold into the
        accumulated grads when microbatched), the optimizer update, and
        the forward for the *next* step's first chunk.  One dispatch
        instead of three and no host sync between segments — and
        bitwise-identical to the separate programs (property-tested).
        ``acc`` may be ``None`` (single-chunk steps add nothing — not
        even a zeros-tree, which would flip -0.0 gradient signs)."""
        head_fwd, head_bwd = self.owner_programs(owner_index)
        optimizer = self.owner_optimizer(owner_lr)
        key = ("owner_tail", owner_lr, id(head_fwd))

        def build():
            def tail(p, s, acc, x, g, step, x_next):
                gr = head_bwd(p, x, g)
                if acc is not None:
                    gr = jax.tree.map(lambda a, b: a + b, acc, gr)
                updates, s2 = optimizer.update(gr, s, p, step)
                p2 = apply_updates(p, updates)
                return p2, s2, head_fwd(p2, x_next)

            return jax.jit(tail, donate_argnums=(0, 1))

        return self._cached(key, build)

_BUILDERS: Dict[type, Callable] = {}


def register_model(*cfg_types: type):
    """Class decorator: dispatch ``session.build(cfg)`` on ``type(cfg)``
    (subclasses included) to the decorated adapter."""
    def deco(adapter_cls):
        for t in cfg_types:
            _BUILDERS[t] = adapter_cls
        return adapter_cls
    return deco


def build_adapter(cfg):
    for t in type(cfg).__mro__:
        if t in _BUILDERS:
            return _BUILDERS[t](cfg)
    raise TypeError(
        f"no split-model adapter registered for {type(cfg).__name__}; "
        f"known: {[t.__name__ for t in _BUILDERS]}")


# ---------------------------------------------------------------------------
# Adapters
# ---------------------------------------------------------------------------

from repro.configs.base import ArchConfig
from repro.configs.pyvertical_mnist import MLPSplitConfig
from repro.core.splitnn import MLPSplitNN
from repro.models.model import SplitModel


@register_model(MLPSplitConfig)
class MLPAdapter(_ProgramCache):
    """The paper's Appendix-B dual-headed MLP on feature-split data."""

    layout = "feature"
    supports_serving = False

    def __init__(self, cfg: MLPSplitConfig):
        self.cfg = cfg
        self.model = MLPSplitNN(cfg)
        self.loss_fn = self.model.loss_fn

    def init(self, key):
        return self.model.init(key)

    def make_batch(self, owner_arrays: Sequence[np.ndarray],
                   labels: Optional[np.ndarray], idx=None):
        return batching.feature_batch(owner_arrays, labels, idx)

    def _segment_opts(self, owner_lr: Optional[float] = None,
                      scientist_lr: Optional[float] = None):
        """THE per-segment update rules (Appendix B) — the joint
        ``default_optimizer`` and the split-mode per-party optimizers
        are both derived from this one definition."""
        sp = self.cfg.split
        return {
            "heads": sgd(owner_lr if owner_lr is not None
                         else sp.owner_lr),
            "trunk": sgd(scientist_lr if scientist_lr is not None
                         else sp.scientist_lr)}

    def default_optimizer(self, owner_lr: Optional[float] = None,
                          scientist_lr: Optional[float] = None):
        return multi_segment(self._segment_opts(owner_lr, scientist_lr))

    def cut_shape(self, batch_size: int,
                  feature_shape: Tuple[int, ...]) -> Tuple[int, ...]:
        """Per-owner cut activation shape: (B, k) — NOT the raw width."""
        return (batch_size, self.model.k)

    # ------------------------------------------------- split execution
    supports_split = True
    supports_microbatch = True
    supports_nopeek = True

    def owner_programs(self, owner_index: int):
        from repro.core.splitnn import make_mlp_head_programs
        # one shape-polymorphic program pair serves every owner; the
        # NoPeek weight is baked into the backward at trace time, so it
        # keys the cache
        w = float(self.cfg.split.nopeek_weight)
        return self._cached(("head_progs", w),
                            lambda: make_mlp_head_programs(self.model, w))

    def trunk_program(self):
        from repro.core.splitnn import make_mlp_trunk_program
        return self._cached("trunk_prog",
                            lambda: make_mlp_trunk_program(self.model))

    def trunk_microbatch_programs(self):
        from repro.core.splitnn import make_mlp_trunk_microbatch_programs
        return self._cached(
            "trunk_micro",
            lambda: make_mlp_trunk_microbatch_programs(self.model))

    # ------------------------------------- secure forward aggregation
    @property
    def supports_masked(self) -> bool:
        """masked_sum rides the sum combine: the scientist only ever
        needs ``sum_p cut_p``, which the ring fold reconstructs."""
        return self.cfg.split.combine == "sum"

    def quant_program(self):
        from repro.core import masking
        return self._cached("quant_prog", masking.make_quant_program)

    def masked_trunk_program(self):
        from repro.core.splitnn import make_mlp_masked_trunk_program
        return self._cached(
            "masked_trunk_prog",
            lambda: make_mlp_masked_trunk_program(self.model))

    def masked_trunk_microbatch_programs(self):
        from repro.core.splitnn import \
            make_mlp_masked_trunk_microbatch_programs
        return self._cached(
            "masked_trunk_micro",
            lambda: make_mlp_masked_trunk_microbatch_programs(self.model))

    def owner_param_slice(self, params, p: int):
        if self.model.symmetric:
            return jax.tree.map(lambda a: a[p], params["heads"])
        return params["heads"][p]

    def stack_head_params(self, slices: Sequence):
        if self.model.symmetric:
            return jax.tree.map(lambda *xs: jnp.stack(xs), *slices)
        return list(slices)

    def owner_batch(self, owner_array: np.ndarray, idx):
        return jnp.asarray(owner_array[idx])

    def owner_optimizer(self, owner_lr: Optional[float] = None):
        # plain SGD is elementwise, so one owner's slice of the joint
        # stacked-heads update IS this update (bit-for-bit equivalence)
        return self._segment_opts(owner_lr=owner_lr)["heads"]

    def trunk_optimizer(self, scientist_lr: Optional[float] = None):
        return self._segment_opts(scientist_lr=scientist_lr)["trunk"]


@register_model(ArchConfig)
class SplitLMAdapter(_ProgramCache):
    """Sequence-split language models (`SplitModel`) — text modality."""

    layout = "sequence"
    supports_serving = True

    def __init__(self, cfg: ArchConfig):
        if cfg.modality != "text":
            raise ValueError(
                f"VerticalSession drives text archs; {cfg.name} is "
                f"{cfg.modality} (see examples/ for vlm/audio training)")
        if float(getattr(cfg.split, "nopeek_weight", 0.0)) > 0.0:
            # refuse rather than silently train undefended: the LM head
            # has no NoPeek program (token inputs have no meaningful
            # euclidean geometry for the dcor penalty)
            raise ValueError(
                "SplitConfig.nopeek_weight > 0 is not supported by the "
                "sequence-split LM adapter (supports_nopeek=False); use "
                "cut_noise_std / grad-side defences instead")
        self.cfg = cfg
        self.model = SplitModel(cfg)
        self.loss_fn = self.model.loss_fn

    def init(self, key):
        return self.model.init(key)

    def make_batch(self, owner_arrays: Sequence[np.ndarray],
                   labels: Optional[np.ndarray], idx=None):
        return batching.sequence_batch(owner_arrays, labels, idx)

    def _segment_opts(self, owner_lr: Optional[float] = None,
                      scientist_lr: Optional[float] = None):
        """THE per-segment update rules, shared by the joint and split
        paths.  NOTE the clip scope differs by construction: jointly the
        "heads" rule sees every owner's grads (one global norm), while
        split mode applies the same rule to one owner's slice — the
        honest federated analogue (an owner cannot see peers' grads)."""
        return {
            "heads": chain(clip_by_global_norm(1.0),
                           adam(owner_lr if owner_lr is not None
                                else 1e-3)),
            "trunk": chain(clip_by_global_norm(1.0),
                           adam(scientist_lr if scientist_lr is not None
                                else 1e-3))}

    def default_optimizer(self, owner_lr: Optional[float] = None,
                          scientist_lr: Optional[float] = None):
        return multi_segment(self._segment_opts(owner_lr, scientist_lr))

    def cut_shape(self, batch_size: int,
                  feature_shape: Tuple[int, ...]) -> Tuple[int, ...]:
        """(B, S_p, k): sequence-slice cut activations."""
        return (batch_size, feature_shape[0], self.model.k)

    def make_engine(self, params, **engine_kw):
        if self.cfg.mla is not None:
            # SplitModel's prefill/decode keep MLA's per-head keys and
            # values, 16x the latent a served MLA model caches per token
            raise ValueError(
                f"{self.cfg.name}: serving multi-head latent attention "
                "needs a latent KV cache, which the serving engine does "
                "not have; this model trains (fit) but is not served")
        from repro.launch.engine import ServingEngine   # avoid import cycle
        return ServingEngine(self.model, params, **engine_kw)

    # ------------------------------------------------- split execution
    supports_split = True
    supports_microbatch = True
    # LM cuts are sequence-sliced then concat-combined (and cast to
    # compute dtype per owner) — no sum combine, so no ring aggregation
    supports_masked = False
    supports_nopeek = False

    def owner_programs(self, owner_index: int):
        """Owner ``owner_index``'s jitted segment programs.  The head
        forward embeds + runs the head blocks on the owner's sequence
        slice (global rope positions for that slice), returning ``(cut,
        aux)`` — the scalar aux rides along so split-mode metrics match
        the joint path's heads+trunk aux; the backward is an explicit
        VJP seeded with the received cut gradient plus a unit cotangent
        on that owner-local aux loss (MoE balance gradients never need
        to cross the boundary)."""
        model = self.model

        def build():
            def head_apply(hp, tokens):
                S_p = tokens.shape[-1]
                positions = model._positions(S_p, owner_index)
                cut, _, aux = model._head_one(hp, tokens, positions, 0)
                return cut, aux

            def head_fwd(hp, tokens):
                return head_apply(hp, tokens)

            def head_bwd(hp, tokens, g):
                (cut, aux), vjp = jax.vjp(
                    lambda q: head_apply(q, tokens), hp)
                return vjp((g.astype(cut.dtype),
                            jnp.ones((), aux.dtype)))[0]

            return jax.jit(head_fwd), jax.jit(head_bwd)

        return self._cached(("head_progs", owner_index), build)

    def trunk_program(self):
        model = self.model
        cdt = jnp.dtype(model.cfg.compute_dtype)

        def build():
            def trunk_step(tp, cut, labels):
                def f(tp_, cut_):
                    z = model.combine(cut_.astype(cdt))
                    logits, _, aux_t, stats = model.trunk_forward(
                        tp_, z, with_stats=True)
                    ce = model.ce_loss(logits, labels)
                    return ce + aux_t, {"loss": ce, "aux": aux_t, **stats}

                (_, metrics), (tg, cg) = jax.value_and_grad(
                    f, argnums=(0, 1), has_aux=True)(tp, cut)
                return metrics, tg, cg

            return jax.jit(trunk_step)

        return self._cached("trunk_prog", build)

    def trunk_microbatch_programs(self):
        """Per-chunk scientist programs (GPipe).  The chunk CE is scaled
        ``bm / denom`` (= chunk mean re-weighted to the full-batch mean)
        and the trunk aux loss contributes ``aux / n_micro``, so summing
        metric parts and grads across chunks reproduces full-batch
        semantics; per-owner clipping already makes the LM path
        tolerance- (not bit-) equivalent to the fused joint program.
        With a share layer in the trunk, the parts also hold its
        counters (``models.moe.MOE_STATS``), summed over its blocks: they
        ride the chunk's one fetch with the loss scalars."""
        model = self.model
        cdt = jnp.dtype(model.cfg.compute_dtype)

        def build():
            def chunk_loss(tp, cuts, labels, denom, inv_micro):
                z = model.combine(jnp.stack(cuts).astype(cdt))
                logits, _, aux_t, stats = model.trunk_forward(
                    tp, z, with_stats=True)
                ce = model.ce_loss(logits, labels) \
                    * labels.shape[0] / denom
                aux = aux_t * inv_micro
                return ce + aux, {"loss": ce, "aux": aux, **stats}

            def cutgrad(tp, cuts, labels, denom, inv_micro):
                (_, parts), cg = jax.value_and_grad(
                    lambda c: chunk_loss(tp, c, labels, denom, inv_micro),
                    has_aux=True)(tuple(cuts))
                return cg, parts

            def weightgrad(tp, cuts, labels, denom, inv_micro):
                return jax.grad(
                    lambda p: chunk_loss(p, tuple(cuts), labels, denom,
                                         inv_micro)[0])(tp)

            return jax.jit(cutgrad), jax.jit(weightgrad)

        return self._cached("trunk_micro", build)

    def owner_param_slice(self, params, p: int):
        return jax.tree.map(lambda a: a[p], params["heads"])

    def stack_head_params(self, slices: Sequence):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *slices)

    def owner_batch(self, owner_array: np.ndarray, idx):
        return jnp.asarray(owner_array[idx])

    def owner_optimizer(self, owner_lr: Optional[float] = None):
        return self._segment_opts(owner_lr=owner_lr)["heads"]

    def trunk_optimizer(self, scientist_lr: Optional[float] = None):
        return self._segment_opts(scientist_lr=scientist_lr)["trunk"]
