"""SplitModel — the paper's multi-headed SplitNN wrapped around any
assigned architecture.

The full network (``cfg.n_superblocks`` super-blocks) is split by layer:
each of ``cfg.split.n_owners`` data owners runs an identical *head segment*
(embedding + ``cut_layer`` super-blocks) on its private vertical slice of
the input; the data scientist combines the cut-layer activations
(concat | sum | mean | max) and runs the *trunk segment* (remaining
super-blocks + final norm + LM head) and the loss.

Vertical-partition semantics per family (DESIGN.md §2):
  text     owner p holds sequence slice [p*S/P, (p+1)*S/P)
  vlm      owner 0 holds patch embeddings (frontend stub), owner 1 text
  audio    owner 0 holds frame embeddings; head = whisper encoder,
           trunk = whisper decoder (enc-dec IS a SplitNN)

Head params for text archs are stacked on a leading owner dim (the paper's
symmetric-segment assumption) so the owner dim can be sharded over the
``pod`` mesh axis — the cut-layer all-gather is then the only cross-pod
collective, PyVertical's communication pattern at datacenter scale.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import attention, layers, transformer
from repro.sharding.specs import constrain

Params = Dict[str, Any]


def _cdtype(cfg):
    return jnp.dtype(cfg.compute_dtype)


def _pdtype(cfg):
    return jnp.dtype(cfg.param_dtype)


class SplitModel:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        sp = cfg.split
        self.P = sp.n_owners
        if cfg.enc_dec:
            self.n_head_units = cfg.n_enc_layers  # encoder layers (pattern len 1)
            self.n_trunk_units = cfg.n_layers
            self.head_pattern = ("attn:global",)  # bidirectional handled below
            self.trunk_pattern = ("dec",)
        else:
            n_units = cfg.n_superblocks
            cut = min(max(sp.cut_layer, 1), n_units - 1)
            self.n_head_units = cut
            self.n_trunk_units = n_units - cut
            self.head_pattern = cfg.block_pattern
            self.trunk_pattern = cfg.block_pattern
        # FFN kind of each segment's blocks (None: the config's).  With
        # leading dense layers, the cut falls at the dense/MoE boundary:
        # the heads hold the dense layers, the trunk the MoE ones.
        self.head_ffn = self.trunk_ffn = None
        if cfg.n_dense_layers:
            if (cfg.enc_dec or len(cfg.block_pattern) != 1
                    or self.n_head_units != cfg.n_dense_layers):
                raise ValueError(
                    f"{cfg.name}: the cut (cut_layer={sp.cut_layer}) must "
                    f"fall at the dense/MoE boundary (n_dense_layers="
                    f"{cfg.n_dense_layers}) of a one-kind block pattern")
            self.head_ffn, self.trunk_ffn = "dense", "moe"
        self.k = sp.cut_dim if sp.cut_dim > 0 else cfg.d_model

    # ------------------------------------------------------------------ init

    def _head_init_one(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        p: Params = {"blocks": transformer.stack_init(
            ks[0], cfg, self.n_head_units, self.head_pattern, _pdtype(cfg),
            ffn=self.head_ffn)}
        if cfg.modality == "text":
            p["embed"] = layers.embed_init(ks[1], cfg.vocab, cfg.d_model,
                                           _pdtype(cfg))
        elif cfg.modality == "vision_text":
            # owner 0: frontend projection; owner 1: token embedding.
            # symmetric param STRUCTURE (stackable), asymmetric use.
            p["embed"] = layers.embed_init(ks[1], cfg.vocab, cfg.d_model,
                                           _pdtype(cfg))
            p["front_proj"] = layers.dense_init(
                ks[2], cfg.d_frontend or cfg.d_model, cfg.d_model,
                _pdtype(cfg))
        elif cfg.modality == "audio_text":
            p["front_proj"] = layers.dense_init(
                ks[2], cfg.d_frontend or cfg.d_model, cfg.d_model,
                _pdtype(cfg))
        if cfg.split.cut_dim > 0:
            p["cut_proj"] = layers.dense_init(ks[3], cfg.d_model, self.k,
                                              _pdtype(cfg))
        return p

    def init(self, key) -> Params:
        cfg = self.cfg
        kh, kt = jax.random.split(key)
        head_keys = jax.random.split(kh, self.P)
        heads = jax.vmap(self._head_init_one)(head_keys)

        ks = jax.random.split(kt, 4)
        trunk: Params = {"blocks": transformer.stack_init(
            ks[0], cfg, self.n_trunk_units, self.trunk_pattern, _pdtype(cfg),
            ffn=self.trunk_ffn)}
        if cfg.split.cut_dim > 0:
            trunk["in_proj"] = layers.dense_init(ks[1], self.k, cfg.d_model,
                                                 _pdtype(cfg))
        trunk["out_norm"] = layers.norm_init(cfg.d_model, cfg.norm,
                                             _pdtype(cfg))
        trunk["lm_head"] = layers.dense_init(ks[2], cfg.d_model, cfg.vocab,
                                             _pdtype(cfg), scale=0.02)
        if cfg.enc_dec:
            trunk["embed"] = layers.embed_init(ks[3], cfg.vocab, cfg.d_model,
                                               _pdtype(cfg))
        return {"heads": heads, "trunk": trunk}

    def param_specs(self, key=None):
        """Shape/dtype structure of params without allocating (dry-run)."""
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(self.init, key)

    # ------------------------------------------------------------- embedding

    def _embed_owner(self, hp, owner_inputs, owner_index, dtype):
        """Map one owner's raw vertical slice to (B, S_p, d)."""
        cfg = self.cfg
        if cfg.modality == "text":
            return layers.embed_apply(hp["embed"], owner_inputs, dtype)
        if cfg.modality == "vision_text":
            if owner_index == 0:   # vision owner: precomputed patch embeds
                return layers.dense_apply(hp["front_proj"],
                                          owner_inputs.astype(dtype))
            return layers.embed_apply(hp["embed"], owner_inputs, dtype)
        if cfg.modality == "audio_text":
            return layers.dense_apply(hp["front_proj"],
                                      owner_inputs.astype(dtype))
        raise ValueError(cfg.modality)

    def _positions(self, S_p: int, owner: int, offset=0):
        """Global positions of owner ``owner``'s slice (rope input)."""
        cfg = self.cfg
        base = owner * S_p + offset + jnp.arange(S_p)
        if cfg.rope == "mrope":
            if cfg.modality == "vision_text" and owner == 0:
                # vision grid (t=0, h, w): synthetic sqrt grid
                side = max(int(np.sqrt(S_p)), 1)
                h = jnp.arange(S_p) // side
                w = jnp.arange(S_p) % side
                t = jnp.zeros((S_p,), jnp.int32)
                return jnp.stack([t, h, w], axis=-1)
            return jnp.stack([base] * 3, axis=-1)
        return base

    # ------------------------------------------------------------ head pass

    def _head_one(self, hp, owner_inputs, positions, owner_index,
                  caches=None, pos=None, swa_override=None):
        cfg = self.cfg
        x = self._embed_owner(hp, owner_inputs, owner_index, _cdtype(cfg))
        if cfg.rope == "sincos":
            S_p = x.shape[1]
            off = pos if pos is not None else 0
            x = x + layers.sincos_positions(off + jnp.arange(S_p),
                                            cfg.d_model).astype(x.dtype)
        x, new_caches, aux = transformer.stack_apply(
            hp["blocks"], x, cfg=cfg, pattern=self.head_pattern,
            positions=positions, caches=caches, pos=pos,
            swa_override=swa_override,
            bidir=cfg.enc_dec and cfg.enc_bidirectional, ffn=self.head_ffn)
        if cfg.split.cut_dim > 0:
            x = layers.dense_apply(hp["cut_proj"], x)
        return x, new_caches, aux

    def heads_forward(self, heads, owner_inputs, *, caches=None, pos=None,
                      rng=None, swa_override=None):
        """owner_inputs: text: (P, B, S_p) — vmapped over owners.
        vlm/audio: dict with per-owner entries — python loop (asymmetric).
        Returns (cut (P, B, S_p, k), caches, aux)."""
        cfg = self.cfg
        if cfg.modality == "text":
            S_p = owner_inputs.shape[-1]
            positions = jnp.stack(
                [self._positions(S_p, p, 0 if pos is None else pos)
                 for p in range(self.P)])

            def one(hp, ti, po, ca):
                return self._head_one(hp, ti, po, 0, ca, pos, swa_override)

            if caches is None:
                cut, new_caches, aux = jax.vmap(
                    lambda hp, ti, po: one(hp, ti, po, None))(
                        heads, owner_inputs, positions)
            else:
                cut, new_caches, aux = jax.vmap(one)(
                    heads, owner_inputs, positions, caches)
            aux = jnp.sum(aux)
        else:
            # asymmetric modality heads: loop owners (P == ragged inputs)
            cuts, new_caches, aux = [], [], 0.0
            keys = list(owner_inputs.keys())
            for p, name in enumerate(keys):
                hp = jax.tree.map(lambda a: a[p], heads)
                S_p = owner_inputs[name].shape[1]
                positions = self._positions(S_p, p,
                                            0 if pos is None else pos)
                ca = None if caches is None else caches[name]
                c, nc, a = self._head_one(hp, owner_inputs[name], positions,
                                          p, ca, pos, swa_override)
                cuts.append(c)
                new_caches.append(nc)
                aux = aux + a
            cut = jnp.stack(cuts) if len({c.shape for c in cuts}) == 1 \
                else cuts
            new_caches = (None if caches is None
                          else dict(zip(keys, new_caches)))
            return cut, new_caches, aux
        return cut, new_caches, aux

    # ------------------------------------------------------------- combine

    def combine(self, cut, rng=None):
        """The paper's cut-layer combine (data-scientist side).

        cut: (P, B, S_p, k) stacked or list of (B, S_i, k).
        concat: along the sequence (ID-aligned order) -> (B, S, k)
        sum/mean/max: elementwise across owners -> (B, S_p, k)
        """
        sp = self.cfg.split
        if sp.cut_noise_std > 0.0 and rng is not None:
            noise = lambda a: a + sp.cut_noise_std * jax.random.normal(
                rng, a.shape, a.dtype)
            cut = ([noise(c) for c in cut] if isinstance(cut, list)
                   else noise(cut))
        if isinstance(cut, list):
            if sp.combine != "concat":
                raise ValueError("ragged cuts support concat only")
            return jnp.concatenate(cut, axis=1)
        P, B, S_p, k = cut.shape
        if sp.combine == "concat":
            return cut.transpose(1, 0, 2, 3).reshape(B, P * S_p, k)
        if sp.combine == "sum":
            return cut.sum(0)
        if sp.combine == "mean":
            return cut.mean(0)
        if sp.combine == "max":
            return cut.max(0)
        raise ValueError(sp.combine)

    # ---------------------------------------------------------- trunk pass

    def trunk_forward(self, trunk, z, *, caches=None, pos=None, enc_out=None,
                      dec_tokens=None, swa_override=None,
                      with_stats: bool = False):
        """z: combined cut (B, S, k) (or enc output for enc_dec).

        enc_dec: trunk is the whisper decoder over ``dec_tokens`` with
        cross-attention to z.  Returns (logits, caches, aux), and the
        share layer's counters (``{}`` without one) when ``with_stats``."""
        cfg = self.cfg
        if cfg.split.cut_dim > 0:
            z = layers.dense_apply(trunk["in_proj"], z)
        if cfg.enc_dec:
            x = layers.embed_apply(trunk["embed"], dec_tokens, _cdtype(cfg))
            off = pos if pos is not None else 0
            S_d = dec_tokens.shape[1]
            x = x + layers.sincos_positions(off + jnp.arange(S_d),
                                            cfg.d_model).astype(x.dtype)
            positions = (pos if pos is not None else 0) + jnp.arange(S_d)
            enc_out = z
        else:
            x = z
            S = x.shape[1]
            off = pos if pos is not None else 0
            base = off + jnp.arange(S)
            positions = (jnp.stack([base] * 3, -1) if cfg.rope == "mrope"
                         else base)
        x, new_caches, aux, stats = transformer.stack_apply(
            trunk["blocks"], x, cfg=cfg, pattern=self.trunk_pattern,
            positions=positions, caches=caches, pos=pos, enc_out=enc_out,
            swa_override=swa_override, ffn=self.trunk_ffn, with_stats=True)
        x = layers.norm_apply(trunk["out_norm"], x, cfg.norm, cfg.norm_eps)
        x = constrain(x, "trunk_hidden")
        logits = layers.dense_apply(trunk["lm_head"],
                                    x.astype(jnp.float32))
        logits = layers.softcap(logits, cfg.logit_softcap)
        logits = constrain(logits, "logits")
        if with_stats:
            return logits, new_caches, aux, stats
        return logits, new_caches, aux

    # ------------------------------------------------------------- forward

    def split_owner_inputs(self, batch):
        """Vertical partition of a global batch into per-owner slices."""
        cfg = self.cfg
        if "owner_tokens" in batch:                   # pre-partitioned (P,B,S_p)
            return batch["owner_tokens"]
        if cfg.modality == "text":
            t = batch["tokens"]                       # (B, S)
            B, S = t.shape
            S_p = S // self.P
            return t.reshape(B, self.P, S_p).transpose(1, 0, 2)
        if cfg.modality == "vision_text":
            return {"patches": batch["patches"], "tokens": batch["tokens"]}
        if cfg.modality == "audio_text":
            return {"frames": batch["frames"]}
        raise ValueError(cfg.modality)

    def forward(self, params, batch, *, rng=None, swa_override=None):
        """Full-sequence forward (train / prefill-no-cache).

        Returns (logits (B, S, vocab), aux)."""
        cfg = self.cfg
        oi = self.split_owner_inputs(batch)
        cut, _, aux_h = self.heads_forward(params["heads"], oi, rng=rng,
                                           swa_override=swa_override)
        if not isinstance(cut, list):
            # the cut tensor is THE protocol traffic (owner -> scientist):
            # pin it to the compute dtype so the cross-pod gather moves
            # bf16, not an upcast (§Perf cut-precision lever)
            cut = constrain(cut.astype(_cdtype(self.cfg)), "cut_stacked")
        z = self.combine(cut, rng=rng)
        z = constrain(z, "combined")
        dec_tokens = batch.get("tokens") if cfg.enc_dec else None
        logits, _, aux_t = self.trunk_forward(
            params["trunk"], z, dec_tokens=dec_tokens,
            swa_override=swa_override)
        return logits, aux_h + aux_t

    @staticmethod
    def ce_loss(logits, labels):
        """Causal LM loss (labels: next-token ids, -100 = masked)."""
        valid = labels >= 0
        lab = jnp.where(valid, labels, 0)
        # vocab-sharding-friendly CE: never gathers the (B, S, V) logits —
        # logsumexp is a sharded reduction and the label logit is picked
        # with an iota comparison (elementwise on the sharded dim).
        lse = jax.scipy.special.logsumexp(logits, axis=-1)        # (B, S)
        vio = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1)
        label_logit = jnp.sum(
            jnp.where(vio == lab[..., None], logits, 0.0), axis=-1)
        ll = label_logit - lse
        n = jnp.maximum(jnp.sum(valid), 1)
        return -jnp.sum(ll * valid) / n

    def loss_fn(self, params, batch, *, rng=None, swa_override=None):
        logits, aux = self.forward(params, batch, rng=rng,
                                   swa_override=swa_override)
        loss = self.ce_loss(logits, batch["labels"])
        return loss + aux, {"loss": loss, "aux": aux}

    # ------------------------------------------------------------ serving

    def cache_init(self, batch_size: int, s_max: int, n_new: int = 8,
                   ring: bool = False, swa_override: int = 0,
                   cache_dtype=None):
        """Decode caches.  Trunk cache covers the combined sequence; head
        caches cover each owner's slice (+ room for generated tokens).
        ``ring``: trim sliding-window layers to ring buffers (§Perf);
        ``cache_dtype``: e.g. float8_e4m3fn KV storage (§Perf)."""
        cfg = self.cfg
        dt = jnp.dtype(cache_dtype) if cache_dtype else _cdtype(cfg)
        kw = dict(ring=ring, swa_override=swa_override)
        if cfg.modality == "text":
            s_head = s_max // self.P + n_new
            one = transformer.stack_cache_init(
                batch_size, cfg, self.n_head_units, s_head,
                self.head_pattern, dt, **kw)
            head_caches = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (self.P,) + a.shape), one)
        elif cfg.modality == "vision_text":
            s_head = s_max // self.P + n_new
            one = transformer.stack_cache_init(
                batch_size, cfg, self.n_head_units, s_head,
                self.head_pattern, dt, **kw)
            head_caches = {"patches": one, "tokens": jax.tree.map(
                jnp.copy, one)}
        else:   # audio: encoder is cache-free at decode (static enc_out)
            head_caches = None
        s_trunk = s_max + n_new
        trunk_caches = transformer.stack_cache_init(
            batch_size, cfg, self.n_trunk_units, s_trunk,
            self.trunk_pattern, dt, **kw)
        out = {"heads": head_caches, "trunk": trunk_caches}
        if cfg.enc_dec:
            out["enc"] = jnp.zeros((batch_size, s_max // 2, self.k), dt)
        return out

    def prefill(self, params, batch, caches, *, swa_override=None):
        """Process the full context, building caches.  Returns
        (last-token logits, caches)."""
        cfg = self.cfg
        oi = self.split_owner_inputs(batch)
        cut, head_caches, _ = self.heads_forward(
            params["heads"], oi, caches=caches["heads"], pos=0,
            swa_override=swa_override)
        z = self.combine(cut)
        if cfg.enc_dec:
            # encoder output is static: stash it; prefill decoder tokens.
            logits, trunk_caches, _ = self.trunk_forward(
                params["trunk"], z, caches=caches["trunk"], pos=0,
                dec_tokens=batch["tokens"], swa_override=swa_override)
            return logits[:, -1], {"heads": head_caches,
                                   "trunk": trunk_caches, "enc": z}
        logits, trunk_caches, _ = self.trunk_forward(
            params["trunk"], z, caches=caches["trunk"], pos=0,
            swa_override=swa_override)
        return logits[:, -1], {"heads": head_caches, "trunk": trunk_caches}

    # ------------------------------------------- per-segment serving programs
    #
    # prefill/decode_step above run heads + trunk as one program.  When the
    # engine serves through a transport-backed boundary, it uses these
    # split halves instead, so the cut activations are a real wire payload
    # (measured bytes) rather than an internal value.  Text, decoder-only.

    def prefill_heads(self, heads, owner_inputs, head_caches, *,
                      swa_override=None):
        """Owner side of prefill: (cut (P, B, S_p, k), head caches)."""
        cut, hc, _ = self.heads_forward(heads, owner_inputs,
                                        caches=head_caches, pos=0,
                                        swa_override=swa_override)
        return cut, hc

    def prefill_trunk(self, trunk, cut, trunk_caches, *, swa_override=None):
        """Scientist side of prefill: combine the received cut and run the
        trunk.  Returns (last-token logits, trunk caches)."""
        z = self.combine(cut)
        logits, tc, _ = self.trunk_forward(trunk, z, caches=trunk_caches,
                                           pos=0, swa_override=swa_override)
        return logits[:, -1], tc

    def decode_heads(self, heads, token, head_caches, pos_local, *,
                     swa_override=None):
        """Owner side of one decode step: the generation owner's cut slice
        (B, 1, k) plus updated head caches."""
        oi = jnp.broadcast_to(token[None], (self.P,) + token.shape)
        cut, hc, _ = self.heads_forward(heads, oi, caches=head_caches,
                                        pos=pos_local,
                                        swa_override=swa_override)
        return cut[0], hc

    def decode_trunk(self, trunk, z, trunk_caches, pos, *,
                     swa_override=None):
        logits, tc, _ = self.trunk_forward(trunk, z, caches=trunk_caches,
                                           pos=pos,
                                           swa_override=swa_override)
        return logits[:, -1], tc

    def decode_step(self, params, caches, token, pos, pos_local,
                    *, swa_override=None):
        """One new token (B, 1).  The generation owner is owner 0 (the
        paper allows the scientist to also be a data owner).  ``pos``:
        global position in the combined sequence; ``pos_local``: position
        within owner 0's slice/cache."""
        cfg = self.cfg
        if cfg.enc_dec:
            logits, trunk_caches, _ = self.trunk_forward(
                params["trunk"], caches["enc"], caches=caches["trunk"],
                pos=pos, dec_tokens=token, swa_override=swa_override)
            new = dict(caches)
            new["trunk"] = trunk_caches
            return logits[:, -1], new

        if cfg.modality == "text":
            oi = jnp.broadcast_to(token[None], (self.P,) + token.shape)
            cut, head_caches, _ = self.heads_forward(
                params["heads"], oi, caches=caches["heads"], pos=pos_local,
                swa_override=swa_override)
            z = cut[0]                                 # generation owner
        else:   # vlm: route the new token through the text-owner head
            hp = jax.tree.map(lambda a: a[1], params["heads"])
            positions = pos + jnp.arange(1)
            if cfg.rope == "mrope":
                positions = jnp.stack([positions] * 3, -1)
            z, tok_caches, _ = self._head_one(
                hp, token, positions, 1, caches["heads"]["tokens"],
                pos_local, swa_override)
            head_caches = {"patches": caches["heads"]["patches"],
                           "tokens": tok_caches}
        logits, trunk_caches, _ = self.trunk_forward(
            params["trunk"], z, caches=caches["trunk"], pos=pos,
            swa_override=swa_override)
        return logits[:, -1], {"heads": head_caches, "trunk": trunk_caches}
