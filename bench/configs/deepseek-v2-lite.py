"""Hands the ``deepseek-v2-lite`` configuration and its token data to the
program: a ``repro`` ``ArchConfig`` with the widths of
``deepseek-v2-lite.json`` and this chip's share of its deployment (the
routed experts it holds, with the router over all of them), two owners
each holding one half of every sequence for a random 90% of the entities,
and the scientist holding every sequence's next-token labels."""
from __future__ import annotations

import numpy as np


def program_config(cfg: dict):
    from repro.configs.base import (ArchConfig, MLAConfig, MoEConfig,
                                    SplitConfig, YaRNConfig)
    dep, rs = cfg["deployment"], cfg["rope_scaling"]
    if rs["type"] != "yarn" or cfg["q_lora_rank"] is not None:
        raise ValueError("the program models YaRN and MLA without query "
                         "compression only")
    return ArchConfig(
        name=cfg["name"], family="moe", source=cfg["source"],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        norm_eps=cfg["rms_norm_eps"], mlp="swiglu",
        rope_theta=float(cfg["rope_theta"]),
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        yarn=YaRNConfig(
            factor=float(rs["factor"]),
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]),
            beta_slow=float(rs["beta_slow"]), mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        n_dense_layers=cfg["first_k_dense_replace"],
        moe=MoEConfig(n_experts=dep["router_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_expert=cfg["moe_intermediate_size"],
                      n_shared=cfg["n_shared_experts"],
                      d_shared=cfg["moe_intermediate_size"],
                      aux_loss_weight=cfg["aux_loss_alpha"],
                      norm_topk=cfg["norm_topk_prob"],
                      aux_kind="seq" if cfg["seq_aux"] else "switch",
                      n_held=cfg["n_routed_experts"],
                      held_first=dep["held_first"]),
        split=SplitConfig(n_owners=cfg["n_owners"],
                          cut_layer=cfg["cut_layer"],
                          combine=cfg["combine"], cut_dim=cfg["cut_dim"]),
        param_dtype=cfg["param_dtype"], compute_dtype=cfg["compute_dtype"])


class Data:
    def __init__(self, cfg: dict, traffic: dict, seed: int, reference):
        from repro.federation import DataOwner, DataScientist
        n, P, S = traffic["n_entities"], cfg["n_owners"], traffic["seq_len"]
        tokens, labels = reference.make_data(cfg, n, seed, S,
                                             traffic["zipf_s"])
        halves = tokens.reshape(n, P, S // P)
        self.ids = [f"doc-{i:08d}" for i in range(n)]
        rng = np.random.default_rng([seed, 1])
        kept = []
        self.owners = []
        for p in range(P):
            idx = np.flatnonzero(rng.random(n) < traffic["keep_frac"])
            rng.shuffle(idx)
            kept.append(set(idx.tolist()))
            self.owners.append(DataOwner(
                f"owner{p}", [self.ids[i] for i in idx], halves[idx, p]))
        self.scientist = DataScientist(self.ids, labels)
        shared = set.intersection(*kept)
        self.aligned = sorted(self.ids[i] for i in shared)
        self.rows = np.array(sorted(shared))     # id order == index order
        self.tokens, self.labels = tokens, labels

    def reference_batch(self, positions):
        r = self.rows[np.asarray(positions)]
        return self.tokens[r], self.labels[r]


def units_per_step(cfg: dict, traffic: dict) -> dict:
    return {"samples": traffic["batch"]}
