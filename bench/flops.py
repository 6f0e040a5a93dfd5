"""Model operations of a training step, from a configuration's shapes.

Model FLOPs count the matrix products the forward and backward passes
require (backward twice the forward), 2 per multiply-add, with causal
attention over half its context on average; recomputed work (``remat``,
the trunk's second forward in ``weightgrad``) does not count, as MFU
conventionally leaves it out.
"""
from __future__ import annotations


def mla_moe_split_step_flops(cfg: dict, traffic: dict,
                             routed_per_step: float) -> float:
    """One training step of a split MLA + MoE model (DeepSeek-V2 layout,
    ``bench/configs/deepseek-v2-lite.json`` keys): each owner embeds its
    own slice of every sequence and runs the leading dense layers on it,
    the scientist runs the MoE layers and the LM head on the whole
    sequences.  ``routed_per_step``: the token choices that landed on the
    held experts in a step, summed over the MoE layers (the program's
    counter)."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    r, de = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    S, B, P = traffic["seq_len"], traffic["batch"], cfg["n_owners"]
    tokens = B * S
    # multiply-adds per token
    mla = d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd) \
        + H * vd * d

    def attn(ctx):
        return H * (nope + rope + vd) * ctx / 2

    head = n_dense * (mla + attn(S // P) + 3 * d * cfg["intermediate_size"])
    trunk = n_moe * (mla + attn(S) + d * cfg["deployment"]["router_experts"]
                     + 3 * d * cfg["n_shared_experts"] * de)
    lm_head = d * cfg["vocab_size"]
    macs = tokens * (head + trunk + lm_head) + routed_per_step * 3 * d * de
    return 3 * 2 * macs
