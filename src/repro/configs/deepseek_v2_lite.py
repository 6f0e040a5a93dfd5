"""deepseek-v2-lite — multi-head latent attention with YaRN, a leading
dense layer, then DeepSeekMoE layers [arXiv:2405.04434;
huggingface.co/deepseek-ai/DeepSeek-V2-Lite, config.json].

27 layers, d_model 2048, 16 heads, vocab 102400.  Attention is MLA with
no query compression: a 512-wide latent (RMS-normed) for keys and values,
128-wide per-head keys without position and one 64-wide rotary key shared
by every head, 128-wide values.  RoPE theta 10000 scaled by YaRN (factor
40 over 4096 positions, beta 32/1, mscale 0.707 on both).  Layer 0 has a
SwiGLU FFN of width 10944; layers 1-26 have 2 shared and 64 routed SwiGLU
experts of width 1408, softmax routing with greedy top-6, weights not
renormalized, and the sequence-wise balance loss (alpha 0.001, the
released config's ``aux_loss_alpha``).  Every expert is held here: the
share layer with all 64 is the uncut, no-drop layer.
"""
from repro.configs.base import (ArchConfig, MLAConfig, MoEConfig,
                                SplitConfig, YaRNConfig)

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    source="arXiv:2405.04434",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab=102400,
    norm_eps=1e-6,
    mlp="swiglu",
    rope_theta=10000.0,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    yarn=YaRNConfig(factor=40.0, original_max_position_embeddings=4096,
                    beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                    mscale_all_dim=0.707),
    n_dense_layers=1,
    moe=MoEConfig(n_experts=64, top_k=6, d_expert=1408, n_shared=2,
                  d_shared=1408, aux_loss_weight=0.001, norm_topk=False,
                  aux_kind="seq", n_held=64),
    long_context="swa",
    long_context_window=8192,
    # the cut at the dense/MoE boundary: each owner's head is the
    # embedding and the dense layer
    split=SplitConfig(n_owners=2, cut_layer=1),
)
