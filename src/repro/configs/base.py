"""Configuration dataclasses for architectures, shapes and the SplitNN.

Every assigned architecture is expressed as an ``ArchConfig``; the four
assigned input shapes are ``ShapeConfig``s; the PyVertical split itself
(how many data owners, where the cut layer sits, how the scientist combines
head outputs) is a ``SplitConfig``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block configuration."""

    n_experts: int
    top_k: int
    d_expert: int              # hidden dim of a single routed expert
    n_shared: int = 0          # always-on shared experts (DeepSeekMoE)
    d_shared: int = 0          # hidden dim of the shared expert(s)
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    # §Perf lever: dispatch tokens within G groups aligned to the data
    # axis (group-local capacity) instead of one global scatter — removes
    # the cross-shard all-reduce of the dispatch buffer.  1 = paper-era
    # global dispatch (baseline).
    dispatch_groups: int = 1
    # Routed-expert weights: renormalize the top-k (Mixtral, DeepSeekMoE)
    # or keep the softmax probabilities as they are (DeepSeek-V2).
    norm_topk: bool = True
    # Balance loss: "switch" (f_e of first choices x P_e over the batch)
    # or "seq" (DeepSeek-V2's sequence-wise loss over every top-k choice).
    aux_kind: str = "switch"
    # The share layer (expert parallelism by share): this chip holds the
    # routed experts [held_first, held_first + n_held) of n_experts, still
    # routes over all of them, and computes its own experts' part of the
    # result for every choice that lands on them — no capacity, no dropped
    # choice.  n_held = 0 keeps the capacity layer with every expert held.
    n_held: int = 0
    held_first: int = 0

    @property
    def share(self) -> bool:
        return self.n_held > 0

    @property
    def n_local(self) -> int:
        """Routed experts whose weights this layer holds."""
        return self.n_held if self.share else self.n_experts


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 multi-head latent attention (no query compression):
    keys and values come up from a ``kv_lora_rank`` latent (RMS-normed),
    and one rotary key of ``qk_rope_head_dim`` is shared by every head."""

    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


@dataclass(frozen=True)
class YaRNConfig:
    """YaRN rotary scaling (arXiv:2309.00071) as DeepSeek-V2 sets it."""

    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 / SSD block configuration."""

    d_state: int = 64
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64         # SSD head dim (P in the SSD paper)
    n_groups: int = 1
    chunk_size: int = 256


@dataclass(frozen=True)
class XLSTMConfig:
    """xLSTM block configuration (sLSTM + mLSTM)."""

    m_proj_factor: float = 2.0    # mLSTM up-projection factor
    s_proj_factor: float = 4.0 / 3.0  # sLSTM FFN projection factor
    conv_width: int = 4
    chunk_size: int = 256


@dataclass(frozen=True)
class SplitConfig:
    """The PyVertical multi-headed SplitNN configuration.

    ``n_owners`` data owners each hold a vertical slice of the features of
    the same data subjects.  Each owner runs ``cut_layer`` blocks (its
    *head segment*) locally; the data scientist combines head outputs at the
    cut layer and runs the remaining blocks (the *trunk segment*).
    """

    n_owners: int = 2
    cut_layer: int = 1             # number of blocks in each owner head
    combine: str = "concat"        # concat | sum | mean | max
    cut_dim: int = 0               # 0 = keep d_model (exact); >0 = bottleneck
    owner_lr: float = 0.01         # paper Appendix B
    scientist_lr: float = 0.1      # paper Appendix B
    # beyond-paper privacy options (Titcombe et al. 2021 future-work item)
    cut_noise_std: float = 0.0     # Gaussian noise added to cut activations
    nopeek_weight: float = 0.0     # distance-correlation regularizer weight
    # gradient-side label-leakage defenses (Li et al. 2021): applied to
    # the cut gradients the scientist ships back in split mode —
    # deterministic per (seed, seq, owner), so supervised replay stays
    # bit-identical with defenses on (see core/privacy.py)
    grad_noise_std: float = 0.0    # Gaussian noise on shipped cut grads
    grad_norm_mode: str = "none"   # none | unit (equalize per-example
    #                                norms) | sign (signs at one common
    #                                magnitude)


# ---------------------------------------------------------------------------
# Architecture config
# ---------------------------------------------------------------------------

# Block kinds understood by the transformer assembler.
#   "attn:global"  full causal attention
#   "attn:local"   sliding-window attention (window = swa_window)
#   "mamba2"       SSD block
#   "slstm"/"mlstm" xLSTM blocks
#   "shared_attn"  zamba2-style shared-parameter attention block
BLOCK_KINDS = ("attn:global", "attn:local", "mamba2", "slstm", "mlstm",
               "shared_attn")


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    source: str                    # citation for the config
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    head_dim: int = 0              # 0 → d_model // n_heads
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    norm_eps: float = 1e-5
    post_block_norm: bool = False  # gemma2 pre+post norms
    mlp: str = "swiglu"            # swiglu | geglu | gelu | relu2 | none
    rope: str = "rope"             # rope | mrope | sincos | none
    rope_theta: float = 10000.0
    attn_softcap: float = 0.0      # gemma2 attention logit soft-capping
    logit_softcap: float = 0.0     # gemma2 final logit soft-capping
    swa_window: int = 4096
    tie_embeddings: bool = False

    # Super-block pattern: the repeating unit of heterogeneous blocks.
    # n_layers must be divisible by len(block_pattern); the model is
    # scan-over-superblocks with this unit.  Default: ("attn:global",).
    block_pattern: Tuple[str, ...] = ("attn:global",)

    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    xlstm: Optional[XLSTMConfig] = None
    mla: Optional[MLAConfig] = None        # attention blocks run MLA
    yarn: Optional[YaRNConfig] = None      # rotary frequencies by YaRN
    # leading layers with a dense FFN of width d_ff (DeepSeek's
    # first_k_dense_replace); the MoE FFN applies from this layer on
    n_dense_layers: int = 0

    # encoder-decoder (whisper): head = encoder, trunk = decoder.
    enc_dec: bool = False
    n_enc_layers: int = 0
    enc_bidirectional: bool = True

    # modality of owner inputs: "text" (token ids) | "vision_text" (owner 0
    # holds precomputed patch embeddings — frontend stub) | "audio_text"
    # (owner 0 holds precomputed frame embeddings — frontend stub)
    modality: str = "text"
    d_frontend: int = 0            # stub frontend embedding dim (0 → d_model)

    # long-context handling: "native" (sub-quadratic already),
    # "swa" (explicit sliding-window variant used ONLY for long_500k),
    # "skip" (architecture cannot meaningfully run 500k decode)
    long_context: str = "swa"
    long_context_window: int = 8192

    split: SplitConfig = field(default_factory=SplitConfig)

    # dtype policy
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    zero_sharding: bool = False    # additionally shard params over "data"
    remat: bool = True             # activation-checkpoint each super-block

    def __post_init__(self):
        if self.mla is not None:
            # q/k heads are nope + rope wide (values are v_head_dim)
            object.__setattr__(self, "head_dim", self.mla.qk_head_dim)
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.enc_dec:
            if self.n_enc_layers <= 0:
                raise ValueError("enc_dec arch needs n_enc_layers")
        else:
            if self.n_layers % len(self.block_pattern) != 0:
                raise ValueError(
                    f"{self.name}: n_layers={self.n_layers} not divisible by "
                    f"block pattern of length {len(self.block_pattern)}")

    # ---- derived quantities -------------------------------------------------

    @property
    def n_superblocks(self) -> int:
        return self.n_layers // len(self.block_pattern)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def with_split(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, split=dataclasses.replace(self.split, **kw))

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ArchConfig":
        """The smoke-test variant: same family/block pattern, tiny dims."""
        pattern = self.block_pattern
        n_layers = len(pattern) if not self.enc_dec else 2
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        head_dim = max(d_model // n_heads, 16)
        n_kv = min(self.n_kv_heads, n_heads)
        kw = dict(
            n_layers=n_layers,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=head_dim,
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab=min(self.vocab, 512),
            swa_window=64,
            long_context_window=128,
            zero_sharding=False,
        )
        if self.enc_dec:
            kw["n_enc_layers"] = 2
        if self.moe is not None:
            kw["moe"] = dataclasses.replace(
                self.moe,
                n_experts=min(self.moe.n_experts, 4),
                top_k=min(self.moe.top_k, 2),
                d_expert=min(self.moe.d_expert, 128),
                d_shared=min(self.moe.d_shared, 128) if self.moe.d_shared else 0,
            )
        if self.ssm is not None:
            kw["ssm"] = dataclasses.replace(
                self.ssm, d_state=16, head_dim=32, chunk_size=32)
        if self.xlstm is not None:
            kw["xlstm"] = dataclasses.replace(self.xlstm, chunk_size=32)
        if self.mla is not None:
            kw["mla"] = MLAConfig(kv_lora_rank=64, qk_nope_head_dim=32,
                                  qk_rope_head_dim=16, v_head_dim=32)
        if self.moe is not None and self.moe.share:
            n_exp = kw["moe"].n_experts
            kw["moe"] = dataclasses.replace(
                kw["moe"], n_held=min(self.moe.n_held, n_exp),
                held_first=min(self.moe.held_first,
                               n_exp - min(self.moe.n_held, n_exp)))
        if self.n_dense_layers:
            # one dense layer (each owner's head) and one after it
            kw["n_dense_layers"] = 1
            kw["n_layers"] = 2
        return dataclasses.replace(self, **kw)

    def param_count(self, active_only: bool = False) -> int:
        """Approximate parameter count (analytic, for roofline MODEL_FLOPS).

        ``active_only`` counts only routed-expert params actually used per
        token (top_k of n_experts) — the MoE "active parameters" convention.
        """
        d, v = self.d_model, self.vocab
        total = v * d  # embedding
        if not self.tie_embeddings:
            total += v * d  # lm head
        per_layer = {}

        def attn_params():
            if self.mla is not None:
                m, h = self.mla, self.n_heads
                return (d * h * m.qk_head_dim                    # W_q
                        + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                        + m.kv_lora_rank                         # latent norm
                        + m.kv_lora_rank * h * (m.qk_nope_head_dim
                                                + m.v_head_dim)
                        + h * m.v_head_dim * d)                  # W_o
            return d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d

        def mlp_params(d_ff):
            if self.mlp in ("swiglu", "geglu"):
                return 3 * d * d_ff
            return 2 * d * d_ff

        n_dense = 0
        for kind in set(self.block_pattern):
            if kind.startswith("attn") or kind == "shared_attn":
                p = attn_params()
                if self.moe is not None:
                    e = self.moe
                    # active: the top-k of the held experts' share
                    routed = (e.top_k * e.n_local / e.n_experts
                              if active_only else e.n_local)
                    p += int(routed * 3 * d * e.d_expert)
                    p += e.n_shared * 3 * d * max(e.d_shared, e.d_expert)
                    p += d * e.n_experts  # router
                    if self.n_dense_layers:
                        # leading dense layers: their FFN in place of the
                        # MoE FFN
                        moe_ffn = p - attn_params()
                        n_dense = self.n_dense_layers * (
                            mlp_params(self.d_ff) - moe_ffn)
                elif self.d_ff:
                    p += mlp_params(self.d_ff)
            elif kind == "mamba2":
                s = self.ssm
                d_in = s.expand * d
                p = d * (2 * d_in + 2 * s.n_groups * s.d_state) + d_in * d
                p += d_in  # dt, A, D etc. (order-d_in terms)
            elif kind in ("slstm", "mlstm"):
                x = self.xlstm
                f = x.m_proj_factor if kind == "mlstm" else x.s_proj_factor
                d_in = int(f * d)
                p = 2 * d * d_in + d_in * d + 4 * d_in * d_in // 4
            else:
                raise ValueError(kind)
            per_layer[kind] = p

        n_units = self.n_superblocks
        total += n_dense
        shared_counted = False
        for kind in self.block_pattern:
            if kind == "shared_attn":
                if not shared_counted:
                    total += per_layer[kind]  # params shared across reuses
                    shared_counted = True
            else:
                total += n_units * per_layer[kind]
        if self.enc_dec:
            # decoder layers: self-attn + cross-attn + mlp
            dec = self.n_layers * (2 * attn_params() + mlp_params(self.d_ff))
            total += dec
        return total


# ---------------------------------------------------------------------------
# Input shapes
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # "train" | "prefill" | "decode"


TRAIN_4K = ShapeConfig("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524_288, 1, "decode")

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}
