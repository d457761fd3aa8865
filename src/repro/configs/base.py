"""Config system: one dataclass describes every supported architecture family.

Every assigned architecture gets a module ``src/repro/configs/<id>.py`` exporting
``CONFIG`` (the full published config) and ``SMOKE_CONFIG`` (a reduced same-family
config for CPU smoke tests). ``repro.configs.get_config(name)`` resolves either.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Sequence


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # "dense" | "moe" | "ssm" | "hybrid" | "encdec"

    n_layers: int
    d_model: int
    vocab_size: int

    # Attention (ignored for pure-SSM layers).
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    qkv_bias: bool = False
    # biases on the attention output and both MLP matrices (StarCoder2's
    # use_bias), beside the q, k, v biases of qkv_bias
    out_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    # gemma2-style alternating local/global attention. 0 => all-global.
    sliding_window: int = 0
    local_global_period: int = 0  # e.g. 2 => layers alternate local, global
    attn_logit_softcap: float = 0.0
    final_logit_softcap: float = 0.0

    # MLP
    d_ff: int = 0
    mlp_gated: bool = True  # SwiGLU/GeGLU vs plain
    mlp_act: str = "silu"  # "silu" | "gelu"

    # MoE
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0  # per-expert ffn dim
    # dense d_ff is used for shared experts * n_shared (deepseek style uses moe_d_ff)
    moe_aux_loss_coef: float = 0.001
    moe_capacity_factor: float = 1.25

    # SSM (Mamba2 / SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4
    ssm_chunk: int = 256
    # hybrid (zamba2): a shared-parameter attention block applied every N ssm layers
    hybrid_attn_period: int = 0

    # Encoder-decoder (whisper): encoder frames are precomputed stub embeddings.
    encoder_layers: int = 0
    encoder_frames_ratio: int = 4  # enc_len = seq_len // ratio

    # Norm
    norm_type: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    rms_eps: float = 1e-6
    tie_embeddings: bool = True

    # ---- perf knobs (§Perf hillclimb; defaults = paper-faithful baseline) --
    attn_scores_bf16: bool = False   # attention score matrix in bf16
    ssd_mask_bf16: bool = False      # SSD decay mask in bf16
    loss_onehot_bf16: bool = False   # label one-hot in bf16
    remat_policy: str = "nothing"    # "nothing" | "dots" (save dot outputs)
    # Measurement instrument ONLY (never a shipping config): replaces the
    # softmax(QK^T)V product with a traffic-free stand-in so
    # (baseline - stub) isolates the S^2 score traffic that the Pallas flash
    # kernel keeps in VMEM. See EXPERIMENTS.md §Perf.
    attn_traffic_stub: bool = False

    # Training
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: bool = True
    # per-arch microbatch count for train_4k (None => global default of 4);
    # sized so per-chip activation temps fit 16 GiB HBM (see EXPERIMENTS.md)
    train_microbatches: Optional[int] = None

    # ---- derived ----
    @property
    def vocab_padded(self) -> int:
        return _round_up(self.vocab_size, 256)

    @property
    def n_experts_padded(self) -> int:
        """Routed experts padded to a multiple of 16 for EP over model=16.
        Padded experts receive no tokens (router width stays n_routed)."""
        if self.n_routed_experts >= 16:
            return _round_up(self.n_routed_experts, 16)
        return self.n_routed_experts

    @property
    def d_inner(self) -> int:  # ssm inner width
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim if self.ssm_state else 0

    @property
    def is_subquadratic(self) -> bool:
        """True when long-context decode (500k) is feasible: SSM state carries
        the context, so per-token cost does not scale with a dense KV cache."""
        return self.family in ("ssm", "hybrid")

    @property
    def n_attn_layers(self) -> int:
        if self.family == "ssm":
            return 0
        if self.family == "hybrid":
            return max(1, self.n_layers // max(1, self.hybrid_attn_period))
        return self.n_layers

    def param_count(self) -> int:
        """Approximate parameter count (for MODEL_FLOPS = 6*N*D)."""
        c = self
        n = c.vocab_padded * c.d_model  # embed (tied head)
        if not c.tie_embeddings:
            n += c.vocab_padded * c.d_model
        per_attn = (
            c.d_model * (c.n_heads * c.head_dim)
            + 2 * c.d_model * (c.n_kv_heads * c.head_dim)
            + (c.n_heads * c.head_dim) * c.d_model
        )
        gate = 3 if c.mlp_gated else 2
        per_mlp = gate * c.d_model * c.d_ff
        per_moe = 0
        if c.n_routed_experts:
            per_moe = (
                c.n_routed_experts * gate * c.d_model * c.moe_d_ff
                + c.n_shared_experts * gate * c.d_model * c.moe_d_ff
                + c.d_model * c.n_routed_experts  # router
            )
        per_ssm = 0
        if c.ssm_state:
            d_in = c.d_inner
            nh = c.n_ssm_heads
            # in_proj produces [z, x, B, C, dt]
            zxbcdt = 2 * d_in + 2 * c.ssm_state + nh
            per_ssm = c.d_model * zxbcdt + d_in * c.d_model + nh * 3  # + A,D,dt_bias
        if c.family == "dense" or c.family == "encdec":
            n += c.n_layers * (per_attn + per_mlp)
            if c.family == "encdec":
                # encoder self-attn + mlp, decoder adds cross-attn
                n += c.encoder_layers * (per_attn + per_mlp)
                n += c.n_layers * per_attn  # cross attention
        elif c.family == "moe":
            n += c.n_layers * (per_attn + per_moe)
        elif c.family == "ssm":
            n += c.n_layers * per_ssm
        elif c.family == "hybrid":
            n += c.n_layers * per_ssm + (per_attn + per_mlp)  # shared attn block
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: routed top-k + shared only)."""
        c = self
        if not c.n_routed_experts:
            return self.param_count()
        gate = 3 if c.mlp_gated else 2
        full_moe = c.n_routed_experts * gate * c.d_model * c.moe_d_ff
        active_moe = (c.moe_top_k + c.n_shared_experts) * gate * c.d_model * c.moe_d_ff
        return self.param_count() - c.n_layers * (full_moe - (c.moe_top_k * gate * c.d_model * c.moe_d_ff))


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

ARCH_IDS: Sequence[str] = (
    "starcoder2_3b",
    "qwen2_5_14b",
    "gemma2_27b",
    "qwen3_1_7b",
    "deepseek_moe_16b",
    "qwen2_moe_a2_7b",
    "chameleon_34b",
    "mamba2_1_3b",
    "whisper_tiny",
    "zamba2_7b",
)

# Accept dashed public ids too.
_ALIASES = {a.replace("_", "-"): a for a in ARCH_IDS}
_ALIASES.update({
    "starcoder2-3b": "starcoder2_3b",
    "qwen2.5-14b": "qwen2_5_14b",
    "gemma2-27b": "gemma2_27b",
    "qwen3-1.7b": "qwen3_1_7b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "chameleon-34b": "chameleon_34b",
    "mamba2-1.3b": "mamba2_1_3b",
    "whisper-tiny": "whisper_tiny",
    "zamba2-7b": "zamba2_7b",
})


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    mod_name = _ALIASES.get(name, name)
    if mod_name not in ARCH_IDS:
        raise ValueError(f"unknown arch {name!r}; known: {sorted(_ALIASES)}")
    mod = importlib.import_module(f"repro.configs.{mod_name}")
    return mod.SMOKE_CONFIG if smoke else mod.CONFIG


def cell_applicable(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Whether a (arch, shape) dry-run cell applies, and why not if skipped."""
    if shape.name == "long_500k" and not cfg.is_subquadratic:
        return False, "full-attention arch: 500k context requires sub-quadratic attention (skip per assignment)"
    return True, ""
