"""StarCoder2-3B [arXiv:2402.19173]: 30L d_model=3072 24H (GQA kv=2) d_ff=12288
vocab=49152 — GQA, RoPE, a 4096-position sliding window on every layer,
non-gated tanh-GELU MLP, LayerNorm, and biases on every projection
(huggingface.co/bigcode/starcoder2-3b config.json: use_bias, norm_epsilon
1e-5, rope_theta 999999.4420358813)."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="starcoder2_3b",
    family="dense",
    n_layers=30,
    d_model=3072,
    vocab_size=49152,
    n_heads=24,
    n_kv_heads=2,
    head_dim=128,
    qkv_bias=True,
    out_bias=True,
    rope_theta=999999.4420358813,
    sliding_window=4096,
    local_global_period=0,  # 0: the window applies to every layer
    d_ff=12288,
    mlp_gated=False,
    mlp_act="gelu",
    norm_type="layernorm",
    rms_eps=1e-5,
    tie_embeddings=True,
    train_microbatches=8,
)

# the published block at smoke widths, its window below the 128 positions
# the CPU tests serve so that the mask is exercised
SMOKE_CONFIG = ModelConfig(
    name="starcoder2_3b_smoke",
    family="dense",
    n_layers=2,
    d_model=64,
    vocab_size=512,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    qkv_bias=True,
    out_bias=True,
    rope_theta=999999.4420358813,
    sliding_window=64,
    local_global_period=0,
    d_ff=128,
    mlp_gated=False,
    mlp_act="gelu",
    norm_type="layernorm",
    rms_eps=1e-5,
)
