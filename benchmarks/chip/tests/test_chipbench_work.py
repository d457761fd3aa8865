"""FLOP and byte counts from configuration shapes, against numbers worked
by hand at both models' published widths."""
import json
import os

import pytest

from chipbench.work import Shapes, least_seconds

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "configs")

# StarCoder2-3B as published (bigcode/starcoder2-3b config.json): use_bias
# puts a bias on q, k, v, o and both MLP matrices
STARCODER2_3B = {
    "hidden_size": 3072, "intermediate_size": 12288, "num_hidden_layers": 30,
    "num_attention_heads": 24, "num_key_value_heads": 2, "head_dim": 128,
    "vocab_size": 49152, "tie_word_embeddings": True, "mlp_gated": False,
    "qk_norm": False, "qkv_bias": True, "out_bias": True,
    "norm": "layernorm"}


def qwen3():
    with open(os.path.join(CONFIGS, "qwen3_1_7b.json")) as f:
        return Shapes.from_config(json.load(f))


def test_kv_bytes_per_token():
    # layers x (k and v) x kv heads x head_dim x 2 bytes
    assert qwen3().kv_bytes_per_token() == 28 * 2 * 8 * 128 * 2 == 114_688
    assert Shapes.from_config(STARCODER2_3B).kv_bytes_per_token() == \
        30 * 2 * 2 * 128 * 2 == 30_720


def test_weight_bytes_qwen3():
    s = qwen3()
    # embedding 151936 x 2048; per layer q 2048x2048, k and v 2048x1024,
    # o 2048x2048, gate/up/down 3 x 2048x6144, two RMSNorm scales of 2048
    # and two qk-norm scales of 128; the final norm 2048
    assert s.param_count() == 1_720_574_976
    assert s.weight_bytes() == 2 * 1_720_574_976 == 3_441_149_952


def test_weight_bytes_starcoder2():
    s = Shapes.from_config(STARCODER2_3B)
    # embedding 49152 x 3072; per layer q, o 3072x3072, k, v 3072x256,
    # c_fc and c_proj 3072x12288, two LayerNorms (scale, bias), biases on
    # q, k, v, o, c_fc, c_proj; the final LayerNorm
    assert s.param_count() == 3_030_371_328
    assert s.weight_bytes() == 6_060_742_656


def test_counts_match_the_programs_own_parameter_count():
    from repro.configs import get_config
    from repro.models.params import param_count_exact
    q = get_config("qwen3_1_7b")
    # the program pads the vocabulary to a multiple of 256
    assert qwen3().param_count() == param_count_exact(q) - \
        (q.vocab_padded - q.vocab_size) * q.d_model
    sc = get_config("starcoder2_3b")
    out_biases = 30 * (3072 + 12288 + 3072)  # published, not in the program
    assert Shapes.from_config(STARCODER2_3B).param_count() == \
        param_count_exact(sc) + out_biases


def test_decode_counts_by_hand():
    s = qwen3()
    per_token = 2 * 28 * 50_331_648   # every layer matrix, 2 per MAC
    head = 2 * 2048 * 151_936         # tied embedding as the head
    attn = 4 * 28 * 16 * 128          # QK^T and PV per (query, key) pair
    # two live slots with 0 and 10 cached positions attend to 1 and 11
    assert s.decode_flops([0, 10]) == 2 * (per_token + head) + attn * 12 \
        == 6_884_556_800
    assert s.decode_bytes([0, 10]) == 3_441_149_952 + 114_688 * (10 + 2)
    assert s.decode_bytes([]) == s.weight_bytes()


def test_prefill_counts_by_hand():
    s = qwen3()
    # 4 tokens: 4 x matrices, the head at the last position only, and
    # 1 + 2 + 3 + 4 causal pairs
    assert s.prefill_flops(4) == 4 * 2_818_572_288 + 622_329_856 + \
        4 * 28 * 16 * 128 * 10 == 11_898_912_768


def test_least_seconds_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert least_seconds(200.0, 10.0, peak) == (2.0, "compute")
    assert least_seconds(100.0, 30.0, peak) == (3.0, "memory")
    # a decode step at these widths is bound by memory on a v5e
    with open(os.path.join(CONFIGS, "..", "peaks.json")) as f:
        v5e = json.load(f)["TPU v5 lite"]
    pos = [300] * 8
    t, bound = least_seconds(qwen3().decode_flops(pos),
                             qwen3().decode_bytes(pos), v5e)
    assert bound == "memory"
    assert t == pytest.approx((3_441_149_952 + 114_688 * 301 * 8) / 819e9)
