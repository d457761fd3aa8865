"""The float32 reference: against the program's own forward pass on both
smoke presets, with the flags the program computes (its departures from the
published models are ``program_config``'s to catch), and against Hugging
Face's implementations of Qwen3 and StarCoder2 as published (a witness that
imports nothing of the program or of the benchmark)."""
import dataclasses
import os

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import program_architecture
from chipbench.reference import Reference, control_gaps, served_gaps
from chipbench.weights import make_weights

# both architectures as published (Qwen/Qwen3-1.7B, bigcode/starcoder2-3b
# config.json) at the smoke presets' widths; StarCoder2's use_bias puts a
# bias on q, k, v, o and both MLP matrices
QWEN3_SMOKE = {
    "hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rope_theta": 1000000.0, "tie_word_embeddings": True,
    "norm": "rmsnorm", "norm_eps": 1e-6, "qk_norm": True, "mlp_gated": True,
    "qkv_bias": False, "out_bias": False, "attention_window": 0}
STARCODER2_SMOKE = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rope_theta": 999999.4420358813,
    "tie_word_embeddings": True, "norm": "layernorm", "norm_eps": 1e-5,
    "qk_norm": False, "mlp_gated": False, "qkv_bias": True, "out_bias": True,
    "attention_window": 4096}


def _tokens(seed, n, vocab):
    return np.random.default_rng(seed).integers(0, vocab, n, dtype=np.int32)


def _program_logits(pc, weights, tokens):
    from repro.models import api
    cfg = dataclasses.replace(pc, dtype="float32")
    logits, _, _ = api.forward(cfg, weights, {"tokens": jnp.asarray(tokens)[None]},
                               mode="train")
    return np.asarray(logits[0, :, :cfg.vocab_size])


@pytest.mark.parametrize("name", ["qwen3_1_7b", "starcoder2_3b"])
@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_reference_matches_the_program_on_smoke_presets(name, seed):
    from repro.configs import get_config
    pc = get_config(name, smoke=True)
    cfg = program_architecture(pc)
    w = make_weights(cfg, seed, pc.vocab_padded, dtype=jnp.float32)
    t = _tokens(seed, 48, cfg["vocab_size"])
    ref = np.asarray(Reference(cfg).logits(w, t, np.arange(48)))
    prog = _program_logits(pc, w, t)
    assert np.abs(ref - prog).max() < 1e-4 * max(1.0, np.abs(ref).max())
    # the served-token gap of the program's own greedy tokens is rounding
    assert served_gaps(ref, prog.argmax(-1)).max() < 1e-4


def _hf_state(cfg, w, starcoder):
    import torch
    t = lambda a: torch.tensor(np.asarray(a, np.float32))  # noqa: E731
    L = cfg["num_hidden_layers"]
    lay = w["layers"]
    sd = {"model.embed_tokens.weight": t(w["embed"]["table"]),
          "model.norm.weight": t(w["final_norm"]["scale"])}
    if starcoder:
        sd["model.norm.bias"] = t(w["final_norm"]["bias"])
    for i in range(L):
        p = f"model.layers.{i}."
        a = {k: np.asarray(v[i]) for k, v in lay["attn"].items()}
        m = {k: np.asarray(v[i]) for k, v in lay["mlp"].items()}
        for hf, ours in (("q", "q"), ("k", "k"), ("v", "v"), ("o", "o")):
            sd[p + f"self_attn.{hf}_proj.weight"] = t(a["w" + ours].T)
        sd[p + "input_layernorm.weight"] = t(lay["ln1"]["scale"][i])
        sd[p + "post_attention_layernorm.weight"] = t(lay["ln2"]["scale"][i])
        if starcoder:
            for hf in "qkvo":
                sd[p + f"self_attn.{hf}_proj.bias"] = t(a["b" + hf])
            sd[p + "input_layernorm.bias"] = t(lay["ln1"]["bias"][i])
            sd[p + "post_attention_layernorm.bias"] = t(lay["ln2"]["bias"][i])
            sd[p + "mlp.c_fc.weight"] = t(m["wi"].T)
            sd[p + "mlp.c_fc.bias"] = t(m["bi"])
            sd[p + "mlp.c_proj.weight"] = t(m["wo"].T)
            sd[p + "mlp.c_proj.bias"] = t(m["bo"])
        else:
            sd[p + "self_attn.q_norm.weight"] = t(a["q_norm"])
            sd[p + "self_attn.k_norm.weight"] = t(a["k_norm"])
            sd[p + "mlp.gate_proj.weight"] = t(m["wg"].T)
            sd[p + "mlp.up_proj.weight"] = t(m["wi"].T)
            sd[p + "mlp.down_proj.weight"] = t(m["wo"].T)
    sd["lm_head.weight"] = sd["model.embed_tokens.weight"]
    return sd


def _hf_logits(cfg, w, tokens, starcoder):
    os.environ.setdefault("USE_TF", "0")
    torch = pytest.importorskip("torch")
    tr = pytest.importorskip("transformers")
    common = dict(vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
                  intermediate_size=cfg["intermediate_size"],
                  num_hidden_layers=cfg["num_hidden_layers"],
                  num_attention_heads=cfg["num_attention_heads"],
                  num_key_value_heads=cfg["num_key_value_heads"],
                  rope_theta=cfg["rope_theta"], tie_word_embeddings=True,
                  max_position_embeddings=4096)
    if starcoder:
        hc = tr.Starcoder2Config(**common, use_bias=True,
                                 norm_epsilon=cfg["norm_eps"],
                                 hidden_act="gelu_pytorch_tanh",
                                 sliding_window=cfg["attention_window"],
                                 residual_dropout=0.0, embedding_dropout=0.0,
                                 attention_dropout=0.0)
        model = tr.Starcoder2ForCausalLM(hc)
    else:
        hc = tr.Qwen3Config(**common, head_dim=cfg["head_dim"],
                            rms_norm_eps=cfg["norm_eps"], hidden_act="silu",
                            attention_bias=False)
        model = tr.Qwen3ForCausalLM(hc)
    model.config._attn_implementation = "eager"
    model.load_state_dict(_hf_state(cfg, w, starcoder), strict=True)
    model.eval()
    with torch.no_grad():
        out = model(torch.tensor(tokens[None].astype(np.int64))).logits
    return out[0].numpy()


@pytest.mark.parametrize("starcoder", [False, True])
def test_reference_matches_transformers(starcoder):
    cfg = STARCODER2_SMOKE if starcoder else QWEN3_SMOKE
    w = make_weights(cfg, 11, cfg["vocab_size"], dtype=jnp.float32)
    t = _tokens(11, 40, cfg["vocab_size"])
    ref = np.asarray(Reference(cfg).logits(w, t, np.arange(40)))
    hf = _hf_logits(cfg, w, t, starcoder)
    assert np.abs(ref - hf).max() < 1e-4 * max(1.0, np.abs(ref).max())


def test_gaps():
    lg = np.array([[0.0, 2.0, 1.0], [3.0, 1.0, 2.5]])
    assert served_gaps(lg, [1, 2]).tolist() == [0.0, 0.5]
    assert served_gaps(lg, [0, 7])[1] == np.inf  # outside the vocabulary
    assert control_gaps(lg, np.array([[0, 0, 1], [0, 1, 0]])).tolist() == \
        [1.0, 2.0]
