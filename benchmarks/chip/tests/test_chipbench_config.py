"""``program_config`` holds the program to the configuration file's whole
architecture before any weights are drawn: the flags, the activation, the
attention window and every leaf of the parameter tree."""
import json
import pathlib

import pytest

from chipbench import harness

HERE = pathlib.Path(__file__).resolve().parent
TINY = HERE / "fixtures" / "bench" / "configs" / "tiny_qwen3.json"
FILES = sorted((HERE.parent / "configs").glob("*.json")) + [TINY]


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.stem)
def test_every_configuration_file_is_accepted(path):
    cfg = json.loads(path.read_text())
    assert harness.program_config(cfg).name.startswith(
        cfg["serve"]["repro_arch"])


# one flag of the Qwen3 fixture departed, and what the refusal names: the
# flag, and the leaves the file would draw that the program lacks
FAULTS = {
    "out_bias": (True, {"layers.attn.bo", "layers.mlp.bi", "layers.mlp.bo"}),
    "qkv_bias": (True, {"layers.attn.bq", "layers.attn.bk", "layers.attn.bv"}),
    "norm": ("layernorm",
             {"final_norm.bias", "layers.ln1.bias", "layers.ln2.bias"}),
    "hidden_act": ("gelu_pytorch_tanh", set()),
    "attention_window": (64, set()),
}


@pytest.mark.parametrize("key", FAULTS)
def test_a_departed_flag_is_refused_by_name(key):
    value, leaves = FAULTS[key]
    cfg = dict(json.loads(TINY.read_text()), **{key: value})
    with pytest.raises(ValueError) as e:
        harness.program_config(cfg)
    named = {line.split(":")[0].strip()
             for line in str(e.value).splitlines()[1:]}
    assert named == {key} | leaves


@pytest.mark.parametrize("name", ["qwen3_1_7b", "starcoder2_3b"])
def test_program_architecture_is_the_inverse_of_program_config(name):
    from repro.configs import get_config
    pc = get_config(name, smoke=True)
    cfg = dict(harness.program_architecture(pc),
               serve={"repro_arch": name, "repro_smoke": True,
                      "dtype": pc.dtype})
    assert harness.program_config(cfg) is pc
