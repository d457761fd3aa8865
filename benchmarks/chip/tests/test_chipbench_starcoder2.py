"""StarCoder2 as published, at smoke widths on the CPU: the program's
prefill and its decode through the cache against the float32 reference,
and the tiny StarCoder2 cell served end to end through the harness's output
check. The fixture ``tiny_starcoder2.json`` is the published block (LayerNorm
at eps 1e-5, biases on q, k, v, o and both MLP matrices, tanh GELU, GQA)
with a 64-position window below its 128-position cache, so that prompts of
up to 96 tokens and the decode after them reach past the window."""
import dataclasses
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.engine import SpanEngine
from chipbench.reference import Reference
from chipbench.weights import make_weights
from test_chipbench_harness import AlteredToken, HalfBatch, StaleCache

FIXTURE = pathlib.Path(__file__).resolve().parent / "fixtures" / "bench"
TINY = json.loads((FIXTURE / "configs" / "tiny_starcoder2.json").read_text())
PROMPT, DECODE = 80, 40  # 120 positions of the 128, the window is 64

# Both sides compute in float32 from the same float32 weights: the reference
# at "highest" matmul precision one layer at a time, the program in its own
# fused order. What is left is float32 rounding of sums taken in different
# orders: 3e-7 to 4e-7 of the largest logit over four seeds, in prefill and
# decode alike. 1e-4 leaves that room and is the reference test's tolerance;
# computing in bf16 would miss it by far (bf16 keeps 8 bits), and so does the
# departed program: 0.22 to 0.40 without the output biases, 0.011 to 0.020
# with a LayerNorm eps of 1e-6 for 1e-5, as the residual stream after the
# 0.02-scaled embedding is not large beside eps (CPU, four seeds).
RTOL = 1e-4


def _strip_out_bias(params):
    lay = params["layers"]
    attn = {k: v for k, v in lay["attn"].items() if k != "bo"}
    mlp = {k: v for k, v in lay["mlp"].items() if k not in ("bi", "bo")}
    return dict(params, layers=dict(lay, attn=attn, mlp=mlp))


# the program as it departed from the published model before it had
# output biases and the published eps
VARIANTS = {
    "published": lambda pc, w: (pc, w),
    "no-out-bias": lambda pc, w: (dataclasses.replace(pc, out_bias=False),
                                  _strip_out_bias(w)),
    "eps-1e-6": lambda pc, w: (dataclasses.replace(pc, rms_eps=1e-6), w),
}


def _program_logits(pc, w, tokens):
    """float32 logits (len(tokens), vocab) of the program: a prefill of the
    first PROMPT tokens spliced into a one-slot cache, then one decode step
    per further token at its own position, as the serving engine runs them.
    The cache is float32 here, as the rest of the computation; the engine's
    bf16 cache is the served precision, which the output check reads."""
    from repro.models import api
    cfg = dataclasses.replace(pc, dtype="float32")
    V = cfg.vocab_size

    @jax.jit
    def prefill(w, toks):
        logits, _, pre = api.forward(cfg, w, {"tokens": toks[None]},
                                     mode="prefill")
        empty = api.init_cache(cfg, 1, TINY["serve"]["max_seq"])
        cache = jax.tree_util.tree_map(
            lambda dst, src: jax.lax.dynamic_update_slice(
                jnp.zeros(dst.shape, jnp.float32), src, (0,) * dst.ndim),
            empty, pre)
        return logits[0, :, :V], cache

    @jax.jit
    def decode(w, tok, cache, pos):
        logits, _, cache = api.forward(cfg, w, {"tokens": tok[None, None]},
                                       mode="decode", cache=cache,
                                       cache_pos=pos[None])
        return logits[0, 0, :V], cache

    logits, cache = prefill(w, jnp.asarray(tokens[:PROMPT]))
    out = [np.asarray(logits)]
    for i in range(PROMPT, len(tokens)):
        logits, cache = decode(w, jnp.asarray(tokens[i]), cache,
                               jnp.asarray(i, jnp.int32))
        out.append(np.asarray(logits)[None])
    return np.concatenate(out)


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
@pytest.mark.parametrize("variant", list(VARIANTS))
def test_prefill_and_cached_decode_match_the_reference(variant, seed):
    pc = harness.program_config(TINY)
    assert pc.sliding_window == TINY["attention_window"] < PROMPT
    w = make_weights(TINY, seed, pc.vocab_padded, dtype=jnp.float32)
    tokens = np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], PROMPT + DECODE, dtype=np.int32)
    ref = np.asarray(Reference(TINY).logits(w, tokens,
                                            np.arange(len(tokens))))
    prog = _program_logits(*VARIANTS[variant](pc, w), tokens)
    scale = max(1.0, np.abs(ref).max())
    gap = np.abs(prog - ref).max(-1) / scale
    if variant == "published":
        assert gap[:PROMPT].max() < RTOL, "prefill"
        assert gap[PROMPT:].max() < RTOL, "decode through the cache"
    else:  # the departed program fails the same tolerances
        assert gap[:PROMPT].max() > 10 * RTOL
        assert gap[PROMPT:].max() > 10 * RTOL


class Departed(SpanEngine):
    """The served program without its output biases and at eps 1e-6."""

    def __init__(self, cfg, params, *args, **kw):
        super().__init__(dataclasses.replace(cfg, rms_eps=1e-6),
                         _strip_out_bias(params), *args, **kw)


@pytest.mark.parametrize("engine_cls,correct", [
    (SpanEngine, True), (StaleCache, False), (HalfBatch, False),
    (AlteredToken, False), (Departed, False)],
    ids=["program", "stale-cache", "half-batch", "altered-token",
         "departed"])
def test_output_check_catches_each_fault(engine_cls, correct):
    # the fixture's limit, 0.05, as the Qwen3 fixture's: over eight seeds
    # the served program read 0 to 0.0033 and the departed one 0.13 to 0.27
    # (CPU); an eps of 1e-6 alone reads 0.002 to 0.007 and is refused by
    # ``program_config`` before any weights are drawn
    out = harness.run("tiny_starcoder2.code", 2**31 + 77, 1.5, False,
                      t_start=time.monotonic(),
                      spec_path=FIXTURE / "spec_starcoder2.json",
                      bench_dir=FIXTURE, require_accelerator=False,
                      engine_cls=engine_cls)
    c = out["checks"]
    assert c["requests_incomplete"]["value"] == 0
    assert c["sampled_tokens_at_least"]["value"] >= \
        c["sampled_tokens_at_least"]["limit"]
    assert out["correct"] is correct, c


def test_cell_serves_the_code_mix_at_its_own_rate():
    # the benchmark's StarCoder2 cell: the code-completion mix of the Qwen3
    # cell (arrivals, lengths, lead-in, check sample) at 0.8 of the knee
    # sweep.py found for this model, with a schedule of its own; the
    # published file is accepted and holds a limit for the output check
    cell = harness.load_cell("starcoder2_3b.code_completion")
    code = json.loads((harness.BENCH_DIR / "traffic" /
                       "code_completion.json").read_text())
    for key in ("prompt", "output", "lead_in_s", "check_sample"):
        assert cell.mix[key] == code[key], key
    assert cell.mix["arrivals"] == dict(code["arrivals"], rate_per_s=2.4)
    assert cell.mix["sizes_seed"] != code["sizes_seed"]
    assert cell.chips == 1
    assert harness.program_config(cell.cfg).name == "starcoder2_3b"
    assert cell.cfg["check"]["max_logit_gap"] > 0
