"""The harness end to end on the CPU at a tiny size: the program passes the
output check, and the same run with the timed path broken underneath fails
it; tails are taken over all samples; the command refuses to run without an
accelerator or outside a checkout."""
import dataclasses
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.engine import Sent, SpanEngine
from chipbench.traffic import Arrival

HERE = pathlib.Path(__file__).resolve().parent
FIXTURE = HERE / "fixtures" / "bench"
BENCH = HERE.parent
REPO = BENCH.parents[1]
SECONDS = 1.5


def _run(engine_cls=SpanEngine, seed=2**31 + 77):
    return harness.run("tiny.chat", seed, SECONDS, False,
                       t_start=time.monotonic(),
                       spec_path=FIXTURE / "spec.json", bench_dir=FIXTURE,
                       require_accelerator=False, engine_cls=engine_cls)


class StaleCache(SpanEngine):
    """A decode step that returns its state unchanged."""

    def _decode_exec(self, live):
        old = jax.tree_util.tree_map(jnp.copy, self.cache)
        nxt = super()._decode_exec(live)
        self.cache = old
        return nxt


class HalfBatch(SpanEngine):
    """Half of the live slots left out of the decode batch."""

    def _decode_exec(self, live):
        return super()._decode_exec(live[:max(1, len(live) // 2)])


class AlteredToken(SpanEngine):
    """One token altered where it is produced, every fourth step."""

    def _decode_exec(self, live):
        nxt = np.array(super()._decode_exec(live))
        if len(self.decode_spans) % 4 == 0:
            nxt[live[0]] = (nxt[live[0]] + 1) % self.cfg.vocab_size
        return nxt


@pytest.mark.parametrize("engine_cls,correct", [
    (SpanEngine, True), (StaleCache, False), (HalfBatch, False),
    (AlteredToken, False)], ids=["program", "stale-cache", "half-batch",
                                 "altered-token"])
def test_output_check_catches_each_fault(engine_cls, correct):
    out = _run(engine_cls)
    c = out["checks"]
    assert list(out)[-1] == "checks"
    assert c["requests_incomplete"]["value"] == 0
    assert c["sampled_tokens_at_least"]["value"] >= \
        c["sampled_tokens_at_least"]["limit"]
    assert out["correct"] is correct, c
    if correct:
        assert set(out["metrics"]) == {"ttft_p95_ms", "itl_p50_ms",
                                       "itl_p95_ms", "serve_tok_s",
                                       "setup_s"}
        assert all(v["value"] > 0 for v in out["metrics"].values())
        assert out["attempted"] > 0 and out["failed"] == 0


def _sent(due_ns, stamps, n_out):
    s = Sent(Arrival(0.0, 8, n_out, True), np.zeros(8, np.int32))
    s.due_ns, s.token_ns = due_ns, list(stamps)
    s.req = type("R", (), {"rejected": False, "tokens": [0] * n_out})()
    return s


def test_tails_are_taken_over_all_samples():
    rng = np.random.default_rng(0)
    sent, ttft, itl = [], [], []
    for i in range(40):
        due = i * 10_000_000
        gaps = rng.integers(1_000_000, 50_000_000, 5)
        first = due + int(rng.integers(1_000_000, 900_000_000))
        stamps = np.concatenate([[first], first + np.cumsum(gaps)])
        sent.append(_sent(due, stamps, 6))
        ttft.append((first - due) / 1e6)
        itl.extend(gaps / 1e6)
    client = type("C", (), {"sent": sent})()
    served = harness.Served(client, [], [], (0, 10**12), None, (0, 0), [], 0,
                            None)
    e2e = harness.end_to_end(served, 1000.0, 3.0)
    assert e2e["ttft_p95_ms"] == pytest.approx(np.percentile(ttft, 95))
    assert e2e["itl_p50_ms"] == pytest.approx(np.percentile(itl, 50))
    assert e2e["itl_p95_ms"] == pytest.approx(np.percentile(itl, 95))
    assert e2e["serve_tok_s"] == 40 * 6 / 1000.0


def _command(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen3_1_7b.chat", "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_command_refuses_without_an_accelerator():
    p = _command(REPO, {})
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    _no_result(p.stdout)


def test_command_fails_outside_a_checkout(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__"))
    p = _command(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    _no_result(p.stdout)



def _small_program(cfg):
    # the program's preset at the fixture's widths: the smoke preset's
    # architecture, wide enough that int8 rounding stands out of bf16's
    from repro.configs import get_config
    return dataclasses.replace(
        get_config("qwen3_1_7b", smoke=True), d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        vocab_size=cfg["vocab_size"])


@pytest.mark.parametrize("seed", [5, 2**31 + 11, 2**32 + 9])
def test_int8_control_fails_the_limit(seed, monkeypatch):
    # the calibration's readings on a small cell: the program's widest gap
    # lies under the cell's limit, and the reference computed in int8, put
    # in the program's place on the same requests, reads above it. The
    # limit 0.028 was set from 14 seeds on the CPU: program 0.003-0.0195,
    # control 0.036-0.088
    import calibrate
    monkeypatch.setattr(harness, "program_config", _small_program)
    cell = harness.load_cell("small.chat", FIXTURE / "spec.json", FIXTURE)
    r = calibrate.readings(cell, seed, SECONDS, True)
    limit = cell.cfg["check"]["max_logit_gap"]
    assert r["incomplete"] == 0 and r["tokens"] >= \
        cell.mix["check_sample"]["tokens"]
    assert r["program_gap"] <= limit < r["control_gap"], r
