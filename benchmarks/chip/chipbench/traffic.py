"""Open-loop request schedules from a traffic mix file and a seed.

A mix (``traffic/<mix>.json``) gives the arrival process and its rate, the
prompt and output length distributions (lognormal, clipped, prompts rounded
up to a grid so that set-up can warm every prompt shape), and a lead-in
served before the measured window opens.

Seed use: the schedule (inter-arrival gaps, prompt lengths and output
lengths, in their order) is drawn from the mix's own ``sizes_seed``, so
every run seed replays the same arrivals and sizes; ``--seed`` draws the
prompt tokens (and, in the harness, the weights). The tails then depend on
the system and not on an order drawn per run: at some forty requests a
window, the 95th percentile is the third-longest wait. Gaps are scaled so
that the schedule spans the lead-in plus the window exactly, which puts the
same number of requests in every run.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float        # seconds after the lead-in started
    prompt_len: int
    output_len: int     # tokens served: the prefill's first plus decodes
    in_window: bool


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    g = spec.get("round_up", 1)
    return np.minimum(-(-x // g) * g, spec["max"])


def _gaps(rng, arrivals: dict, n: int) -> np.ndarray:
    """Unit-mean inter-arrival gaps of the mix's renewal process."""
    if arrivals["process"] == "poisson":
        return rng.exponential(1.0, n)
    if arrivals["process"] == "gamma":
        k = 1.0 / arrivals["cv"] ** 2
        return rng.gamma(k, 1.0 / k, n)
    raise ValueError(f"unknown arrival process {arrivals['process']!r}")


def prompt_grid(mix: dict) -> list:
    """Every prompt length the mix can draw."""
    p = mix["prompt"]
    g = p.get("round_up", 1)
    lo = min(-(-p["min"] // g) * g, p["max"])
    return list(range(lo, p["max"] + 1, g))


def schedule(mix: dict, seconds: float, rate: float | None = None) -> list:
    rate = mix["arrivals"]["rate_per_s"] if rate is None else rate
    lead = mix["lead_in_s"]
    span = lead + seconds
    n = max(1, int(round(rate * span)))
    sizes = np.random.default_rng([mix["sizes_seed"], n])
    gaps = _gaps(sizes, mix["arrivals"], n)
    prompts = _lognormal(sizes, mix["prompt"], n)
    outputs = _lognormal(sizes, mix["output"], n)
    due = np.cumsum(gaps)
    due = (due - due[0]) * (span / (due[-1] - due[0])) if n > 1 \
        else np.zeros(1)
    return [Arrival(float(d), int(p), int(o), bool(d >= lead))
            for d, p, o in zip(due, prompts, outputs)]


def prompt_tokens(seed: int, schedule_: list, vocab: int) -> list:
    rng = np.random.default_rng([seed, 2])
    return [rng.integers(0, vocab, a.prompt_len, dtype=np.int32)
            for a in schedule_]
