"""Open-loop schedules: the same arrivals and sizes for every seed, prompt
tokens drawn from the seed, lengths on the grid and in range."""
import collections
import json
import os

import numpy as np
import pytest

from chipbench import traffic

MIXES = os.path.join(os.path.dirname(__file__), "..", "traffic")


def _mix(name):
    with open(os.path.join(MIXES, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat", "code_completion"])
def test_same_seed_same_schedule(name):
    mix = _mix(name)
    seed = 2**31 + 12345  # run seeds exceed 32 signed bits
    a = traffic.schedule(mix, 51)
    assert a == traffic.schedule(mix, 51)
    pa = traffic.prompt_tokens(seed, a, 151936)
    pb = traffic.prompt_tokens(seed, a, 151936)
    assert all((x == y).all() for x, y in zip(pa, pb))
    assert all(0 <= x.min() and x.max() < 151936 for x in pa)
    pc = traffic.prompt_tokens(seed + 1, a, 151936)
    assert not any((x == y).all() for x, y in zip(pa, pc))


@pytest.mark.parametrize("name", ["chat", "code_completion"])
def test_every_seed_gets_the_same_work(name):
    mix = _mix(name)
    r = traffic.schedule(mix, 51)
    # the run seed enters only the prompt tokens; the sizes come from the
    # mix, and are spread over the grid
    sizes = collections.Counter((x.prompt_len, x.output_len) for x in r)
    assert len(sizes) > 1 and len(r) == \
        round(mix["arrivals"]["rate_per_s"] * (51 + mix["lead_in_s"]))
    toks = [traffic.prompt_tokens(s, r, 151936)
            for s in (1, 2**31 + 7, 2**32 + 3)]
    assert all([len(x) for x in t] == [x.prompt_len for x in r]
               for t in toks)
    due = [x.due_s for x in r]
    assert due == sorted(due) and due[0] == 0.0
    assert due[-1] == pytest.approx(51 + mix["lead_in_s"])
    assert all(x.in_window == (x.due_s >= mix["lead_in_s"]) for x in r)


@pytest.mark.parametrize("name", ["chat", "code_completion"])
def test_lengths_on_the_grid_and_in_range(name):
    mix = _mix(name)
    grid = traffic.prompt_grid(mix)
    r = traffic.schedule(mix, 51)
    assert {x.prompt_len for x in r} <= set(grid)
    assert all(mix["prompt"]["min"] <= x.prompt_len <= mix["prompt"]["max"]
               for x in r)
    assert all(mix["output"]["min"] <= x.output_len <= mix["output"]["max"]
               for x in r)


def test_grids_of_the_mixes():
    assert traffic.prompt_grid(_mix("chat")) == list(range(256, 2049, 256))
    assert traffic.prompt_grid(_mix("code_completion")) == \
        list(range(1024, 3585, 256))


def test_gamma_arrivals_are_bursty():
    rng = np.random.default_rng(0)
    g = traffic._gaps(rng, {"process": "gamma", "cv": 2.0}, 200_000)
    assert g.mean() == pytest.approx(1.0, rel=0.02)
    assert g.std() / g.mean() == pytest.approx(2.0, rel=0.05)
    p = traffic._gaps(rng, {"process": "poisson"}, 200_000)
    assert p.std() / p.mean() == pytest.approx(1.0, rel=0.02)
