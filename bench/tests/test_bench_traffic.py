"""The traffic generator: deterministic per seed, every seed the same
work, and faithful to the mix files."""

import json
import math
import os
import re

import numpy as np
import pytest

from bench import harness, traffic

MIXES = os.path.join(harness.BENCH, "mixes")
SERVING = [m[:-5] for m in sorted(os.listdir(MIXES))
           if json.load(open(os.path.join(MIXES, m)))["driver"] == "serve"]


def load(name):
    return json.load(open(os.path.join(MIXES, name + ".json")))


def sig(plan):
    return [(p.rid, p.due, p.system, p.prompt.tolist(), p.max_new)
            for p in plan]


@pytest.mark.parametrize("mix", SERVING)
def test_same_seed_same_requests(mix):
    m = load(mix)
    a = traffic.plan(m, 2**33 + 5, 30, 1000)
    b = traffic.plan(m, 2**33 + 5, 30, 1000)
    assert sig(a) == sig(b)
    assert sig(a) != sig(traffic.plan(m, 2**33 + 6, 30, 1000))


@pytest.mark.parametrize("mix", SERVING)
def test_every_seed_the_same_work(mix):
    """Seeds share one schedule (due times, sizes, system prompts, in one
    order) and differ in token contents."""
    m = load(mix)
    plans = [traffic.plan(m, s, 30, 1000) for s in (1, 2**40, 77)]
    shape = lambda p: [(r.due, r.system, len(r.prompt), r.max_new)
                       for r in p]
    assert shape(plans[0]) == shape(plans[1]) == shape(plans[2])
    assert plans[0][-1].prompt.tolist() != plans[1][-1].prompt.tolist()


@pytest.mark.parametrize("mix", SERVING)
def test_plan_follows_the_mix(mix):
    m = load(mix)
    seconds = 30
    plan = traffic.plan(m, 9, seconds, 1000)
    arr = m["arrivals"]
    if arr["kind"] == "poisson":
        assert len(plan) == round(arr["rate_per_s"] * seconds)
        due = [r.due for r in plan]
        assert due == sorted(due) and 0 < due[0] and due[-1] < seconds
    else:
        assert len(plan) == arr["requests"]
        assert all(r.due == 0 for r in plan)
    sp = m.get("shared_prefix")
    own = [len(r.prompt) - (sp["tokens"] if sp else 0) for r in plan]
    assert min(own) >= m["prompt"]["min"] and max(own) <= m["prompt"]["max"]
    outs = [r.max_new for r in plan]
    assert min(outs) >= m["output"]["min"] and max(outs) <= m["output"]["max"]
    assert all(len(r.prompt) + r.max_new < m["engine"]["max_len"]
               for r in plan)
    if sp:
        systems = traffic.system_prompts(m, 9, 1000)
        for r in plan:
            assert (r.prompt[:sp["tokens"]] == systems[r.system]).all()
        counts = np.bincount([r.system for r in plan], minlength=sp["count"])
        assert list(counts) == traffic.zipf_counts(sp["count"], len(plan),
                                                   sp["zipf"])


def test_lengths_at_stratified_quantiles():
    d = {"kind": "lognormal", "median": 96, "sigma": 0.8, "min": 16,
         "max": 512}
    got = traffic.lengths(d, 101)
    assert got == sorted(got) and got[50] == 96
    u = traffic.lengths({"kind": "uniform", "min": 256, "max": 736}, 4)
    assert u == [316, 436, 556, 676]


def test_zipf_counts():
    c = traffic.zipf_counts(8, 100, 1.1)
    assert sum(c) == 100 and c == sorted(c, reverse=True) and c[0] > 2 * c[2]


def test_the_chat_mix_runs_at_its_stated_share_of_the_knee():
    """The chat cell's rate is the factor its source states times the rate
    recorded there (the knee, or the highest rate swept where the sweep
    found none), rounded down to 0.1 req/s, and a window of the
    benchmark's length holds enough requests for its tails."""
    from bench.tests.conftest import REAL
    m = load("chat-sysprompt")
    said = re.match(r"Poisson at ([0-9.]+) x ([0-9.]+) req/s",
                    m["sources"]["arrivals"])
    factor, knee = map(float, said.groups())
    assert 0.7 <= factor <= 0.85
    assert m["arrivals"]["rate_per_s"] == math.floor(
        factor * knee * 10 + 1e-9) / 10
    plan = traffic.plan(m, 2**31 + 11, REAL["run_seconds"], 1000)
    assert len(plan) >= 100
