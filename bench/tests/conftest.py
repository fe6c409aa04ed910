"""Smoke-size cells for the benchmark's CPU tests.

The smoke mixes are the real ones cut to a size the CPU runs in seconds,
and compare against the real mixes' limits.  ``smoke_root`` writes a BENCHMARK.json of three tiny cells (one per
driver) into a temporary directory, with their configuration and mix files
under its own ``bench/``; the harness finds the drivers and metric readers
in the real ``bench/`` after it.  Nothing here touches the TPU library.
"""

import copy
import json
import os

import pytest

from bench import harness

def real_mix(name):
    """A real mix file: the smoke mixes hold the real limits."""
    with open(os.path.join(harness.BENCH, "mixes", name + ".json")) as f:
        return json.load(f)


SMOKE_CONFIG = {
    "name": "smoke", "source": "test", "hidden_size": 128,
    "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 500,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "program": {"dtype": "bfloat16", "qkv_bias": False},
}

SMOKE_MIXES = {
    "p2-smoke": {
        "driver": "p2", "batch": 2, "seq": 64, "batches": 4,
        "loss_chunk": 32, "buffer_mode": "clone", "loss_backend": "pallas",
        "tau": 2.0,
        "optimizer": {"lr": 0.001, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                      "weight_decay": 0.1},
        "check": dict(real_mix("p2-clone-2k")["check"])},
    "chat-smoke": {
        "driver": "serve",
        "engine": {"slots": 4, "max_len": 128, "page_size": 16},
        "arrivals": {"kind": "poisson", "rate_per_s": 6.0},
        "shared_prefix": {"count": 2, "tokens": 32, "zipf": 1.1},
        "prompt": {"kind": "lognormal", "median": 12, "sigma": 0.8,
                   "min": 4, "max": 40},
        "output": {"kind": "lognormal", "median": 6, "sigma": 0.8,
                   "min": 2, "max": 16},
        "check": dict(real_mix("chat-sysprompt")["check"], min_tokens=20,
                      max_requests=4, drain_s=60)},
    "backlog-smoke": {
        "driver": "serve",
        "engine": {"slots": 2, "max_len": 64, "page_size": 16},
        "arrivals": {"kind": "backlog", "requests": 4},
        "shared_prefix": None,
        "prompt": {"kind": "lognormal", "median": 10, "sigma": 0.6,
                   "min": 4, "max": 20},
        "output": {"kind": "uniform", "min": 8, "max": 16},
        "check": dict(real_mix("decode-backlog")["check"], min_tokens=10,
                      max_requests=2, drain_s=60)},
}

REAL = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))


def smoke_spec():
    spec = copy.deepcopy(REAL)
    spec["configs"] = [{"name": "smoke", "source": "test",
                        "file": "bench/configs/smoke.json", "reduced": [],
                        "why": "smoke size"}]
    cells = {"granite-p2-clone": "p2-smoke",
             "granite-chat-prefix": "chat-smoke",
             "qwen15-decode-backlog": "backlog-smoke"}
    for w in spec["workloads"]:
        w["config"], w["traffic"] = "smoke", cells[w["name"]]
    return spec


def write_root(root, spec=None, mixes=None):
    os.makedirs(os.path.join(root, "bench", "configs"), exist_ok=True)
    os.makedirs(os.path.join(root, "bench", "mixes"), exist_ok=True)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec or smoke_spec(), f)
    with open(os.path.join(root, "bench", "configs", "smoke.json"), "w") as f:
        json.dump(SMOKE_CONFIG, f)
    for name, mix in (mixes or SMOKE_MIXES).items():
        with open(os.path.join(root, "bench", "mixes", name + ".json"),
                  "w") as f:
            json.dump(mix, f)
    return str(root)


@pytest.fixture
def smoke_root(tmp_path):
    return write_root(tmp_path)


def run_smoke(root, workload, seed=12345678901, seconds=2.0, trace=False):
    """One smoke-size run through the harness, off the chip."""
    return harness.run_cell(root, workload, seed, seconds, trace,
                            search=[os.path.join(root, "bench"),
                                    harness.BENCH],
                            require_tpu=False, compile_cache=False)
