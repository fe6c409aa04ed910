"""The harness: device refusal, lookup by name, the compile guard, and
BENCHMARK.json against the files it names."""

import json
import os
import re

import pytest

from bench import harness
from bench.tests.conftest import REAL, SMOKE_MIXES, run_smoke, write_root

PEAKS = harness.load_json(os.path.join(harness.BENCH, "peaks.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_a_listed_tpu_is_accepted():
    harness.check_device("tpu", "TPU v5 lite", 1, 1, PEAKS)


@pytest.mark.parametrize("platform, kind, count, chips, why", [
    ("cpu", "cpu", 1, 1, "no TPU"),
    ("tpu", "TPU v9 imaginary", 1, 1, "no peaks"),
    ("tpu", "TPU v5 lite", 1, 4, "needs 4 chips"),
])
def test_a_run_off_a_known_tpu_is_refused(platform, kind, count, chips, why):
    with pytest.raises(harness.HarnessError, match=why):
        harness.check_device(platform, kind, count, chips, PEAKS)


def test_the_command_line_refuses_the_cpu(capsys):
    from bench import run
    assert run.main(["--workload", "granite-p2-clone", "--seed", "1",
                     "--seconds", "1"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_a_compile_in_the_window_fails_the_run():
    import jax
    counter = harness.CompileCounter()
    f = jax.jit(lambda x: x * 3 + 1)
    f(1.0)
    with harness.window(counter):
        f(2.0)                                   # cached: no compile
    with pytest.raises(harness.HarnessError, match="compile"):
        with harness.window(counter):
            f(jax.numpy.ones(3))                 # a new shape compiles


def test_a_mix_added_as_a_file_is_found_by_name(tmp_path):
    mixes = dict(SMOKE_MIXES)
    mixes["backlog-other"] = dict(SMOKE_MIXES["backlog-smoke"],
                                  output={"kind": "uniform", "min": 4,
                                          "max": 6})
    root = write_root(tmp_path, mixes=mixes)
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["workloads"].append(dict(spec["workloads"][2],
                                  name="backlog-other",
                                  traffic="backlog-other"))
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    res = run_smoke(root, "backlog-other", seconds=1.0)
    assert res["correct"] and res["attempted"] == 4


def test_a_metric_added_as_a_file_is_found_by_name(tmp_path):
    mdir = tmp_path / "bench" / "metrics"
    mdir.mkdir(parents=True)
    (mdir / "answer.p2.py").write_text(
        "def read(facts):\n    return facts['steps'] * 2\n")
    (mdir / "silent.p2.py").write_text("def read(facts):\n    return None\n")
    spec = json.loads(json.dumps(REAL))
    for name in ("answer.p2", "silent.p2"):
        spec["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "test",
            "moves": "p2_tokens_s", "workloads": ["granite-p2-clone"]})
    got = harness.read_metrics(spec, "granite-p2-clone", {"steps": 21},
                               [str(tmp_path / "bench"), harness.BENCH])
    assert got["answer.p2"] == {"value": 42, "unit": "%"}
    assert "silent.p2" not in got            # nothing to read: left out
    assert "step_mfu.p2" not in got          # no trace facts: left out


def test_benchmark_json_names_files_that_exist():
    root = harness.ROOT
    assert set(REAL) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in REAL["end_to_end"]}
    assert "setup_s" in e2e
    for c in REAL["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["why"]) <= 200
        cfg = json.load(open(os.path.join(root, c["file"])))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in REAL["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(harness.BENCH, "mixes",
                                           w["traffic"] + ".json"))
    for m in REAL["end_to_end"] + REAL["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    for m in REAL["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in REAL["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                           m["name"] + ".py"))
        for w in m["workloads"]:
            cell_e2e = {e["name"] for e in REAL["end_to_end"]
                        if "workloads" not in e or w in e["workloads"]}
            assert m["moves"] in cell_e2e
