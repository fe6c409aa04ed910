"""The control: the plain reference computed in float8 (e4m3), put in the
program's place.  Run here at a size a test run holds; ``bench/calibrate.py``
reads the same numbers on the chip at the cells' own sizes, where the
limits were set from them (PERF.md gives the readings).

Training: the control and the half-batch fault fail the mix file's limits
at this size too.  Serving: the widest logit gap is a maximum that grows
with depth and with the tokens compared (float8 read 1.77-4.35 at the
cells' sizes on the chip, 0.3-0.7 at 16 layers here), so here the control
must read far above the program, which stays inside the cell's limit."""

import json

from bench import calibrate, harness
from bench.tests.conftest import SMOKE_CONFIG, real_mix
from bench.weights import Dims

# Wider and deeper than the drivers' smoke size, so float8 rounding is not
# lost in a tiny model's logits.
CONTROL_CONFIG = dict(SMOKE_CONFIG, hidden_size=256, intermediate_size=512,
                      num_hidden_layers=4, vocab_size=2000)
SERVE_CONFIG = dict(CONTROL_CONFIG, num_hidden_layers=16)


def test_the_float8_control_fails_the_training_limits():
    mix = dict(real_mix("p2-clone-2k"), batch=2, seq=128, loss_chunk=64)
    dm = Dims.from_config(CONTROL_CONFIG)
    limits = mix["check"]
    ctrl, half = calibrate.p2_readings(dm, mix, 3, limits)
    assert any(ctrl[n] > limits[n] for n in limits), (ctrl, limits)
    assert any(half[n] > limits[n] for n in limits), (half, limits)


def serve_mix():
    """The chat mix at a size a test run holds, with its real limit."""
    base = real_mix("chat-sysprompt")
    return dict(base, engine={"slots": 4, "max_len": 256, "page_size": 16},
                arrivals={"kind": "poisson", "rate_per_s": 4.0},
                shared_prefix={"count": 2, "tokens": 64, "zipf": 1.1},
                prompt=dict(base["prompt"], median=24, min=8, max=64),
                output=dict(base["output"], median=64, min=16, max=128),
                check=dict(base["check"], min_tokens=150, max_requests=4))


def test_the_float8_control_reads_far_above_the_program_in_serving():
    mix = serve_mix()
    cell = harness.Cell("control", {"chips": 1}, SERVE_CONFIG, mix, 5, 2.0,
                        False, {"platform": "cpu", "kind": "cpu", "count": 1},
                        {}, [harness.BENCH])
    dm = Dims.from_config(SERVE_CONFIG)
    r = calibrate.serve_readings(cell, dm, 5, True,
                                 harness.CompileCounter())
    assert r["served"] >= 150
    assert max(r["program"]) <= mix["check"]["max_logit_gap"], r
    assert max(r["control"]) >= 5 * max(r["program"]), r



def test_the_float8_control_in_the_served_tokens_place_is_not_correct(
        tmp_path, monkeypatch):
    """A whole serving run through the harness, with the tokens that the
    float8 reference puts first compared in place of the served ones:
    ``correct`` reads false against the real mix's limit.

    The four requests are all due at the start, admitted in the first tick
    and drained to their end, so the check compares the same four
    requests, 283 served tokens, however slow the host.  The widest gap
    of float8 grows with depth and with the tokens compared: on the chip
    it reads 9-10 times the limit at the cells' 40 layers of width 2048
    over 300 tokens or more.  At width 256 these four requests read 0.615
    at 48 layers and 0.974 at 64, so the depth is the shallowest, in steps
    of 16 layers, at which the control fails by the margin asserted."""
    import functools

    from bench.tests.conftest import SMOKE_MIXES, run_smoke, write_root
    mix = serve_mix()
    mix = dict(mix, arrivals={"kind": "backlog", "requests": 4},
               check=dict(mix["check"], drain_s=3600))
    root = write_root(tmp_path, mixes=dict(SMOKE_MIXES, **{
        "chat-smoke": mix}))
    with open(f"{root}/bench/configs/smoke.json", "w") as f:
        json.dump(dict(CONTROL_CONFIG, num_hidden_layers=64), f)
    load = harness.load_module

    def control_in_place(path):          # the driver the harness loads
        mod = load(path)
        if hasattr(mod, "logit_gaps"):
            mod.logit_gaps = functools.partial(mod.logit_gaps, fp8=True)
        return mod

    monkeypatch.setattr(harness, "load_module", control_in_place)
    res = run_smoke(root, "granite-chat-prefix", seed=5)
    assert res["attempted"] == 4
    c = res["checks"]["max_logit_gap"]
    assert not res["correct"] and c["value"] > 2 * c["limit"], c


def test_calibrate_prints_program_and_control_readings_of_a_serving_cell(
        capsys):
    mix = serve_mix()
    cell = harness.Cell("control", {"chips": 1}, SMOKE_CONFIG, mix, 5, 2.0,
                        False, {"platform": "cpu", "kind": "cpu", "count": 1},
                        {}, [harness.BENCH])
    calibrate.serve_cell(cell, Dims.from_config(SMOKE_CONFIG), [5], {5})
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [r["kind"] for r in said] == ["program", "control"]
    assert said[0]["correct"] and said[0]["served"] > 0
