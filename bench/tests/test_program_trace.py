"""The program's own marks in a profiler trace: the serving engine's
``serve.*`` spans and counters, the Phase-2 step's named scopes, and their
reduction by :mod:`bench.program_trace`, on CPU runs and on small
fixtures cut from chip traces (TPU v5 lite, one chip)."""

import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, traffic
from bench import program_trace as PT
from bench import trace as T
from bench.drivers import serve
from bench.tests.conftest import SMOKE_CONFIG, SMOKE_MIXES
from bench.weights import Dims

HERE = os.path.dirname(os.path.abspath(__file__))
STEP_SCOPES = ["p2_student", "p2_teacher", "p2_buffer", "p2_kd_loss",
               "p2_adamw"]
# span -> the span it lies in (the engine module's docstring)
PARENT = {"serve.schedule": "serve.tick", "serve.admit": "serve.tick",
          "serve.decode": "serve.tick", "serve.decode.sync": "serve.tick",
          "serve.emit": "serve.tick", "serve.admit.prep": "serve.admit",
          "serve.admit.sync": "serve.admit"}


def smoke_engine(mix_name, seed=2500000003):
    mix = SMOKE_MIXES[mix_name]
    dm = Dims.from_config(SMOKE_CONFIG)
    cell = harness.Cell(name="smoke", workload={"chips": 1},
                        config=SMOKE_CONFIG, mix=mix, seed=seed, seconds=2.0,
                        trace=False, device={}, peaks={}, search=[])
    plan = traffic.plan(mix, seed, cell.seconds, dm.vocab)
    engine = serve.build_engine(cell, dm)
    serve.warm(engine, plan, mix, seed, dm.vocab)
    return engine, plan, mix


def test_serve_spans_nest_as_documented(tmp_path):
    engine, plan, _ = smoke_engine("chat-smoke")
    reqs = [serve.make_request(p.rid, p.prompt, p.max_new) for p in plan[:6]]
    c0 = engine.counters()
    with jax.profiler.trace(str(tmp_path)):
        serve.serve_until_idle(engine, reqs)
    c1 = engine.counters()
    path = next(os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
                for f in fs if f.endswith(".xplane.pb"))
    spans = PT.load_spans(path)
    names = [s[0] for s in spans]
    assert set(names) == set(PARENT) | {"serve.tick"}
    for name, start, dur, _ in spans:
        if name == "serve.tick":
            continue
        outer = [s for s in spans if s[0] == PARENT[name]
                 and s[1] <= start and start + dur <= s[1] + s[2]]
        assert len(outer) == 1, name
    # one decode per decode tick; the admission args add up to the counters
    assert names.count("serve.decode") == (c1["decode_ticks"]
                                           - c0["decode_ticks"])
    admits = [a for n, _, _, a in spans if n == "serve.admit"]
    assert len(admits) == c1["admissions"] - c0["admissions"]
    assert sum(a["rows"] for a in admits) == len(reqs)
    assert sum(engine.slots * a["bucket"] for a in admits) == (
        c1["admit_rows"] - c0["admit_rows"])
    assert all(a["npp"] > 0 for a in admits)        # every prompt hits
    # the harness's own reduction keeps its bench.* spans alone
    assert T.load(path).spans == []


@pytest.mark.parametrize("mix_name", ["chat-smoke", "backlog-smoke"])
def test_counters_match_the_ledger(mix_name):
    """Over a smoke window the engine counts the rows the harness's Ledger
    saw in slots tick by tick, and stamps every admitted request's
    admission before its first token."""
    engine, plan, _ = smoke_engine(mix_name)
    c0 = engine.counters()
    ledger, _, _, handed, _, _ = serve.serve_window(
        engine, plan, 2.0, harness.Tracer(False), harness.CompileCounter())
    c1 = engine.counters()
    d = {k: c1[k] - c0[k] for k in c1}
    assert d["decode_ticks"] == len(ledger.holders)
    assert d["decode_rows"] == sum(map(len, ledger.holders))
    assert PT.slot_occupancy(c0, c1, engine.slots) == pytest.approx(
        100 * sum(map(len, ledger.holders))
        / (len(ledger.holders) * engine.slots))
    admitted = [r for r in handed if r.t_admit > 0]
    assert len(admitted) == d["admitted"] > 0
    assert all(r.t_admit <= r.t_first for r in admitted)
    assert all(r.t_first < 0 for r in handed if r.t_admit < 0)


def test_scopes_reach_the_compiled_hlo():
    """Two scopes of a jitted toy step, forward and backward, and a
    compiler copy inside a loop that takes the loop's scope."""

    def step(w, x):
        def loss(w):
            with jax.named_scope("student"):
                h = jnp.tanh(x @ w)
            with jax.named_scope("teacher"):
                t = jax.lax.stop_gradient(jnp.sin(x @ w.T))
            return jnp.sum((h - t) ** 2)
        return jax.grad(loss)(w)

    w = x = jnp.ones((64, 64), jnp.float32)
    text = jax.jit(step).lower(w, x).compile().as_text()
    scopes = PT.hlo_scopes(text)
    paths = set(scopes.values())
    assert any(PT.in_scope(p, "student") and "transpose" in p for p in paths)
    assert any(PT.in_scope(p, "teacher") for p in paths)
    dots = [n for n in scopes if n.startswith("dot")]
    ops = [(f"%{n} = f32[64,64]{{1,0}} dot(...)", i, 1) for i, n in
           enumerate(dots)]
    by = PT.time_by_scope(ops, scopes, ["student", "teacher"])
    assert by["student"] > 0 and by["teacher"] > 0
    assert sum(by.values()) == pytest.approx(len(dots) / 1e9)


def test_unscoped_instructions_take_the_scope_of_their_caller():
    text = "\n".join([
        "%body.1 (p: f32[4]) -> f32[4] {",
        "  %copy.3 = f32[4]{0} copy(f32[4]{0} %p)",
        '  ROOT %mul.2 = f32[4]{0} multiply(%copy.3, %copy.3), '
        'metadata={op_name="jit(f)/transpose(jvp(p2_student))/while/body/mul"}',
        "}",
        "%cond.1 (p: f32[4]) -> pred[] {",
        "  ROOT %c.1 = pred[] constant(true)",
        "}",
        "ENTRY %main.9 (a: f32[4]) -> f32[4] {",
        "  %a = f32[4]{0} parameter(0)",
        "  %while.5 = f32[4]{0} while(%a), condition=%cond.1, body=%body.1, "
        'metadata={op_name="jit(f)/transpose(jvp(p2_student))/while"}',
        "  ROOT %copy.9 = f32[4]{0} copy(%while.5)",
        "}"])
    s = PT.hlo_scopes(text)
    assert PT.in_scope(s["copy.3"], "p2_student")
    assert PT.in_scope(s["c.1"], "p2_student")
    assert s["copy.9"] == PT.UNSCOPED and s["a"] == PT.UNSCOPED
    assert PT.instruction("%copy.3 = f32[4]{0} copy(...)") == "copy.3"
    assert not PT.in_scope("jit(f)/p2_student_x/add", "p2_student")


def test_idle_time_goes_to_the_innermost_span():
    ops = [("a", 10, 10), ("b", 40, 20), ("c", 55, 20)]   # busy 10-20, 40-75
    spans = [("bench.tick", 0, 100, {}), ("serve.tick", 5, 90, {}),
             ("serve.decode.sync", 20, 15, {}), ("bench.wait", 100, 10, {})]
    idle = PT.idle_by_span(ops, spans, (0, 120))
    assert idle == pytest.approx({
        "serve.tick": 5e-9 + 5e-9 + 20e-9,       # 5-10, 35-40, 75-95
        "serve.decode.sync": 15e-9,              # 20-35
        "bench.tick": 5e-9 + 5e-9,               # 0-5, 95-100
        "bench.wait": 10e-9, PT.NO_SPAN: 10e-9})
    assert sum(idle.values()) == pytest.approx(
        120e-9 - T.busy_s(T.Trace([ops], [], []), (0, 120)))


def test_queue_waits_run_to_the_admission_or_the_close():
    from repro.serve.streams import Request
    reqs = [Request(rid=i, arrival=0, prompt=np.zeros(4, np.int32), max_new=2)
            for i in range(3)]
    reqs[0].t_admit, reqs[1].t_admit = 10.5, 30.0     # rid 1 after the close
    due = {0: 10.0, 1: 11.0, 2: 12.0}
    assert PT.queue_waits(reqs, due, 20.0) == [0.5, 9.0, 8.0]
    assert PT.serve_idle_s({"serve.emit": 1.0, "serve.tick": 2.0,
                            "bench.tick": 4.0, PT.NO_SPAN: 8.0}) == 3.0
    assert PT.slot_occupancy({"decode_ticks": 1, "decode_rows": 2},
                             {"decode_ticks": 1, "decode_rows": 2}, 4) is None


def test_module_ops_keep_only_the_named_executable():
    ops = [("x", 5, 1), ("y", 15, 1), ("z", 25, 1)]
    mods = [("jit_p2_step(1)", 10, 10), ("jit_other(2)", 20, 10)]
    assert PT.module_ops(ops, mods, "jit_p2_step") == [("y", 15, 1)]


# -- fixtures cut from chip traces ---------------------------------------------

def fixture(name):
    with gzip.open(os.path.join(HERE, "fixtures", name), "rt") as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["trace_chat_spans.json.gz",
                                  "trace_decode_spans.json.gz"])
def test_idle_by_span_on_chip_fixtures(name):
    d = fixture(name)
    ops = [tuple(o) for o in d["ops"][0]]
    spans = [tuple(s) for s in d["program_spans"]] + [
        (n, s, w, {}) for n, s, w in d["spans"]]
    idle = PT.idle_by_span(ops, spans, tuple(d["window"]))
    assert idle == pytest.approx(d["printed"]["idle_by_span_s"], rel=1e-9)
    assert any(k.startswith("serve.") for k in idle)
    admits = [a for n, _, _, a in d["program_spans"] if n == "serve.admit"]
    assert admits and all(set(a) == {"rows", "bucket", "npp"}
                          for a in admits)


def test_time_by_scope_on_the_chip_fixture():
    d = fixture("trace_p2_scopes.json.gz")
    ops = PT.module_ops([tuple(o) for o in d["ops"][0]],
                        [tuple(m) for m in d["modules"][0]], "jit_p2_step")
    leaves = T.leaf_ops(ops)
    p = d["printed"]
    assert sum(x[2] for x in leaves) / 1e9 == pytest.approx(p["leaf_total_s"])
    by = PT.time_by_scope(leaves, d["scopes"], STEP_SCOPES)
    assert by == pytest.approx(p["by_step_scope"], rel=1e-9)
    layer = PT.time_by_scope(leaves, d["scopes"], ["attention", "mlp",
                                                   "unembed"])
    assert layer == pytest.approx(p["by_layer_scope"], rel=1e-9)
    # the step scopes cover nine tenths of the step's leaf-op time
    assert by[PT.UNSCOPED] < 0.1 * p["leaf_total_s"]
