"""The trace reduction on small fixtures cut from chip traces (TPU v5 lite,
one chip): a granite chat window (one admission and the tick after it) and
one granite-l4 Phase-2 step.  Names were shortened to their first 120
characters, keeping the custom-call marker."""

import gzip
import json
import os

import pytest

from bench import trace as T
from bench.drivers import p2, serve

HERE = os.path.dirname(os.path.abspath(__file__))


def fixture(name):
    with gzip.open(os.path.join(HERE, "fixtures", name), "rt") as f:
        d = json.load(f)
    return T.Trace.from_json(d), tuple(d["window"])


@pytest.fixture(scope="module")
def chat():
    return fixture("trace_serve.json.gz")


@pytest.fixture(scope="module")
def step():
    return fixture("trace_p2.json.gz")


def test_busy_is_the_union_of_op_intervals(chat):
    tr, win = chat
    busy = T.busy_s(tr, win)
    assert 0 < busy <= (win[1] - win[0]) / 1e9
    # nested loop events do not count twice: the leaves alone give as much
    leaves = T.Trace([T.leaf_ops(tr.ops[0])], tr.modules, tr.spans)
    assert abs(T.busy_s(leaves, win) - busy) < 0.05 * busy
    # a trace with two identical devices averages, not sums
    twice = T.Trace(tr.ops * 2, tr.modules * 2, tr.spans)
    assert T.busy_s(twice, win) == pytest.approx(busy)


def test_modules_and_the_paged_kernel(chat):
    tr, _ = chat
    tick_s, ticks = T.module_time(tr, "jit_tick")
    admit_s, admits = T.module_time(tr, "jit_admit")
    assert ticks >= 1 and admits >= 1 and tick_s > 0 and admit_s > 0
    kern_s, calls = T.op_time(tr, serve.paged_decode_op)
    assert calls == 40 * ticks                  # one call a layer a tick
    assert 0 < kern_s < tick_s


def test_the_kd_loss_kernels_of_one_step(step):
    tr, _ = step
    s, runs = T.module_time(tr, "jit_p2_step")
    assert runs == 1 and s > 0.3
    rows = 4 * 512
    _, fwd = T.op_time(tr, lambda n: p2.kd_loss_op(n, rows) == "fwd")
    _, bwd = T.op_time(tr, lambda n: p2.kd_loss_op(n, rows) == "bwd")
    assert (fwd, bwd) == (8, 4)     # 4 chunks: forward, its remat, backward


def test_breakdown(chat):
    tr, win = chat
    top = T.top_ops(tr)
    assert len(top) <= 10 and top == sorted(top, key=lambda x: -x[1])
    assert "custom-call" in top[0][0]           # paged_decode leads
    assert not any("while" in name for name, _ in top)   # leaves only
    gaps = T.idle_gaps(tr, win)
    assert gaps and all(g[0].startswith("bench.") or g[0] == "no host span"
                        for g in gaps)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)


def test_hlo_text_helpers():
    name = ('%closed_call.14 = f32[2048,11]{1,0:T(8,128)S(1)} custom-call('
            's32[2048,1]{1,0} %b), custom_call_target="tpu_custom_call"')
    assert T.is_kernel(name) and T.result_shape(name) == "f32[2048,11]"
    assert p2.kd_loss_op(name, 2048) == "fwd"
    assert T.short_name(name) == "%closed_call.14 = f32[2048,11] custom-call"
    assert not T.is_kernel("%fusion.3 = bf16[16,8192]{1,0} fusion(x)")
