"""Each driver at smoke size through the harness's own code path (the
test-only entry ``run_cell(..., require_tpu=False)``; the command line has
no such switch)."""

import pytest

from bench.tests.conftest import run_smoke

CELLS = {
    "granite-p2-clone": {"setup_s", "p2_tokens_s"},
    "granite-chat-prefix": {"setup_s", "ttft_p90_ms", "itl_p99_ms"},
    "qwen15-decode-backlog": {"setup_s", "decode_tok_s"},
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_sound_run_is_correct(smoke_root, cell):
    res = run_smoke(smoke_root, cell)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == CELLS[cell]
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])


def test_the_check_takes_a_request_from_every_slot_in_use():
    from types import SimpleNamespace as R

    import numpy as np

    from bench.drivers import serve
    ledger = serve.Ledger(16)
    ledger.holders = [[1, 2], [1, 2, 3], [2, 3, 4], [4]]
    finished = [R(rid=i, out=[0] * n) for i, n in
                ((1, 5), (2, 9), (3, 4), (4, 7), (5, 30), (6, 3))]
    busiest = {tuple(ledger.busiest(np.random.default_rng(s)))
               for s in range(20)}
    assert busiest == {(1, 2, 3), (2, 3, 4)}
    check = {"min_tokens": 0, "max_requests": 8}
    for seed in range(5):
        got = {r.rid for r in serve.sample(finished, check, seed, ledger)}
        assert got in ({1, 2, 3, 5}, {2, 3, 4, 5})    # + the longest, 5
    got = serve.sample(finished, dict(check, min_tokens=1000), 0, ledger)
    assert len(got) == len(finished)


def smoke_facts(root, workload, monkeypatch):
    """The facts a smoke run's driver hands the metric readers."""
    from bench import harness
    seen = {}
    load = harness.load_module

    def keep_facts(path):
        mod = load(path)
        if hasattr(mod, "run"):                 # the driver, not a reader
            real = mod.run

            def run(cell, t_start):
                out = real(cell, t_start)
                seen.update(out.facts)
                return out
            mod.run = run
        return mod

    monkeypatch.setattr(harness, "load_module", keep_facts)
    res = run_smoke(root, workload)
    return res, seen


def test_the_chat_cell_reads_slot_occupancy_and_queue_wait(smoke_root,
                                                            monkeypatch):
    """Both come from the engine's own counters and stamps over the window,
    in every run; the readers give nothing where the run gave nothing."""
    import numpy as np

    from bench import harness
    from bench.tests.conftest import REAL
    res, facts = smoke_facts(smoke_root, "granite-chat-prefix", monkeypatch)
    got = harness.read_metrics(REAL, "granite-chat-prefix", facts,
                               [harness.BENCH])
    before, after = facts["window_counters"]
    ticks = after["decode_ticks"] - before["decode_ticks"]
    rows = after["decode_rows"] - before["decode_rows"]
    occ = got["slot_occupancy.chat"]
    assert occ["unit"] == "%" and 0 < occ["value"] <= 100
    assert occ["value"] == pytest.approx(100 * rows / (ticks * facts["slots"]))
    waits = facts["queue_waits_s"]
    assert len(waits) == res["attempted"] and min(waits) >= 0
    wait = got["queue_wait_p90_ms.chat"]
    assert wait["unit"] == "ms"
    assert wait["value"] == pytest.approx(1e3 * np.percentile(waits, 90))
    for name in ("slot_occupancy.chat", "queue_wait_p90_ms.chat"):
        reader = harness.load_module(f"{harness.BENCH}/metrics/{name}.py")
        assert reader.read({}) is None
        assert reader.read(dict(facts, window_counters=(before, before),
                                queue_waits_s=[])) is None
    backlog = harness.read_metrics(REAL, "qwen15-decode-backlog", facts,
                                   [harness.BENCH])
    assert not {"slot_occupancy.chat", "queue_wait_p90_ms.chat"} & set(backlog)
