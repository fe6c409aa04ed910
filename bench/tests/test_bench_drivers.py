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
