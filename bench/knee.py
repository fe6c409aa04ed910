"""Find a serving cell's knee once: the highest open-loop rate at which the
engine keeps up, by a sweep of rates in one process on the chip.

    python3 bench/knee.py --workload granite-chat-prefix --rates 0.8 1.2 1.6 \
        --seconds 30 --seed 5

For each rate the cell's mix is run with that rate for ``--seconds`` on one
engine (weights and warm-up made once), drained between rates, and one
line is printed: requests due and admitted, time to first token at p50
and p90, and how much the wait grew from the first third of the window's
requests to the last.  A rate is sustained where every request due was
admitted and the wait does not grow.  The result is written into the mix
file and PERF.md by hand; the benchmark's own runs never sweep.
"""

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, traffic  # noqa: E402
from bench.drivers import serve  # noqa: E402
from bench.weights import Dims  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    w, c = harness.cell_spec(spec, a.workload)
    peaks = harness.load_json(os.path.join(harness.BENCH, "peaks.json"))
    device = harness.device_facts(w["chips"], peaks)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    config = harness.load_json(os.path.join(ROOT, c["file"]))
    base = harness.load_json(os.path.join(harness.BENCH, "mixes",
                                          w["traffic"] + ".json"))
    dm = Dims.from_config(config)
    cell = harness.Cell(a.workload, w, config, base, a.seed, a.seconds,
                        False, device, peaks, [harness.BENCH])
    engine = serve.build_engine(cell, dm)
    counter = harness.CompileCounter()
    tracer = harness.Tracer(False)
    for rate in a.rates:
        mix = copy.deepcopy(base)
        mix["arrivals"]["rate_per_s"] = rate
        plan = traffic.plan(mix, a.seed, a.seconds, dm.vocab)
        serve.warm(engine, plan, mix, a.seed, dm.vocab)
        ledger, t0, t_end, handed, finished, inflight = serve.serve_window(
            engine, plan, a.seconds, tracer, counter)
        e2e, info = serve.end_to_end(ledger, plan, handed, t0, t_end, 0.0)
        due = {p.rid: t0 + p.due for p in plan}
        waits = [(r.t_first if 0 < r.t_first <= t_end else t_end)
                 - due[r.rid] for r in handed]
        third = max(1, len(waits) // 3)
        admitted = sum(1 for r in handed if r.admitted_at >= 0)
        print(json.dumps({
            "rate_per_s": rate, "due": len(handed), "admitted": admitted,
            "ttft_p50_ms": info["ttft_p50_ms"],
            "ttft_p90_ms": e2e.get("ttft_p90_ms"),
            "itl_p99_ms": e2e.get("itl_p99_ms"),
            "wait_first_third_s": sum(waits[:third]) / third,
            "wait_last_third_s": sum(waits[-third:]) / third,
            "tokens_per_s": e2e["decode_tok_s"]}), flush=True)
        serve.drain(engine, inflight, finished, 600)


if __name__ == "__main__":
    main()
