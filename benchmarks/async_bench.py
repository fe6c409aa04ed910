"""Asynchronous-FL benchmark — bkd vs kd vs fedavg under emergent delay.

The simulator-scale version of the paper's Figs. 9 & 11 story: instead of
scripting staleness (a StalenessPolicy), each named `async_*` scenario runs
the event-driven virtual-clock simulator (repro/core/simulator.py) over a
heterogeneous device population — uniform speed spread, heavy-tail
(lognormal) speeds with deadline aggregation, and lossy edges with
distill-on-arrival — and every method consumes the *same* emergent arrival
timeline.  The `hier_*` family adds the two-level regime (fleet.py): each
region buffers its own window of edges and regions distill into the core
asynchronously, so the benchmark reports whether the buffered-vs-plain gap
(`bkd_minus_kd`) survives when aggregation composes across levels.  The
fleet-scale section times the vectorized FleetSimulator on a 100k-edge
timeline (the acceptance wall-clock assert) against the heap loop at an
overlapping scale.  Everything lands in one JSON document, the start of
the BENCH_*.json perf trajectory (CI runs `--smoke` and uploads the
artifact).

    PYTHONPATH=src python benchmarks/async_bench.py [--smoke] [--out f.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

try:
    from benchmarks.common import run_method
except ModuleNotFoundError:  # invoked as `python benchmarks/async_bench.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.common import run_method
from repro.core.scheduler import (ASYNC_SCENARIOS, HIER_SCENARIOS,
                                  build_scenario)

METHODS = ("bkd", "kd", "fedavg")

#: Wall-clock ceiling for the 100k-edge fleet timeline ("simulates in
#: seconds") — the vectorized loop does it in well under one.
FLEET_SCALE_BUDGET_S = 60.0


def bench_fleet_scale(smoke, seed=0):
    """Time the vectorized simulator at fleet scale (100k edges) and
    against the heap loop at an overlapping scale.  Asserts the 100k
    timeline stays within FLEET_SCALE_BUDGET_S."""
    from repro.core.fleet import FleetSimulator, HierarchicalFleetSimulator
    from repro.core.simulator import BufferedWindow, EventDrivenSimulator

    edges, rounds = 100_000, 100 if smoke else 300
    t0 = time.time()
    flat = FleetSimulator(edges, "heavy_tail", BufferedWindow(64), seed=seed)
    flat.plans(rounds)
    flat_s = time.time() - t0

    t0 = time.time()
    hier = HierarchicalFleetSimulator(
        edges, 100, "uniform", region_trigger=BufferedWindow(8),
        core_trigger=BufferedWindow(8), seed=seed)
    hier.plans(20 if smoke else 50)
    hier_s = time.time() - t0

    # Heap-vs-fleet at an overlapping scale: same arguments, same plans
    # (pinned by tests/test_fleet.py) — here only the wall-clock ratio.
    small, small_rounds = 2_000, 50
    t0 = time.time()
    EventDrivenSimulator(small, "heavy_tail", BufferedWindow(16),
                         seed=seed).plans(small_rounds)
    heap_s = time.time() - t0
    t0 = time.time()
    FleetSimulator(small, "heavy_tail", BufferedWindow(16),
                   seed=seed).plans(small_rounds)
    fleet_small_s = time.time() - t0

    ok = flat_s < FLEET_SCALE_BUDGET_S and hier_s < FLEET_SCALE_BUDGET_S
    print(f"# fleet-scale: {edges} edges x {rounds} rounds in {flat_s:.2f}s "
          f"(budget {FLEET_SCALE_BUDGET_S:.0f}s -> "
          f"{'ok' if ok else 'OVER BUDGET'}); hierarchical "
          f"{hier.stats['regions']} regions in {hier_s:.2f}s; "
          f"{small}-edge heap {heap_s:.2f}s vs fleet {fleet_small_s:.2f}s "
          f"({heap_s / max(fleet_small_s, 1e-9):.0f}x)", flush=True)
    return {
        "edges": edges, "rounds": rounds, "seconds": round(flat_s, 3),
        "budget_seconds": FLEET_SCALE_BUDGET_S, "within_budget": ok,
        "timeline": {k: flat.stats[k] for k in
                     ("dispatches", "teachers", "mean_staleness",
                      "max_staleness", "makespan")},
        "hierarchical": {"regions": hier.stats["regions"],
                         "core_rounds": hier.stats["rounds"],
                         "region_rounds": hier.stats["region_rounds"],
                         "seconds": round(hier_s, 3)},
        "heap_vs_fleet": {"edges": small, "rounds": small_rounds,
                          "heap_seconds": round(heap_s, 3),
                          "fleet_seconds": round(fleet_small_s, 3),
                          "speedup": round(heap_s / max(fleet_small_s, 1e-9),
                                           1)},
    }


def bench_scenario(name, *, methods, rounds, num_edges, aggregation_r, seed,
                   epochs):
    # The timeline is method-independent (device heterogeneity, not weights,
    # drives it): simulate it once for the stats every method shares.
    sim = build_scenario(name, num_edges, aggregation_r=aggregation_r,
                         seed=seed)
    plans = sim.plans(rounds)
    timeline = dict(sim.stats)
    # Two-level (hier_*) streams interleave region rounds between the core
    # rounds; the per-round teacher counts describe the distillation rounds
    # the methods actually consume at the top level.
    timeline["teachers_per_round"] = [
        len(p.tasks) for p in plans if getattr(p, "level", "") != "region"]

    per_method = {}
    for method in methods:
        hist, dt = run_method(method, rounds=rounds, num_edges=num_edges,
                              aggregation_r=aggregation_r, seed=seed,
                              epochs=epochs, scenario=name)
        accs = [h["test_acc"] for h in hist]
        forget = [h["forget_score"] for h in hist if "forget_score" in h]
        per_method[method] = {
            "final_acc": round(accs[-1], 4),
            "mean_acc": round(float(np.mean(accs)), 4),
            "mean_forget": (round(float(np.mean(forget)), 4)
                            if forget else None),
            "seconds": round(dt, 2),
        }
        print(f"# {name}/{method}: final={accs[-1]:.3f} "
              f"mean={np.mean(accs):.3f}", flush=True)
    out = {"timeline": timeline, "methods": per_method}
    if "bkd" in per_method and "kd" in per_method:
        # The paper's question, per scenario: does buffering beat plain KD
        # under this timeline?  (For hier_* scenarios: across two levels.)
        out["bkd_minus_kd"] = round(per_method["bkd"]["mean_acc"]
                                    - per_method["kd"]["mean_acc"], 4)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes — CI wiring check, not a benchmark")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--edges", type=int, default=None)
    ap.add_argument("--aggregation-r", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--methods", nargs="+", default=list(METHODS))
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    rounds = args.rounds or (2 if args.smoke else 6)
    edges = args.edges or (4 if args.smoke else 6)
    epochs = (4, 4, 2) if args.smoke else (10, 10, 5)

    scenarios = {}
    for name in ASYNC_SCENARIOS + HIER_SCENARIOS:
        scenarios[name] = bench_scenario(
            name, methods=args.methods, rounds=rounds, num_edges=edges,
            aggregation_r=args.aggregation_r, seed=args.seed, epochs=epochs)

    fleet_scale = bench_fleet_scale(args.smoke, seed=args.seed)

    report = {
        "config": {"smoke": args.smoke, "rounds": rounds, "edges": edges,
                   "aggregation_r": args.aggregation_r, "seed": args.seed,
                   "methods": list(args.methods)},
        "scenarios": scenarios,
        "fleet_scale": fleet_scale,
    }
    doc = json.dumps(report, indent=2)
    print(doc)
    if args.out:
        with open(args.out, "w") as f:
            f.write(doc + "\n")

    ok = all(np.isfinite(m["final_acc"])
             for s in scenarios.values() for m in s["methods"].values())
    # The scenarios must actually exercise the async machinery: some
    # emergent staleness somewhere, and every scenario produced its rounds.
    ok &= any(s["timeline"]["max_staleness"] > 0 for s in scenarios.values())
    ok &= all(s["timeline"]["rounds"] == rounds for s in scenarios.values())
    # Acceptance: 100k-edge fleet timeline simulates in seconds, and the
    # hierarchical family reported the bkd-vs-kd gap.
    ok &= fleet_scale["within_budget"]
    ok &= all("bkd_minus_kd" in scenarios[n] for n in HIER_SCENARIOS
              if {"bkd", "kd"} <= set(args.methods))
    return 0 if ok else 1


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
