"""Transport benchmark — accuracy-vs-uplink-bytes frontiers per codec.

The paper's uplink is the edge→core link each round's teacher must cross;
repro/transport makes that link a pluggable codec (identity, top-k, int8,
int4 affine quantization, entropy filtering — see docs/transport.md).  This
benchmark runs the same FL problem under every codec across three round
regimes — the synchronous paper default, an emergent-staleness `async_*`
timeline, and a two-level `hier_*` fleet — and reports one frontier per
regime: final/mean accuracy against exact uplink bytes from the Phase-2
engine's per-dispatch accounting (`DistillEngine.uplink_log`).

Two lockdowns ride along: `identity` must reproduce the no-transport
baseline bit-for-bit (the codec is a pass-through in the traced graph, so
the accuracies must be *equal*, not close), and the heap/fleet simulators
must report bit-identical uplink-byte stats for the same timeline
arguments.  Everything lands in one JSON document (BENCH_transport.json);
CI runs `--smoke` and uploads the artifact.

    PYTHONPATH=src python benchmarks/transport_bench.py [--smoke] [--out f.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

try:
    from benchmarks.common import build_setup
except ModuleNotFoundError:  # invoked as `python benchmarks/transport_bench.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.common import build_setup
from repro.core.fl import FederatedKD, FLConfig
from repro.core.scheduler import build_scenario
from repro.transport import parse_codec

#: The frontier: the exact baseline plus every lossy family, including one
#: filter composition.  "none" is the control the identity gate compares to.
CODEC_SPECS = ("identity", "topk:16", "int8", "int4", "entropy:0.5+int8")

#: One synchronous regime, one emergent-staleness timeline, one two-level
#: fleet — the frontier must survive all three plan streams.
SCENARIO_NAMES = ("none", "async_uniform", "hier_uniform")

METHOD = "bkd"


def run_one(scenario, transport, *, rounds, num_edges, epochs, seed):
    """One end-to-end FL run through FederatedKD (not run_method: the bench
    needs the engine's uplink_log, which the csv harness doesn't expose)."""
    adapter, core, edges, test = build_setup(num_edges=num_edges, seed=seed)
    cfg = FLConfig(num_edges=num_edges, rounds=rounds, method=METHOD,
                   core_epochs=epochs[0], edge_epochs=epochs[1],
                   kd_epochs=epochs[2], batch_size=128, seed=seed,
                   transport=transport)
    scheduler = (None if scenario == "none" else
                 build_scenario(scenario, num_edges, seed=seed))
    fl = FederatedKD(adapter, cfg, core, edges, test, scheduler=scheduler)
    t0 = time.time()
    _, hist = fl.run(jax.random.key(seed), log=None)
    dt = time.time() - t0
    eng = fl.distill_engine
    accs = [h["test_acc"] for h in hist]
    return {
        "final_acc": accs[-1],
        "mean_acc": float(np.mean(accs)),
        "uplink_bytes": eng.uplink_bytes_total,
        "dispatches": len(eng.uplink_log),
        "teachers": sum(r["teachers"] for r in eng.uplink_log),
        "seconds": round(dt, 2),
    }


def bench_frontier(scenario, *, rounds, num_edges, epochs, seed):
    """The no-transport control plus every codec, as one frontier."""
    base = run_one(scenario, "none", rounds=rounds, num_edges=num_edges,
                   epochs=epochs, seed=seed)
    print(f"# {scenario}/none: final={base['final_acc']:.3f}", flush=True)
    points, ident_bytes = [], None
    for spec in CODEC_SPECS:
        r = run_one(scenario, spec, rounds=rounds, num_edges=num_edges,
                    epochs=epochs, seed=seed)
        if spec == "identity":
            ident_bytes = r["uplink_bytes"]
        points.append({"codec": spec, **{k: (round(v, 4)
                       if isinstance(v, float) else v) for k, v in r.items()}})
        print(f"# {scenario}/{spec}: final={r['final_acc']:.3f} "
              f"bytes={r['uplink_bytes']}", flush=True)
    for p in points:
        p["compression_vs_identity"] = (
            round(ident_bytes / p["uplink_bytes"], 2)
            if p["uplink_bytes"] else None)
    identity = next(p for p in points if p["codec"] == "identity")
    return {
        "baseline": {k: (round(v, 4) if isinstance(v, float) else v)
                     for k, v in base.items()},
        "frontier": points,
        # The acceptance gate: identity transport is a pass-through in the
        # traced loss, so its accuracies must EQUAL the no-transport run's.
        "identity_bit_for_bit": (
            identity["final_acc"] == round(base["final_acc"], 4)
            and identity["mean_acc"] == round(base["mean_acc"], 4)),
    }


def bench_sim_accounting(seed=0):
    """Uplink-byte accounting at the simulator level: the heap and fleet
    simulators must report bit-identical byte stats for the same timeline,
    and the hierarchical fleet splits edge-logit vs core-snapshot bytes per
    region."""
    from repro.core.fleet import FleetSimulator, HierarchicalFleetSimulator
    from repro.core.simulator import BufferedWindow, EventDrivenSimulator
    from repro.nn import resnet as R

    payload = float(parse_codec("int8").payload_bytes(2048, 10))
    args = dict(trigger=BufferedWindow(8), seed=seed, payload_bytes=payload)
    heap = EventDrivenSimulator(512, profiles="heavy_tail", **args)
    heap.plans(30)
    fleet = FleetSimulator(512, profiles="heavy_tail", **args)
    fleet.plans(30)
    keys = ("uplink_bytes", "wasted_uplink_bytes")
    parity = all(heap.stats[k] == fleet.stats[k] for k in keys)

    # Region→core snapshots are parameters, not logits: charge one float32
    # per weight of the CPU-scale MLP the frontiers train.
    params = R.mlp_init(jax.random.key(0), 32, 64, 10, 2)
    core_payload = float(sum(4 * int(np.prod(np.shape(l)))
                             for l in jax.tree.leaves(params)))
    hier = HierarchicalFleetSimulator(
        512, 16, "uniform", region_trigger=BufferedWindow(8),
        core_trigger=BufferedWindow(4), seed=seed,
        payload_bytes=payload, core_payload_bytes=core_payload)
    hier.plans(10)
    hs = hier.stats
    split_ok = (hs["uplink_bytes"]
                == hs["edge_uplink_bytes"] + hs["core_uplink_bytes"]
                and sum(hs["region_uplink_bytes"]) == hs["uplink_bytes"])
    print(f"# sim accounting: heap==fleet {parity}, hier split {split_ok} "
          f"({hs['uplink_bytes'] / 1e6:.1f} MB over "
          f"{hs['regions']} regions)", flush=True)
    return {
        "payload_bytes_per_teacher": payload,
        "heap_fleet_bit_identical": parity,
        "heap_stats": {k: heap.stats[k] for k in keys},
        "fleet_stats": {k: fleet.stats[k] for k in keys},
        "hierarchical": {
            "core_payload_bytes": core_payload,
            "edge_uplink_bytes": hs["edge_uplink_bytes"],
            "core_uplink_bytes": hs["core_uplink_bytes"],
            "uplink_bytes": hs["uplink_bytes"],
            "region_uplink_bytes": list(hs["region_uplink_bytes"]),
            "split_consistent": split_ok,
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes — CI wiring check, not a benchmark")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--edges", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    rounds = args.rounds or (2 if args.smoke else 5)
    edges = args.edges or (4 if args.smoke else 5)
    epochs = (3, 3, 2) if args.smoke else (8, 8, 4)

    scenarios = {}
    for name in SCENARIO_NAMES:
        scenarios[name] = bench_frontier(name, rounds=rounds, num_edges=edges,
                                         epochs=epochs, seed=args.seed)
    sim_accounting = bench_sim_accounting(seed=args.seed)

    report = {
        "config": {"smoke": args.smoke, "rounds": rounds, "edges": edges,
                   "seed": args.seed, "method": METHOD,
                   "codecs": list(CODEC_SPECS)},
        "scenarios": scenarios,
        "sim_accounting": sim_accounting,
    }
    doc = json.dumps(report, indent=2)
    print(doc)
    if args.out:
        with open(args.out, "w") as f:
            f.write(doc + "\n")

    ok = all(np.isfinite(p["final_acc"])
             for s in scenarios.values() for p in s["frontier"])
    # Acceptance: identity is bit-for-bit the no-transport run, every lossy
    # codec actually compresses, and the simulators agree on bytes.
    ok &= all(s["identity_bit_for_bit"] for s in scenarios.values())
    for s in scenarios.values():
        by = {p["codec"]: p["uplink_bytes"] for p in s["frontier"]}
        ok &= by["int4"] < by["int8"] < by["identity"]
        ok &= by["entropy:0.5+int8"] <= by["int8"] + by["identity"] // 4
    ok &= sim_accounting["heap_fleet_bit_identical"]
    ok &= sim_accounting["hierarchical"]["split_consistent"]
    return 0 if ok else 1


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
