"""Phase-2 distillation throughput per execution path / loss backend.

Times `DistillEngine.run` (one full set of KD epochs, bkd method) at the
default CIFAR-shaped config for:

    python_loop       the seed's per-batch path: scan=False, jnp losses, and
                      a fresh engine per round (the seed rebuilt the
                      optimizer and re-traced the jitted KD step inside
                      every distill() call — that cost is part of the loop)
    python_loop_warm  scan=False with the step executable cached across
                      rounds (this PR's escape hatch)
    scan_jnp          jitted lax.scan epochs, jnp losses (default on CPU)
    scan_pallas       scan epochs + fused Pallas KD kernel (interpret mode
                      off TPU — correctness-priced on CPU, fused on TPU)
    scan_topk_cached  scan epochs, bkd_cached with the top-k compressed
                      logit cache

and checks the `bkd_cached` accuracy contract: a short FL run with the
compressed cache must land within 0.5pt of the exact cache.  Output is one
JSON document (stdout, plus --out FILE).

`--all-methods` switches to the registry-completeness mode: every method in
the DistillMethod registry (repro/core/methods.py) runs one full round
end-to-end through `FederatedKD` at toy scale and has its Phase-2 timed, so
the bench trajectory tracks per-method overhead and a method that breaks
the round-trip fails CI (which runs `--smoke --all-methods`).

    PYTHONPATH=src python benchmarks/phase2_bench.py [--smoke] [--out f.json]
    PYTHONPATH=src python benchmarks/phase2_bench.py --smoke --all-methods
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distill_engine import DistillEngine
from repro.core.fl import FederatedKD, FLConfig, mlp_adapter
from repro.data import (Dataset, dirichlet_partition, make_cifar_like,
                        make_synthetic_classification)


def cifar_shaped(smoke):
    """CIFAR-shaped Phase-2 workload: 32x32x3 inputs, 10 classes, batch 128."""
    n = 512 if smoke else 2048
    x, y = make_cifar_like(num_classes=10, n=n, seed=0)
    core = Dataset(x.reshape(n, -1), y)
    adapter = mlp_adapter(core.x.shape[-1], 128, 10)
    cfg_kw = dict(batch_size=128, kd_epochs=1 if smoke else 3, seed=0)
    return adapter, core, cfg_kw


def time_variant(adapter, core, cfg_kw, *, scan, method="bkd",
                 loss_backend="jnp", repeats, cold_per_round=False):
    cfg = FLConfig(method=method, scan=scan, loss_backend=loss_backend,
                   cache_topk=8, **cfg_kw)
    state = adapter.init(jax.random.key(0))
    teacher = adapter.init(jax.random.key(1))
    steps = max(len(core) // cfg.batch_size, 1) * cfg.kd_epochs

    def one_round(engine, r):
        out = engine.run(state, [teacher], r)
        jax.block_until_ready(jax.tree.leaves(out))

    engine = DistillEngine(adapter, cfg, core)
    if not cold_per_round:
        one_round(engine, 0)                         # compile + warm cache
    t0 = time.perf_counter()
    for r in range(1, repeats + 1):
        if cold_per_round:
            # Seed semantics: every round re-built its optimizer and
            # re-traced the per-batch jitted step.
            engine = DistillEngine(adapter, cfg, core)
        one_round(engine, r)
    dt = time.perf_counter() - t0
    return {"steps_per_sec": round(repeats * steps / dt, 2),
            "total_steps": repeats * steps, "seconds": round(dt, 4)}


def accuracy_contract(smoke):
    """bkd_cached end-to-end: top-k compressed cache vs exact cache."""
    x, y = make_synthetic_classification(num_classes=10, dim=32, per_class=120,
                                         seed=0)
    xt, yt, xtr, ytr = x[:300], y[:300], x[300:], y[300:]
    parts = dirichlet_partition(ytr, 4, alpha=0.5, seed=1)
    core = Dataset(xtr[parts[0]], ytr[parts[0]])
    edges = [Dataset(xtr[p], ytr[p]) for p in parts[1:]]
    test = Dataset(xt, yt)
    adapter = mlp_adapter(32, 64, 10)
    ep = 2 if smoke else 6
    accs = {}
    for backend in ("jnp", "topk_cached"):
        cfg = FLConfig(num_edges=3, rounds=1 if smoke else 3,
                       method="bkd_cached", loss_backend=backend, cache_topk=8,
                       core_epochs=ep, edge_epochs=ep, kd_epochs=max(ep // 2, 1),
                       batch_size=64, seed=0)
        fl = FederatedKD(adapter, cfg, core, edges, test)
        _, hist = fl.run(jax.random.key(0), log=None)
        accs[backend] = hist[-1]["test_acc"]
    return {"exact_cache_acc": accs["jnp"],
            "topk_cached_acc": accs["topk_cached"],
            "abs_delta": round(abs(accs["jnp"] - accs["topk_cached"]), 4)}


def _method_setup(smoke):
    """Toy FL setup shared by the per-method round-trips."""
    x, y = make_synthetic_classification(num_classes=10, dim=32,
                                         per_class=60 if smoke else 120,
                                         seed=0)
    n_test = 150
    xt, yt, xtr, ytr = x[:n_test], y[:n_test], x[n_test:], y[n_test:]
    parts = dirichlet_partition(ytr, 4, alpha=0.5, seed=1)
    core = Dataset(xtr[parts[0]], ytr[parts[0]])
    edges = [Dataset(xtr[p], ytr[p]) for p in parts[1:]]
    return mlp_adapter(32, 64, 10), core, edges, Dataset(xt, yt)


def all_methods_report(smoke, repeats):
    """Registry completeness: every registered method (a) round-trips
    through FederatedKD for one round and (b) has its Phase-2 timed.
    `steps_per_sec` is null for full-round methods (fedavg runs no gradient
    steps — its `seconds` is the averaging wall time)."""
    from repro.core.methods import method_names, resolve_method

    adapter, core, edges, test = _method_setup(smoke)
    ep = 2 if smoke else 4
    out = {}
    for name in method_names():
        cfg = FLConfig(num_edges=3, rounds=1, method=name, core_epochs=ep,
                       edge_epochs=ep, kd_epochs=max(ep // 2, 1),
                       batch_size=64, seed=0)
        fl = FederatedKD(adapter, cfg, core, edges, test)
        _, hist = fl.run(jax.random.key(0), log=None)
        final_acc = hist[-1]["test_acc"]

        # Phase-2 timing on the same engine (round 0 warms the compile).
        engine = fl.distill_engine
        state = adapter.init(jax.random.key(0))
        teacher = adapter.init(jax.random.key(1))
        steps = max(len(core) // cfg.batch_size, 1) * cfg.kd_epochs
        full_round = resolve_method(name).full_round
        jax.block_until_ready(jax.tree.leaves(
            engine.run(state, [teacher], 0, teacher_weights=[1])))
        t0 = time.perf_counter()
        for r in range(1, repeats + 1):
            jax.block_until_ready(jax.tree.leaves(
                engine.run(state, [teacher], r, teacher_weights=[1])))
        dt = time.perf_counter() - t0
        out[name] = {
            "final_acc": final_acc,
            "steps_per_sec": (None if full_round
                              else round(repeats * steps / dt, 2)),
            "seconds": round(dt, 4),
        }
        print(f"# {name}: acc={final_acc:.3f} "
              f"steps/s={out[name]['steps_per_sec']}", flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes — CI wiring check, not a benchmark")
    ap.add_argument("--all-methods", action="store_true",
                    help="registry completeness: run + time every "
                         "registered DistillMethod for one round")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    repeats = args.repeats or (1 if args.smoke else 3)

    if args.all_methods:
        methods = all_methods_report(args.smoke, repeats)
        report = {
            "config": {"smoke": args.smoke, "repeats": repeats,
                       "backend": jax.default_backend()},
            "methods": methods,
        }
        doc = json.dumps(report, indent=2)
        print(doc)
        if args.out:
            with open(args.out, "w") as f:
                f.write(doc + "\n")
        ok = all(np.isfinite(m["final_acc"]) for m in methods.values())
        return 0 if ok else 1

    adapter, core, cfg_kw = cifar_shaped(args.smoke)
    variants = {
        "python_loop": dict(scan=False, loss_backend="jnp",
                            cold_per_round=True),
        "python_loop_warm": dict(scan=False, loss_backend="jnp"),
        "scan_jnp": dict(scan=True, loss_backend="jnp"),
        "scan_pallas": dict(scan=True, loss_backend="pallas"),
        "scan_topk_cached": dict(scan=True, method="bkd_cached",
                                 loss_backend="topk_cached"),
    }
    throughput = {}
    for name, kw in variants.items():
        throughput[name] = time_variant(adapter, core, cfg_kw,
                                        repeats=repeats, **kw)
        print(f"# {name}: {throughput[name]['steps_per_sec']} steps/s",
              flush=True)

    report = {
        "config": {"smoke": args.smoke, "core_examples": len(core),
                   "input_dim": int(core.x.shape[-1]), "classes": 10,
                   "batch_size": cfg_kw["batch_size"],
                   "kd_epochs": cfg_kw["kd_epochs"], "repeats": repeats,
                   "backend": jax.default_backend()},
        "throughput": throughput,
        "speedup_scan_vs_loop": round(
            throughput["scan_jnp"]["steps_per_sec"]
            / throughput["python_loop"]["steps_per_sec"], 2),
        "bkd_cached_accuracy": accuracy_contract(args.smoke),
    }
    doc = json.dumps(report, indent=2)
    print(doc)
    if args.out:
        with open(args.out, "w") as f:
            f.write(doc + "\n")
    ok = report["speedup_scan_vs_loop"] >= (1.0 if args.smoke else 2.0) \
        and report["bkd_cached_accuracy"]["abs_delta"] <= 0.005 + \
        (0.05 if args.smoke else 0.0)
    return 0 if ok else 1


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
