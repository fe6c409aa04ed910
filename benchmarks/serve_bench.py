"""Serving throughput/latency: engine vs the frozen pre-refactor loop.

Times the same request stream through both serving paths at the smoke
config (reduced arch on CPU; `--full` for the real config on accelerator):

    legacy   the pre-refactor loop (repro.serve.legacy): scalar shared-
             `ptick` decode, one prefill retrace per distinct prompt
             length, one host round-trip per slot per tick
    engine   repro.serve.ServeEngine: per-slot device-resident positions,
             one jitted tick + one host sync per tick, bucketed batched
             prefill (<= log2(max_prompt)+1 prefill executables)

Wall time includes compilation on both sides — bounded tracing IS the
optimization being measured.  (The legacy loop's tokens are additionally
*wrong* on stacked-layer configs — see repro/serve/legacy.py's defect
list — but it executes the same per-tick work, so its throughput remains
the honest baseline.)

A third section measures the block-paged KV cache (`--page-size`):

    paged    ServeEngine(paged=True): one physical page pool + per-slot
             page tables, hash-chained prefix sharing at admission

against two claims the PR-10 acceptance bar sets: (a) resident-cache
bytes at skewed occupancy — short prompts in a long-max_len engine leave
dense slots nearly empty while the pool only holds written pages (gate:
>= 4x reduction); (b) prefix-hit TTFT collapse — on a shared-system-
prompt stream, admissions served from the prefix cache skip the shared
pages' prefill, so their time-to-first-token drops vs the cold misses.

Emits one JSON document (stdout, plus --out FILE): tok/s for both paths,
the speedup, p50/p99 time-to-first-token and inter-token latency for the
engine, per-arrival-process scenario stats (the `STREAMS` registry), the
prefill executable count vs its bucketing bound, and the `paged` section
(per-stream parity + residency + hit/miss TTFT).  CI runs `--smoke` and
uploads BENCH_serve.json, seeding the serving bench trajectory.

    PYTHONPATH=src python benchmarks/serve_bench.py [--smoke] [--out f.json]
"""

from __future__ import annotations

import argparse
import json
import math
import time

import jax
import numpy as np

from repro.configs import registry
from repro.launch.mesh import make_test_mesh
from repro.launch.serve import summarize
from repro.models.transformer import Transformer
from repro.serve import STREAMS, ServeEngine, build_stream
from repro.serve import legacy as legacy_mod
from repro.serve.engine import bucket_length


def run_legacy(cfg, params, reqs, slots, max_len, mesh):
    t0 = time.perf_counter()
    finished = legacy_mod.simulate(cfg, params, reqs, slots, max_len, mesh,
                                   log=lambda *a: None)
    return summarize(finished, time.perf_counter() - t0)


def run_engine(cfg, params, reqs, slots, max_len, mesh, engine=None):
    t0 = time.perf_counter()
    with jax.set_mesh(mesh):
        # construct/reset inside the mesh context: the engine's jitted
        # state init matches the step outputs' shardings only under the
        # same mesh (keeps every executable compiled exactly once)
        if engine is None:
            engine = ServeEngine(cfg, params, slots=slots, max_len=max_len)
        else:
            engine.reset()
        finished = engine.run(reqs, log=None)
    return summarize(finished, time.perf_counter() - t0), engine


def _ttft_ms(reqs):
    vals = [r.ttft for r in reqs if r.t_first >= 0 and r.t_enqueue >= 0]
    return round(float(np.median(vals)) * 1e3, 3) if vals else None


def paged_section(cfg, params, mesh, args, n_req):
    """Dense vs paged: token parity on every named stream, resident-cache
    bytes at skewed occupancy, and prefix-hit vs miss TTFT.

    The residency claim is measured at the fleet shape that motivates
    paging: an engine PROVISIONED for long contexts (4x the headline
    ``--max-len``) serving mostly short requests.  The dense engine holds
    its full ``slots x max_len`` allocation regardless; the pool's peak
    tracks pages actually written."""
    slots, ps = args.slots, args.page_size
    max_len = 4 * args.max_len
    short_max = max(4, ps - 2)     # prompts below one page: the skew
    with jax.set_mesh(mesh):
        dense = ServeEngine(cfg, params, slots=slots, max_len=max_len)
        paged = ServeEngine(cfg, params, slots=slots, max_len=max_len,
                            paged=True, page_size=ps)

        residency, parity_ok = {}, True
        for name in sorted(STREAMS):
            mk = lambda: build_stream(name, n_req, vocab=cfg.vocab_size,
                                      seed=args.seed, prompt_max=short_max,
                                      out_max=args.out_max)
            dense.reset()
            paged.reset()
            want = {r.rid: r.out for r in dense.run(mk(), log=None)}
            got = {r.rid: r.out for r in paged.run(mk(), log=None)}
            parity_ok &= got == want
            d, p = dense.resident_cache_bytes(), paged.resident_cache_bytes()
            residency[name] = {
                "dense_bytes": d, "paged_peak_bytes": p,
                "reduction_x": round(d / p, 2) if p else None,
                "tokens_match": got == want,
            }
            print(f"# paged {name}: {d} -> {p} B "
                  f"({residency[name]['reduction_x']}x), "
                  f"parity={got == want}", flush=True)

        # Prefix-hit vs miss TTFT on a shared-system-prompt stream: hits
        # prefill only the suffix past the shared pages.  Warm-up run
        # first so the lone cold miss isn't charged for compilation.
        shared = 2 * ps
        mk = lambda: build_stream("poisson", n_req, vocab=cfg.vocab_size,
                                  seed=args.seed, shared_prefix=shared,
                                  prompt_max=args.prompt_max,
                                  out_max=args.out_max)
        paged.reset()
        paged.run(mk(), log=None)
        paged.reset()
        finished = paged.run(mk(), log=None)
        hits = [r for r in finished if r.prefix_pages > 0]
        misses = [r for r in finished if r.prefix_pages == 0]
        stats = paged.prefix_stats()
        prefix = {
            "shared_prefix_tokens": shared,
            "hits": stats["hits"], "misses": stats["misses"],
            "evictions": stats["evictions"],
            "ttft_hit_p50_ms": _ttft_ms(hits),
            "ttft_miss_p50_ms": _ttft_ms(misses),
        }
        print(f"# paged prefix: {stats['hits']} hits / {stats['misses']} "
              f"misses, TTFT hit {prefix['ttft_hit_p50_ms']} ms vs miss "
              f"{prefix['ttft_miss_p50_ms']} ms", flush=True)

    worst = min(r["reduction_x"] for r in residency.values()
                if r["reduction_x"])
    return {
        "page_size": ps,
        "skew": {"max_len": max_len, "prompt_max": short_max},
        "residency": residency,
        "worst_reduction_x": worst,
        "prefix": prefix,
        "tokens_match_all_streams": parity_ok,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes — CI wiring check + trajectory seed")
    ap.add_argument("--full", action="store_true",
                    help="full arch config (accelerator)")
    ap.add_argument("--arch", default="granite-3-2b",
                    choices=registry.list_archs())
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--prompt-max", type=int, default=40)
    ap.add_argument("--out-max", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    n_req = args.requests or (12 if args.smoke else 32)

    cfg = registry.get_config(args.arch) if args.full \
        else registry.get_smoke_config(args.arch)
    mesh = make_test_mesh()
    with jax.set_mesh(mesh):
        params, _ = Transformer.init(cfg, jax.random.key(args.seed))

    def stream(name):
        return build_stream(name, n_req, vocab=cfg.vocab_size,
                            seed=args.seed, prompt_max=args.prompt_max,
                            out_max=args.out_max)

    # Headline comparison: cold engine vs cold legacy on the same stream
    # (poisson has many distinct prompt lengths — the legacy loop's
    # per-length retrace worst case is the common case).
    legacy_stats = run_legacy(cfg, params, stream("poisson"), args.slots,
                              args.max_len, mesh)
    print(f"# legacy: {legacy_stats['tok_per_sec']} tok/s", flush=True)
    engine_stats, engine = run_engine(cfg, params, stream("poisson"),
                                      args.slots, args.max_len, mesh)
    print(f"# engine: {engine_stats['tok_per_sec']} tok/s", flush=True)
    speedup = round(engine_stats["tok_per_sec"]
                    / legacy_stats["tok_per_sec"], 2)

    # Scenario sweep on the (now warm) engine: per-arrival-process stats.
    scenarios = {}
    for name in sorted(STREAMS):
        stats, _ = run_engine(cfg, params, stream(name), args.slots,
                              args.max_len, mesh, engine=engine)
        scenarios[name] = stats
        print(f"# stream {name}: {stats['tok_per_sec']} tok/s, "
              f"ttft p99 {stats['ttft_p99_ms']} ms", flush=True)

    paged = paged_section(cfg, params, mesh, args, n_req)

    bound = int(math.log2(bucket_length(args.prompt_max))) + 1
    compiles = engine.prefill_compile_count()
    report = {
        "config": {"smoke": args.smoke, "arch": cfg.name,
                   "requests": n_req, "slots": args.slots,
                   "max_len": args.max_len, "prompt_max": args.prompt_max,
                   "out_max": args.out_max, "seed": args.seed,
                   "page_size": args.page_size,
                   "backend": jax.default_backend()},
        "legacy": legacy_stats,
        "engine": engine_stats,
        "speedup_tok_s": speedup,
        "streams": scenarios,
        "prefill_compiles": {"count": compiles, "bound": bound},
        "paged": paged,
    }
    doc = json.dumps(report, indent=2)
    print(doc)
    if args.out:
        with open(args.out, "w") as f:
            f.write(doc + "\n")
    # CI gate: the engine must beat the legacy loop even at smoke scale
    # (2x is the acceptance bar; 1.5 leaves headroom for runner noise),
    # bucketing must hold its compile bound, and the paged cache must be
    # token-exact on every stream while cutting resident bytes >= 4x.
    ok = (speedup >= (1.5 if args.smoke else 2.0) and compiles <= bound
          and paged["tokens_match_all_streams"]
          and paged["worst_reduction_x"] >= 4.0)
    return 0 if ok else 1


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
