"""Serving CLI — a thin driver over the ``repro.serve`` subsystem.

Builds a named request stream (``--stream``, see ``repro.serve.streams``),
spins up a :class:`~repro.serve.engine.ServeEngine` (per-slot paged decode,
bucketed batched prefill, device-side sampling) and serves the stream to
completion, printing throughput and latency percentiles.  ``--legacy`` runs
the frozen pre-refactor loop instead — the comparison baseline, kept in
``repro.serve.legacy``.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
        --stream poisson --requests 12 --slots 4 [--ring] [--sample topk]
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import registry
from repro.launch.mesh import make_device_mesh, make_test_mesh
from repro.models.transformer import Transformer
from repro.serve import STREAMS, Request, ServeEngine, build_stream
from repro.serve import legacy as legacy_mod
from repro.serve.engine import simulate  # re-export: tests drive this entry

__all__ = ["Request", "ServeEngine", "build_stream", "simulate", "main"]


def _percentile_ms(vals, q):
    """None (not NaN — keeps the JSON strict) when no samples exist, e.g.
    the legacy loop, which never stamps wall-clock lifecycle times."""
    if not vals:
        return None
    return round(float(np.percentile(np.asarray(vals), q)) * 1e3, 3)


def summarize(finished, wall_seconds):
    """Aggregate a finished request list into the bench-facing stats."""
    toks = sum(len(r.out) for r in finished)
    ttfts = [r.ttft for r in finished
             if getattr(r, "t_first", -1) >= 0 and getattr(r, "t_enqueue", -1) >= 0]
    itls = [r.itl for r in finished
            if len(r.out) > 1 and getattr(r, "t_done", -1) >= 0
            and getattr(r, "t_first", -1) >= 0]
    return {
        "requests": len(finished),
        "tokens": toks,
        "seconds": round(wall_seconds, 4),
        "tok_per_sec": round(toks / wall_seconds, 2) if wall_seconds else None,
        "ttft_p50_ms": _percentile_ms(ttfts, 50),
        "ttft_p99_ms": _percentile_ms(ttfts, 99),
        "itl_p50_ms": _percentile_ms(itls, 50),
        "itl_p99_ms": _percentile_ms(itls, 99),
    }


def main(argv=None):
    """Serve one stream; returns ``(finished requests, engine)`` (the
    engine is None under ``--legacy``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=registry.list_archs())
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--stream", default="poisson", choices=sorted(STREAMS),
                    help="named arrival process (repro.serve.streams)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--prompt-max", type=int, default=48)
    ap.add_argument("--out-max", type=int, default=12)
    ap.add_argument("--ring", action="store_true",
                    help="ring-buffer windowed cache (long-context serving)")
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV cache with prefix sharing")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per physical page (with --paged)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many identical system-prompt tokens "
                         "to every request (exercises the prefix cache)")
    ap.add_argument("--sample", default="greedy", choices=("greedy", "topk"))
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=40)
    ap.add_argument("--legacy", action="store_true",
                    help="run the frozen pre-refactor loop (baseline)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = registry.get_config(args.arch) if args.full else registry.get_smoke_config(args.arch)
    if cfg.is_encoder:
        raise SystemExit("encoder-only arch has no decode step")
    if args.ring:
        cfg = dataclasses.replace(cfg, sliding_window=32, ring_cache=True)
    mesh = make_device_mesh() if args.full else make_test_mesh()

    with jax.set_mesh(mesh):
        params, _ = Transformer.init(cfg, jax.random.key(args.seed))
    if args.ring and args.paged:
        raise SystemExit("--ring and --paged are exclusive (the page pool "
                         "replaces the ring buffer)")
    reqs = build_stream(args.stream, args.requests, vocab=cfg.vocab_size,
                        seed=args.seed,
                        prompt_max=min(args.prompt_max, args.max_len - 2
                                       - args.shared_prefix),
                        out_max=args.out_max,
                        shared_prefix=args.shared_prefix)

    t0 = time.perf_counter()
    engine = None
    if args.legacy:
        finished = legacy_mod.simulate(cfg, params, reqs, args.slots,
                                       args.max_len, mesh)
    else:
        with jax.set_mesh(mesh):
            # built inside the mesh scope so the jitted state init shares
            # the step outputs' shardings (one compile per executable)
            engine = ServeEngine(cfg, params, slots=args.slots,
                                 max_len=args.max_len, sample=args.sample,
                                 temperature=args.temperature,
                                 top_k=args.top_k if args.sample == "topk" else 0,
                                 seed=args.seed, paged=args.paged,
                                 page_size=args.page_size)
            finished = engine.run(reqs, log=print)
    stats = summarize(finished, time.perf_counter() - t0)
    cache = "paged" if args.paged else ("ring" if args.ring else "full")
    mode = "legacy" if args.legacy else f"engine[{args.sample}, {cache} cache]"
    print(f"served {stats['requests']}/{args.requests} requests "
          f"({args.stream} stream, {mode}): {stats['tokens']} tokens in "
          f"{stats['seconds']}s = {stats['tok_per_sec']} tok/s; "
          f"TTFT p50/p99 {stats['ttft_p50_ms']}/{stats['ttft_p99_ms']} ms; "
          f"ITL p50/p99 {stats['itl_p50_ms']}/{stats['itl_p99_ms']} ms")
    if args.paged and not args.legacy:
        ps = engine.prefix_stats()
        print(f"paged: {ps['hits']} prefix hits / {ps['misses']} misses, "
              f"peak {ps['peak_pages']} pages "
              f"({engine.resident_cache_bytes()} B resident vs "
              f"{engine.slots * engine.pages_per_slot * engine.cache_page_bytes()}"
              f" B dense-equivalent)")
    return finished, engine


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
