"""End-to-end distributed FL-distillation driver (Algorithm 1 at LLM scale).

Runs the paper's protocol with transformer cores/edges on a jax mesh:
Phase 0 pre-trains the core on the core token silo, each round fine-tunes an
edge replica on its domain silo (Phase 1) and distills it back into the core
with buffered KD (Phase 2) using the pjit step functions from steps.py.

On this CPU container it runs reduced (--arch <id> uses the smoke config by
default); on TPU the same driver scales by passing --full and a real mesh.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --rounds 2 --edges 2 --steps-per-phase 30 --method bkd
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.core import distill
from repro.core.methods import method_names, resolve_method, validate_backend
from repro.core.scheduler import (ASYNC_SCENARIOS, FROZEN, HIER_SCENARIOS,
                                  SCENARIOS, build_scenario,
                                  max_retained_staleness)
from repro.core.simulator import (DistillOnArrival, EventDrivenSimulator,
                                  PROFILE_FAMILIES)
from repro.data import make_token_stream
from repro.launch import specs as S
from repro.launch import steps as St
from repro.launch.mesh import make_device_mesh, make_test_mesh
from repro.models.transformer import Transformer
from repro.optim import adamw
from repro.transport import codec_names, parse_codec


def lm_batches(tokens, batch, seq, steps, seed=0):
    rng = np.random.default_rng(seed)
    n = len(tokens)
    for _ in range(steps):
        sel = rng.integers(0, n, size=batch)
        chunk = tokens[sel, : seq + 1]
        yield {"tokens": jnp.asarray(chunk[:, :-1]),
               "labels": jnp.asarray(chunk[:, 1:])}


def eval_nll(cfg, params, tokens, batch, seq, mesh, n_batches=4, seed=1):
    from repro.core import distill
    apply_fn = jax.jit(Transformer.apply, static_argnums=0)
    tot = jnp.zeros(())
    with jax.set_mesh(mesh):
        for b in lm_batches(tokens, batch, seq, n_batches, seed):
            logits, _ = apply_fn(cfg, params, {"tokens": b["tokens"]})
            tot = tot + distill.ce_loss(logits, b["labels"],
                                        vocab=cfg.vocab_size)
    return float(tot) / n_batches


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--full", action="store_true",
                    help="use the full production config (TPU scale)")
    ap.add_argument("--method", default="bkd", choices=list(method_names()),
                    help="FL method, resolved through the DistillMethod "
                         "registry (repro/core/methods.py)")
    ap.add_argument("--loss-backend", default="auto",
                    choices=["auto", "jnp", "pallas", "topk_cached"],
                    help="Phase-2 KD loss implementation: jnp reference, "
                         "fused Pallas kernel (interpret mode off TPU), or "
                         "top-k compressed logit transfer (topk_cached maps "
                         "to distill.topk_kl with --cache-topk entries); "
                         "validated against the method's declared backends")
    ap.add_argument("--cache-topk", type=int, default=64,
                    help="k for --loss-backend topk_cached")
    ap.add_argument("--transport", default="none",
                    help="uplink codec for the teacher logits (see "
                         "docs/transport.md): 'none', or a spec of at most "
                         "one transform and one filter joined by '+', e.g. "
                         "'int8' or 'entropy:0.5+topk:16'; registered "
                         f"heads: {', '.join(codec_names())}")
    ap.add_argument("--ema-decay", type=float, default=0.9,
                    help="shadow decay for --method ema")
    ap.add_argument("--kd-epochs", type=int, default=2,
                    help="Phase-2 'epoch' segments for --method melting: "
                         "the buffer re-clones at each segment start (the "
                         "CPU engine re-clones per epoch; re-cloning every "
                         "step would zero the buffer KL term exactly)")
    ap.add_argument("--scenario", default="none", choices=sorted(SCENARIOS),
                    help="round-scheduling policy (see docs/scenarios.md); "
                         "the async_* names run the event-driven simulator "
                         "with distill-on-arrival (equivalent to --sim)")
    ap.add_argument("--sim", default="sync",
                    help="'sync' (RoundScheduler via --scenario), "
                         "'async:<profile>' — event-driven virtual-clock "
                         "simulation over heterogeneous device profiles "
                         f"({'|'.join(PROFILE_FAMILIES)}); staleness is "
                         "emergent from the timeline, not scripted — or "
                         "'fleet:<profile>': the same timeline from the "
                         "vectorized FleetSimulator (plan-for-plan "
                         "identical, scales to 100k+ edges)")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--edges", type=int, default=2)
    ap.add_argument("--steps-per-phase", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--tau", type=float, default=2.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    # Method/backend compatibility is rejected here, at argparse time, from
    # the method's declared capabilities — not deep inside the engine.
    meth = resolve_method(args.method)
    if not meth.llm_driver:
        ap.error(f"--method {args.method} is CPU-scale only "
                 f"({meth.llm_unsupported_reason}); "
                 f"see repro.core.fl.FederatedKD")
    try:
        validate_backend(args.method, args.loss_backend, llm=True)
    except ValueError as e:
        ap.error(str(e))
    codec = None
    if args.transport != "none":
        if meth.llm_averaging:
            ap.error(f"--transport compresses distilled logits; --method "
                     f"{args.method} uplinks parameters (no logit phase)")
        try:
            codec = parse_codec(args.transport)
        except ValueError as e:
            ap.error(str(e))

    cfg = registry.get_config(args.arch) if args.full else registry.get_smoke_config(args.arch)
    if cfg.is_encoder or cfg.is_vlm:
        raise SystemExit("train.py drives token-LM FL; see examples/ for "
                         "encoder/VLM paths")
    mesh = make_device_mesh() if args.full else make_test_mesh(len(jax.devices()))
    print(f"arch={cfg.name} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"method={args.method}")

    # Domain-silo corpora: silo 0 is the core set, 1..K are edges.
    data, domains = make_token_stream(cfg.vocab_size, 256 * (args.edges + 1),
                                      args.seq + 1, num_domains=args.edges + 1,
                                      seed=args.seed)
    silos = [data[domains == d] for d in range(args.edges + 1)]

    opt = adamw(args.lr)
    pre_step = St.make_pretrain_step(cfg, opt, loss_chunk=args.seq)
    backend = args.loss_backend
    topk = None
    if backend == "topk_cached":
        # Compressed logit transfer for the LLM driver: top-k KL against
        # teacher and buffer (the batches are resampled every step, so the
        # compression lives in the loss rather than a precomputed cache).
        backend, topk = "jnp", min(args.cache_topk, cfg.vocab_size - 1)
    # Phase-2 wiring comes from the method's declared LLM hints: buffer
    # cloning ("clone"/"remelt"), CE weight (FedDF: 0), EMA shadow,
    # parameter averaging.  fedavg runs no gradient phase at all.
    p2_step = None
    if not meth.llm_averaging:
        # The transport codec is a pure value map on the teacher's chunk
        # logits, applied inside the traced loss — the student distills what
        # the uplink delivered (docs/transport.md).
        transform = (None if codec is None else
                     (lambda lt, ls: codec.roundtrip(lt, student=ls)))
        p2_step = St.make_phase2_step(
            cfg, opt, tau=args.tau,
            buffer_mode="none" if meth.llm_buffer == "none" else "clone",
            loss_chunk=args.seq, topk=topk, loss_backend=backend,
            ce_weight=meth.llm_ce_weight, teacher_transform=transform)
    # Uplink accounting: one Phase-2 pass distills steps * batch * seq token
    # rows of teacher logits.  Filter codecs are charged the all-kept upper
    # bound here — the streamed driver resamples batches every step, so the
    # exact kept count is data-dependent (the CPU engine logs it exactly).
    payload_bytes = 0.0
    if codec is not None:
        kd_rows = args.steps_per_phase * args.batch * args.seq
        payload_bytes = float(codec.payload_bytes(kd_rows, cfg.vocab_size))
    # Plan source: synchronous RoundScheduler, the event-driven async
    # simulator (--sim async:<profile>, or an async_* scenario name), or its
    # vectorized fleet-scale twin (--sim fleet:<profile>).  This driver
    # distills one teacher per round, so the simulated paths always use the
    # distill-on-arrival trigger (R = 1 per consumption).
    if args.scenario in HIER_SCENARIOS:
        # Two-level streams interleave region- and core-level plans; this
        # flat R=1 driver cannot consume them.
        ap.error(f"--scenario {args.scenario} emits a two-level region/core "
                 f"plan stream; drive it through the CPU orchestrator "
                 f"instead: python -m benchmarks.scenarios --scenario "
                 f"{args.scenario}")
    profile, sim_kind = None, None
    if args.sim != "sync":
        sim_kind, _, profile = args.sim.partition(":")
        if sim_kind not in ("async", "fleet") or not profile:
            ap.error(f"--sim must be 'sync', 'async:<profile>' or "
                     f"'fleet:<profile>', got {args.sim!r}")
        if args.scenario != "none":
            # Refuse rather than silently dropping the scenario: the
            # simulator replaces the RoundScheduler entirely.
            ap.error(f"--sim {args.sim} conflicts with --scenario "
                     f"{args.scenario}: the event-driven simulator replaces "
                     f"the scenario's RoundScheduler")
    elif args.scenario in ASYNC_SCENARIOS:
        profile, sim_kind = args.scenario[len("async_"):], "async"
    if profile is not None:
        if sim_kind == "fleet":
            from repro.core.fleet import FleetSimulator
            sim_cls = FleetSimulator
        else:
            sim_cls = EventDrivenSimulator
        source = sim_cls(args.edges, profiles=profile,
                         trigger=DistillOnArrival(), seed=args.seed,
                         payload_bytes=payload_bytes)
        print(f"{sim_kind} simulator: profiles={profile}, distill-on-arrival")
    else:
        source = build_scenario(args.scenario, num_edges=args.edges,
                                seed=args.seed)

    with jax.set_mesh(mesh):
        params, _ = Transformer.init(cfg, jax.random.key(args.seed))
        opt_state = opt.init(params)
        jit_pre = jax.jit(pre_step, donate_argnums=(0, 1))
        jit_p2 = (jax.jit(p2_step, donate_argnums=(0, 3))
                  if p2_step is not None else None)

        # Phase 0: core pre-training.
        t0 = time.time()
        i = 0
        for batch in lm_batches(silos[0], args.batch, args.seq,
                                args.steps_per_phase, args.seed):
            params, opt_state, m = jit_pre(params, opt_state, batch, jnp.int32(i))
            i += 1
        print(f"[phase0] loss={float(m['loss']):.4f} ({time.time()-t0:.1f}s)")

        # Round scheduling: one driver over the plan stream (synchronous
        # scheduler plans, or simulator plans with emergent staleness — the
        # stream decides which weights each edge starts from).
        w0 = jax.tree.map(jnp.copy, params)
        plans = list(source.plans(args.rounds))
        keep = 1 + max_retained_staleness(plans)
        core_log = []
        uplink_total = 0.0
        for plan in plans:
            r = plan.round_idx
            if keep > 1:
                # jit_p2 donates `params`, so stale-weight plans need a
                # copy of each round's starting core (bounded ring buffer).
                core_log = (core_log + [jax.tree.map(jnp.copy, params)])[-keep:]
            task = plan.tasks[0]          # the LLM driver distills R=1 per round
            edge = 1 + (task.edge_id % args.edges)  # silo 0 is the core set
            if task.staleness == FROZEN:
                src = w0
            elif task.staleness == 0:
                src = params
            else:
                src = core_log[max(len(core_log) - 1 - task.staleness, 0)]

            # Phase 1: edge fine-tune from the scheduled starting weights.
            teacher = jax.tree.map(jnp.copy, src)
            t_opt = opt.init(teacher)
            for j, batch in enumerate(lm_batches(silos[edge], args.batch, args.seq,
                                                 args.steps_per_phase,
                                                 args.seed + 31 * r)):
                teacher, t_opt, m = jit_pre(teacher, t_opt, batch, jnp.int32(j))
            stale = ("" if not task.stale else
                     " stale=w0" if task.staleness == FROZEN else
                     f" stale={task.staleness}")
            tinfo = (f" t={plan.time:.2f}" if getattr(plan, "trigger", "")
                     else "")
            print(f"[round {r}] edge {edge} trained{stale}{tinfo}, "  # reprolint: disable=R002 (one log sync per round)
                  f"loss={float(m['loss']):.4f}")

            if plan.withdraw:
                print(f"[round {r}] straggler round withdrawn (no distillation)")
                continue
            # One teacher's logits cross the uplink per distilled round
            # (simulator plans carry the same figure in plan.uplink_bytes).
            uplink_total += payload_bytes

            if meth.llm_averaging:
                # fedavg: the "distill" phase is parameter averaging (the
                # round's R=1 weighted average is the teacher itself).
                params = jax.tree.map(jnp.copy, teacher)
                print(f"[round {r}] aggregated ({args.method}): "
                      f"core <- average of round teachers")
                continue

            # Phase 2: distillation into the core over the core silo, wired
            # per the method's LLM hints.
            if meth.llm_init_from_avg:
                # FedDF: student starts from the teacher parameter average.
                params = jax.tree.map(jnp.copy, teacher)
            buffer_params = (jax.tree.map(jnp.copy, params)  # frozen clone
                             if meth.llm_buffer != "none" else teacher)
            ema = jax.tree.map(jnp.copy, params) if meth.llm_ema else None
            opt_state = opt.init(params)
            # Melting's streaming analogue of "re-clone per epoch": split the
            # phase into --kd-epochs segments and re-clone at each segment
            # start.  (Re-cloning before every step would make the buffer KL
            # identically zero — value and gradient — i.e. exactly plain KD.)
            remelt_every = max(args.steps_per_phase // max(args.kd_epochs, 1),
                               1)
            for j, batch in enumerate(lm_batches(silos[0], args.batch, args.seq,
                                                 args.steps_per_phase,
                                                 args.seed + 77 * r)):
                if meth.llm_buffer == "remelt" and j % remelt_every == 0 and j:
                    buffer_params = jax.tree.map(jnp.copy, params)
                params, opt_state, m = jit_p2(params, teacher, buffer_params,
                                              opt_state, batch, jnp.int32(j))
                if meth.llm_ema:
                    ema = distill.ema_update(ema, params, args.ema_decay)
            if meth.llm_ema:
                params = ema
            print(f"[round {r}] distilled ({args.method}), "  # reprolint: disable=R002 (one log sync per round)
                  f"loss={float(m['loss']):.4f} kd={float(m['kd_loss']):.4f}")

    if codec is not None:
        ident = 4.0 * cfg.vocab_size * args.steps_per_phase * args.batch * args.seq
        print(f"transport={codec.spec}: uplink {uplink_total / 1e6:.3f} MB "
              f"total ({payload_bytes / 1e6:.3f} MB/teacher, "
              f"{ident / max(payload_bytes, 1.0):.1f}x vs raw float32)")
    nll = eval_nll(cfg, params, silos[1], args.batch, args.seq, mesh)
    print(f"final core NLL on edge-1 domain: {nll:.4f}")
    return params


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
