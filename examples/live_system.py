"""Live co-scheduled system demo — serve traffic while FL rounds update it.

The closed loop: one `ServeEngine` decodes a request stream tick by tick
while a `LiveTrainer` advances Phase-2 distillation microbatches on the
same device budget; each completed round hot-swaps the served params
atomically between ticks.  Rounds come from the async event-driven
simulator, so their event times are gated onto the serving clock — a round
only starts once the stream has reached its simulated arrival.

Watch the interleaving in the log: `admit`/`finish` lines from the engine,
`[round NN]` lines from the trainer, `== swap ==` lines when a new core
goes live mid-stream (with the core-domain NLL of the model now serving).

    PYTHONPATH=src python examples/live_system.py --stream diurnal
    PYTHONPATH=src python examples/live_system.py --stream heavy_tail --method kd
"""

import argparse

import jax

from repro.configs import registry
from repro.core.fl import FederatedKD, FLConfig
from repro.core.simulator import EventDrivenSimulator
from repro.launch.mesh import make_test_mesh
from repro.launch.serve import summarize
from repro.live import LiveSystem, LiveTrainer, lm_adapter, lm_fl_data, nll_on
from repro.serve import STREAMS, ServeEngine, build_stream


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b",
                    choices=registry.list_archs())
    ap.add_argument("--stream", default="diurnal", choices=sorted(STREAMS))
    ap.add_argument("--method", default="bkd",
                    help="distillation method for the live rounds")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=32)
    ap.add_argument("--quantum", type=int, default=2,
                    help="distill microbatches per co-scheduler turn")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = registry.get_smoke_config(args.arch)
    if cfg.is_encoder:
        raise SystemExit(f"{args.arch} is encoder-only: nothing to serve")
    core, edges, test, silos = lm_fl_data(cfg, num_edges=2, seq_len=8,
                                          n_seqs=96, seed=args.seed)
    flcfg = FLConfig(num_edges=2, rounds=args.rounds, method=args.method,
                     core_epochs=1, edge_epochs=1, kd_epochs=2, batch_size=8,
                     seed=args.seed)
    reqs = build_stream(args.stream, args.requests, vocab=cfg.vocab_size,
                        seed=args.seed, prompt_max=10, out_max=4)

    mesh = make_test_mesh()
    with jax.set_mesh(mesh):
        fl = FederatedKD(lm_adapter(cfg), flcfg, core, edges, test,
                         scheduler=EventDrivenSimulator(
                             flcfg.num_edges, "uniform", seed=args.seed))
        print(f"# pretraining core ({cfg.name}, {args.method}, "
              f"{args.rounds} rounds)...", flush=True)
        trainer = LiveTrainer(fl, jax.random.key(args.seed), log=print)
        print(f"# core NLL after pretrain: "
              f"{nll_on(cfg, trainer.state, silos['core']):.4f}", flush=True)
        engine = ServeEngine(cfg, trainer.state, slots=args.slots,
                             max_len=args.max_len)
        horizon = max(r.arrival for r in reqs) + 2 * args.requests
        t_last = max(p.time for p in trainer.plans)

        def on_swap(system, rec):
            nll = nll_on(cfg, system.trainer.state, silos["core"])
            rec["eval_nll_core"] = round(nll, 4)
            print(f"== swap == round {rec['round']} live at tick "
                  f"{rec['tick']} (swap #{rec['swap']}, core NLL "
                  f"{nll:.4f})", flush=True)

        system = LiveSystem(trainer, engine, quantum=args.quantum,
                            ticks_per_time=0.6 * horizon / t_last,
                            on_swap=on_swap)
        import time
        t0 = time.perf_counter()
        finished = system.run(reqs, log=print)
        stats = summarize(finished, time.perf_counter() - t0)
    print(f"\nserved {stats['requests']} requests / {stats['tokens']} tokens "
          f"in {stats['seconds']}s across {engine.ticks} ticks; "
          f"{engine.swaps} hot-swaps at ticks {engine.swap_log}")
    print(f"rounds completed: {trainer.rounds_done}/{args.rounds}; "
          f"final core NLL {nll_on(cfg, trainer.state, silos['core']):.4f}")


if __name__ == "__main__":
    main()
