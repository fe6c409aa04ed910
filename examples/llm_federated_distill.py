"""LLM-scale federated distillation — Algorithm 1 with transformer silos.

The datacenter reading of the paper: N domain-specialist fine-tunes ("edges")
are periodically distilled into one central model ("core") that never sees
the silo data.  Compares plain KD vs buffered KD on the *core* domain after
distilling a foreign-domain specialist — BKD should preserve more of the
core's own-domain quality (less forgetting).

Uses the reduced config of any assigned arch; the same code path scales to
the production mesh via launch/train.py --full.

    PYTHONPATH=src python examples/llm_federated_distill.py --arch granite-3-2b
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.core import distill
from repro.data import make_token_stream
from repro.launch import steps as St
from repro.launch.mesh import make_test_mesh
from repro.models.transformer import Transformer
from repro.optim import adamw


def nll_on(cfg, params, data, batch, seq, n=4, seed=9):
    rng = np.random.default_rng(seed)
    tot = 0.0
    for _ in range(n):
        sel = rng.integers(0, len(data), batch)
        toks = jnp.asarray(data[sel])
        logits, _ = Transformer.apply(cfg, params, {"tokens": toks[:, :-1]})
        tot += float(distill.ce_loss(logits, toks[:, 1:], vocab=cfg.vocab_size))
    return tot / n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    cfg = registry.get_smoke_config(args.arch)
    mesh = make_test_mesh()
    data, domains = make_token_stream(cfg.vocab_size, 512, args.seq + 1,
                                      num_domains=2, seed=0)
    core_silo, edge_silo = data[domains == 0], data[domains == 1]

    opt = adamw(3e-4)
    pre = jax.jit(St.make_pretrain_step(cfg, opt, loss_chunk=args.seq))

    def run_phase(params, silo, steps, seed):
        st = opt.init(params)
        rng = np.random.default_rng(seed)
        for i in range(steps):
            sel = rng.integers(0, len(silo), args.batch)
            toks = jnp.asarray(silo[sel])
            batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
            params, st, m = pre(params, st, batch, jnp.int32(i))
        return params

    with jax.set_mesh(mesh):
        core, _ = Transformer.init(cfg, jax.random.key(0))
        core = run_phase(core, core_silo, args.steps, 1)         # Phase 0
        teacher = run_phase(jax.tree.map(jnp.copy, core),
                            edge_silo, args.steps, 2)            # Phase 1
        base = nll_on(cfg, core, core_silo, args.batch, args.seq)
        print(f"core NLL on own domain before distillation: {base:.4f}")

        for mode in ("none", "clone"):                           # KD vs BKD
            p2 = jax.jit(St.make_phase2_step(cfg, opt, buffer_mode=mode,
                                             loss_chunk=args.seq))
            p = jax.tree.map(jnp.copy, core)
            buf = jax.tree.map(jnp.copy, core)
            st = opt.init(p)
            rng = np.random.default_rng(3)
            for i in range(args.steps):
                sel = rng.integers(0, len(core_silo), args.batch)
                toks = jnp.asarray(core_silo[sel])
                batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
                barg = buf if mode == "clone" else jnp.zeros((1,))
                p, st, m = p2(p, teacher, barg, st, batch, jnp.int32(i))
            own = nll_on(cfg, p, core_silo, args.batch, args.seq)
            other = nll_on(cfg, p, edge_silo, args.batch, args.seq)
            name = "bkd" if mode == "clone" else "kd "
            print(f"{name}: own-domain NLL {own:.4f} (forgetting "
                  f"{own-base:+.4f}), edge-domain NLL {other:.4f}")


if __name__ == "__main__":
    main()
