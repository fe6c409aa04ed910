"""Serving demo — prefill + batched greedy decode for any assigned arch.

Exercises the same serve_step / prefill_step the decode-shape dry-runs
lower, at reduced scale on CPU: prompt -> prefill -> N greedy tokens,
including recurrent-state caches for the SSM/hybrid families.

    PYTHONPATH=src python examples/serve_decode.py --arch mamba2-370m -n 16
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.launch import steps as St
from repro.launch.mesh import make_test_mesh
from repro.models.transformer import Transformer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b",
                    choices=registry.list_archs())
    ap.add_argument("-n", "--new-tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    args = ap.parse_args()

    cfg = registry.get_smoke_config(args.arch)
    if cfg.is_encoder:
        raise SystemExit(f"{args.arch} is encoder-only: no decode step "
                         "(see DESIGN.md shape-coverage policy)")
    mesh = make_test_mesh()
    max_len = args.prompt_len + args.new_tokens + 1

    with jax.set_mesh(mesh):
        params, _ = Transformer.init(cfg, jax.random.key(0))
        prompt = jax.random.randint(jax.random.key(1),
                                    (args.batch, args.prompt_len), 0,
                                    cfg.vocab_size - 1)
        prefill = jax.jit(St.make_prefill_step(cfg, max_len))
        serve = jax.jit(St.make_serve_step(cfg))

        t0 = time.time()
        tok, cache = prefill(params, {"tokens": prompt})
        out = [tok]
        for i in range(args.new_tokens - 1):
            tok, cache = serve(params, cache, tok, jnp.int32(args.prompt_len + i))
            out.append(tok)
        gen = jnp.concatenate(out, axis=1)
        dt = time.time() - t0
    print(f"arch={cfg.name} cache={'recurrent' if 'ssd' in cfg.block_pattern or 'rglru' in cfg.block_pattern else 'kv'}")
    for b in range(args.batch):
        print(f"  seq{b}: {' '.join(str(int(t)) for t in gen[b])}")
    print(f"{args.new_tokens} tokens x {args.batch} seqs in {dt:.2f}s "
          f"({args.new_tokens*args.batch/dt:.1f} tok/s on CPU, reduced config)")


if __name__ == "__main__":
    main()
