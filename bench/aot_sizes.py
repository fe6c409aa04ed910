"""Compile each cell's programs ahead of time for a described TPU v5e (no
chip needed) and print their ``memory_analysis``: the serving cells'
admission at the largest length bucket and prefix depth their traffic
reaches, and their decode tick; the training cells' Phase-2 step.

    JAX_PLATFORMS=cpu python3 bench/aot_sizes.py [--workload W ...] \
        [--slots 8 16]

The slots and max_len of the serving mixes were chosen from this output
(PERF.md records it).  The compiles of a described chip agree with the
chip's own ``memory_analysis`` to the byte.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, program, traffic  # noqa: E402
from bench.weights import Dims  # noqa: E402


def sds(tree, sharding):
    import jax
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def serve_programs(cfg, mix, slots, sharding):
    """(name, jitted, args) of the largest admission and of the tick."""
    import jax
    import jax.numpy as jnp
    from repro.models.transformer import Transformer
    from repro.serve import engine as E
    from bench.drivers import serve
    eng = mix["engine"]
    ml, ps = eng["max_len"], eng["page_size"]
    pp = -(-ml // ps)
    admit, tick = E.make_paged_steps(cfg, ml, E.make_sampler("greedy"), ps)
    state = jax.eval_shape(E.make_paged_init_state(cfg, slots, slots * pp + 1,
                                                   ps, pp))
    tokens, caches, pt, pos, budget, active = sds(state, sharding)
    params = sds(jax.eval_shape(lambda k: Transformer.init(cfg, k)[0],
                                jax.random.key(0)), sharding)
    key = sds(jax.eval_shape(lambda: jax.random.key(0)), sharding)
    plan = traffic.plan(mix, 1, 60, cfg.vocab_size)
    npp, length = max(serve.shapes(plan, mix), key=lambda s: (s[1], s[0]))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
    fill = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=sharding)
    a_args = (params, caches, pt, tokens, pos, budget, active,
              i32(slots, length), i32(slots), i32(slots), fill, key)
    t_args = (params, caches, pt, tokens, pos, budget, active, key)
    return [(f"admit(slots={slots}, bucket={length}, prefix_pages={npp})",
             lambda *a: admit.lower(*a, npp=npp), a_args),
            (f"tick(slots={slots})", tick.lower, t_args)]


def p2_programs(cfg, mix, sharding):
    import jax
    import jax.numpy as jnp
    from repro.models.transformer import Transformer
    from bench.drivers import p2
    jitted, opt = p2.build(cfg, mix)
    params = sds(jax.eval_shape(lambda k: Transformer.init(cfg, k)[0],
                                jax.random.key(0)), sharding)
    opt_state = sds(jax.eval_shape(opt.init, params), sharding)
    b, s = mix["batch"], mix["seq"]
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=sharding)
    args = (params, params, params, opt_state, {"tokens": tok, "labels": tok},
            jax.ShapeDtypeStruct((), jnp.int32, sharding=sharding))
    return [(f"p2_step(batch={b}, seq={s})", jitted.lower, args)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", nargs="*")
    ap.add_argument("--slots", type=int, nargs="*",
                    help="slot counts to try (default: the mix's own)")
    a = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in spec["workloads"]:
        if a.workload and w["name"] not in a.workload:
            continue
        _, c = harness.cell_spec(spec, w["name"])
        dm = Dims.from_config(harness.load_json(os.path.join(ROOT,
                                                             c["file"])))
        cfg = program.lm_config(dm, c["name"])
        mix = harness.load_json(os.path.join(harness.BENCH, "mixes",
                                             w["traffic"] + ".json"))
        if mix["driver"] == "p2":
            progs = p2_programs(cfg, mix, one)
        else:
            progs = []
            for slots in a.slots or [mix["engine"]["slots"]]:
                progs += serve_programs(cfg, mix, slots, one)
        for name, lower, args in progs:
            try:
                ma = lower(*args).compile().memory_analysis()
            except jax.errors.JaxRuntimeError as e:
                msg = str(e).split("\n")[0]
                print(json.dumps({"workload": w["name"], "program": name,
                                  "does_not_fit": msg}), flush=True)
                continue
            total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes)
            print(json.dumps({
                "workload": w["name"], "program": name,
                "argument_bytes": ma.argument_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "total_bytes": total}), flush=True)


if __name__ == "__main__":
    main()
