import os
# A CPU compile tool: pin the host platform (never take an attached chip)
# and add 512 host devices to whatever XLA_FLAGS already holds.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination against ShapeDtypeStruct stand-ins and extract the roofline
terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch kimi-k2-1t-a32b \
        --shape train_4k [--multi-pod] [--buffer-mode clone] [--out out.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all   # every combo

The environment lines above MUST run before any other import (jax locks
the platform and device count on first init); do not set them anywhere
else in the repo.
"""

import argparse
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES
from repro.configs import registry
from repro.launch import specs as S
from repro.launch import steps as St
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.optim import adamw
from repro.sharding.rules import named_sharding

_HLO_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                    "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8,
                    "u64": 8, "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
# Bytes-on-the-wire factor per result byte (ring cost model, documented in
# EXPERIMENTS.md): all-reduce moves ~2x its payload (reduce-scatter +
# all-gather phases); the others ~1x.
_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
           "all-to-all": 1.0, "collective-permute": 1.0}

_SHAPE_RE = re.compile(r"(f32|bf16|f16|f64|s8|u8|s16|u16|s32|u32|s64|u64|pred|"
                       r"f8e4m3fn|f8e5m2)\[([0-9,]*)\]")


def _shape_bytes(m):
    dt, dims = m.group(1), m.group(2)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _HLO_DTYPE_BYTES[dt]


def collective_bytes(hlo_text):
    """Sum per-device wire bytes over collective ops in post-SPMD HLO."""
    total = 0.0
    per_kind = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        rhs = ls.split("=", 1)[1]
        kind = None
        for k in _COLLECTIVES:
            if re.search(rf"\b{k}(-start|-done)?\(", rhs) or \
               re.search(rf"\b{k}(-start)?\.?\d*\(", rhs):
                kind = k
                break
        if kind is None:
            continue
        if f"{kind}-done" in rhs:
            continue  # counted at -start
        m = _SHAPE_RE.search(rhs)  # result shape (per-device)
        if not m:
            continue
        b = _shape_bytes(m) * _FACTOR[kind]
        # CPU-backend legalization promotes bf16 all-reduce accumulation to
        # f32 ("to_apply=%add...promoted" over a convert); real TPUs reduce
        # bf16 on the wire, so count the un-promoted payload.
        if kind == "all-reduce" and "_promoted" in rhs and m.group(1) == "f32":
            b *= 0.5
        total += b
        per_kind[kind] += b
    return total, per_kind


def build_combo(arch, shape_name, mesh, buffer_mode="clone", topk=None,
                overrides=None):
    """Returns (jit_fn, example_args) for one combination — nothing executed."""
    import dataclasses
    cfg = registry.for_shape(arch, shape_name)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    batch = S.input_specs(cfg, shape)
    batch_sh = S.batch_shardings(batch, mesh)
    p_shapes, p_sh = S.params_shardings(cfg, mesh)

    if shape.kind == "train":
        opt = adamw(1e-4)
        opt_shapes = jax.eval_shape(opt.init, p_shapes)
        opt_sh = {k: jax.tree.map(lambda l, s: s, opt_shapes[k], p_sh)
                  for k in opt_shapes}
        step = St.make_phase2_step(cfg, opt, buffer_mode=buffer_mode, topk=topk)
        if buffer_mode == "clone":
            buf_shapes, buf_sh = p_shapes, p_sh
        elif buffer_mode == "cached":
            k = topk or 256
            b, s_ = shape.global_batch, shape.seq_len
            buf_shapes = {
                "top_vals": jax.ShapeDtypeStruct((b, s_, k), jnp.float32),
                "top_idx": jax.ShapeDtypeStruct((b, s_, k), jnp.int32),
                "tail_lse": jax.ShapeDtypeStruct((b, s_), jnp.float32),
            }
            buf_sh = {kk: named_sharding(("batch", None, None)[: len(v.shape)],
                                         v.shape, mesh)
                      for kk, v in buf_shapes.items()}
        else:
            buf_shapes = jax.ShapeDtypeStruct((1,), jnp.float32)
            buf_sh = NamedSharding(mesh, P())
        scalar = jax.ShapeDtypeStruct((), jnp.int32)
        fn = jax.jit(
            step,
            in_shardings=(p_sh, p_sh, buf_sh, opt_sh, batch_sh, NamedSharding(mesh, P())),
            out_shardings=(p_sh, opt_sh, None),
            donate_argnums=(0, 3),
        )
        args = (p_shapes, p_shapes, buf_shapes, opt_shapes, batch, scalar)
        return fn, args

    if shape.kind == "prefill":
        step = St.make_prefill_step(cfg, shape.seq_len)
        fn = jax.jit(step, in_shardings=(p_sh, batch_sh))
        return fn, (p_shapes, batch)

    # decode
    c_shapes, c_sh = S.cache_shardings(cfg, shape.global_batch, shape.seq_len, mesh)
    step = St.make_serve_step(cfg)
    tok = batch["token"]
    tok_sh = named_sharding(("batch", None), tok.shape, mesh)
    fn = jax.jit(step,
                 in_shardings=(p_sh, c_sh, tok_sh, NamedSharding(mesh, P())),
                 donate_argnums=(1,))
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return fn, (p_shapes, c_shapes, tok, pos)


def _compile_and_measure(arch, shape_name, mesh, buffer_mode, topk, overrides):
    t0 = time.time()
    fn, args = build_combo(arch, shape_name, mesh, buffer_mode, topk, overrides)
    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll, per_kind = collective_bytes(compiled.as_text())
    return {
        "mem": mem,
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": coll, "coll_kind": per_kind,
        "t_lower": t_lower, "t_compile": t_compile,
    }


def run_one(arch, shape_name, multi_pod=False, buffer_mode="clone", topk=None,
            overrides=None, verbose=True, probe=True):
    """Full scanned compile (the lowering proof + exact per-device memory)
    plus two unrolled probe compiles (1 and 2 super-blocks) from which
    per-layer flops/bytes/collectives are extrapolated — XLA's cost analysis
    counts while-loop bodies once, so the scanned module undercounts by the
    layer count; the probes fix that with measured (not analytic) numbers."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    shape = SHAPES[shape_name]
    cfg0 = registry.for_shape(arch, shape_name)
    if overrides:
        import dataclasses as _dc
        cfg0 = _dc.replace(cfg0, **overrides)
    full = _compile_and_measure(arch, shape_name, mesh, buffer_mode, topk, overrides)

    pat = len(cfg0.block_pattern)
    if probe:
        ov1 = dict(overrides or {}, num_layers=pat, unroll=True)
        ov2 = dict(overrides or {}, num_layers=2 * pat, unroll=True)
        u1 = _compile_and_measure(arch, shape_name, mesh, buffer_mode, topk, ov1)
        u2 = _compile_and_measure(arch, shape_name, mesh, buffer_mode, topk, ov2)
        eff = cfg0.num_layers / pat  # fractional super-blocks incl. tail

        def extrap(key):
            per = max(u2[key] - u1[key], 0.0)
            return u1[key] + (eff - 1.0) * per

        flops = extrap("flops")
        bytes_acc = extrap("bytes")
        coll = extrap("coll")
        per_kind = {k: u1["coll_kind"][k] + (eff - 1.0) *
                    max(u2["coll_kind"][k] - u1["coll_kind"][k], 0.0)
                    for k in u1["coll_kind"]}
    else:
        flops, bytes_acc, coll = full["flops"], full["bytes"], full["coll"]
        per_kind = full["coll_kind"]

    mem = full["mem"]
    t_lower, t_compile = full["t_lower"], full["t_compile"]
    n_dev = mesh.devices.size

    n_params = S.param_count(cfg0)
    n_active = S.param_count(cfg0, active_only=True)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    fwd_mult = {"train": 10, "prefill": 2, "decode": 2}[shape.kind]
    if shape.kind == "train" and buffer_mode != "clone":
        fwd_mult = 8  # student fwd+bwd (6) + teacher fwd (2); no buffer fwd
    model_flops = fwd_mult * n_active * tokens / n_dev  # per-device

    res = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "buffer_mode": buffer_mode, "topk": topk,
        "devices": n_dev,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "per_device": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.argument_size_in_bytes + mem.temp_size_in_bytes,
            "flops": flops,
            "bytes_accessed": bytes_acc,
            "collective_bytes": coll,
            "collective_by_kind": per_kind,
        },
        "roofline": {
            "compute_s": flops / PEAK_FLOPS_BF16,
            "memory_s": bytes_acc / HBM_BW,
            "collective_s": coll / ICI_BW,
        },
        "model_flops_per_device": model_flops,
        "useful_flops_ratio": model_flops / flops if flops else None,
        "params_total": n_params, "params_active": n_active,
    }
    terms = res["roofline"]
    res["bottleneck"] = max(terms, key=terms.get)
    if verbose:
        print(json.dumps(res, indent=2))
    return res


ALL_DEFAULT_COMBOS = [
    (a, s)
    for a in registry.list_archs()
    for s in SHAPES
    if registry.skip_reason(a, s) is None
]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--buffer-mode", default="clone",
                    choices=["clone", "cached", "none"])
    ap.add_argument("--topk", type=int, default=None)
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override, e.g. num_heads=48 or "
                         "seq_parallel=true (repeatable)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    if args.all:
        results = []
        for a, s in ALL_DEFAULT_COMBOS:
            for mp in (False, True):
                print(f"=== {a} x {s} ({'2x16x16' if mp else '16x16'}) ===",
                      file=sys.stderr)
                results.append(run_one(a, s, mp, args.buffer_mode, args.topk,
                                       verbose=False))
        out = args.out or "dryrun_all.json"
        with open(out, "w") as f:
            json.dump(results, f, indent=2)
        print(f"wrote {out}")
        return

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            import ast
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = {"true": True, "false": False}.get(v.lower(), v)
    res = run_one(args.arch, args.shape, args.multi_pod, args.buffer_mode,
                  args.topk, overrides=overrides or None)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=2)


if __name__ == "__main__":
    main()
