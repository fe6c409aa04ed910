"""HLO inspection toolkit — the instruments behind the §Perf hillclimbs.

    PYTHONPATH=src python -m benchmarks.hlo_tools --arch nemotron-4-340b \
        --shape train_4k [--layers 2] [--top 15] [--collectives]

Compiles a small unrolled probe of the given combo on the production mesh
and prints (a) an op-kind histogram by result bytes, (b) the largest
collective ops with shapes and replica-group axes, (c) dtype mix of the
all-reduce traffic.  These reports are how the fragment-reshard, the
batch-replication and the f32-promotion findings in EXPERIMENTS.md §Perf
were localized.
"""

import os
# A CPU compile tool, like repro.launch.dryrun: host platform, 512 devices.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse
import collections
import re


def op_histogram(hlo_text, top=15):
    from repro.launch.dryrun import _SHAPE_RE, _shape_bytes
    sizes = collections.Counter()
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        rhs = ls.split("=", 1)[1].strip()
        m = _SHAPE_RE.match(rhs.lstrip("("))
        if not m:
            continue
        opm = re.search(r"\)?\s*([a-z0-9-]+)\(", rhs)
        op = opm.group(1) if opm else "?"
        sizes[op] += _shape_bytes(m)
    return sizes.most_common(top)


def biggest_collectives(hlo_text, top=10):
    from repro.launch.dryrun import _COLLECTIVES, _SHAPE_RE, _shape_bytes
    rows = []
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls or not any(f"{k}(" in ls for k in _COLLECTIVES):
            continue
        m = _SHAPE_RE.search(ls.split("=", 1)[1])
        if not m:
            continue
        kind = next(k for k in _COLLECTIVES if f"{k}(" in ls)
        promoted = "_promoted" in ls
        rows.append((_shape_bytes(m), kind, m.group(0), promoted))
    rows.sort(reverse=True)
    agg = collections.Counter()
    for b, kind, shape, promoted in rows:
        agg[(kind, shape, promoted)] += 1
    out = []
    for (kind, shape, promoted), n in agg.most_common(top):
        b = _shape_bytes(_SHAPE_RE.search(shape))
        out.append((n, kind, shape, b, promoted))
    out.sort(key=lambda r: -r[0] * r[3])
    return out[:top]


def ar_dtype_mix(hlo_text):
    from repro.launch.dryrun import _SHAPE_RE, _shape_bytes
    agg = collections.Counter()
    for line in hlo_text.splitlines():
        if "all-reduce(" in line and "=" in line:
            m = _SHAPE_RE.search(line.split("=", 1)[1])
            if m:
                agg[m.group(1)] += _shape_bytes(m)
    return dict(agg)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--buffer-mode", default="clone")
    args = ap.parse_args()

    import jax
    from repro.configs import registry
    from repro.launch.dryrun import build_combo
    from repro.launch.mesh import make_production_mesh

    pat = len(registry.get_config(args.arch).block_pattern)
    layers = args.layers or pat
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    fn, fargs = build_combo(args.arch, args.shape, mesh, args.buffer_mode, None,
                            dict(num_layers=layers, unroll=True))
    with jax.set_mesh(mesh):
        txt = fn.lower(*fargs).compile().as_text()

    print(f"== op histogram (result bytes, {layers}-layer probe) ==")
    for op, b in op_histogram(txt, args.top):
        print(f"  {op:28s} {b/1e9:10.2f} GB")
    print("== largest collectives ==")
    for n, kind, shape, b, promoted in biggest_collectives(txt, args.top):
        star = " [f32-promoted: bf16 on TPU]" if promoted else ""
        print(f"  {n:4d} x {kind:18s} {shape:32s} {n*b/1e9:8.2f} GB{star}")
    print("== all-reduce dtype mix ==")
    for dt, b in ar_dtype_mix(txt).items():
        print(f"  {dt:6s} {b/1e9:10.2f} GB")


if __name__ == "__main__":
    main()
