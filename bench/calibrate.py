"""Readings that the limits of ``correct`` are set from, on the chip, at a
cell's own size, in one process:

  program  the numbers a run compares, on each of ``--seeds``
  control  the same numbers with the plain reference computed in float8
           (e4m3) put in the program's place, on each of ``--control-seeds``
  fault    (training cells) the reference with half of each batch left
           out, the mean taken over the rest, on the control seeds

    python3 bench/calibrate.py --workload granite-p2-clone \
        --seeds 1 2 3 --control-seeds 4 5 6 [--seconds 10]

One JSON line per reading.  A serving cell serves ``--seconds`` of its
traffic per seed (its window, untimed here) before the check.  The limits
in the mix files are set from these readings by hand, as PERF.md records;
the benchmark's own runs never run the control.
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, program, traffic  # noqa: E402
from bench.weights import Dims, make_weights  # noqa: E402


def say(**kw):
    print(json.dumps(kw), flush=True)


def serve_readings(cell, dm, seed, control, counter):
    """Serve ``cell.seconds`` of the cell's traffic from ``seed``, then the
    widest logit gap of the program's served tokens and, with
    ``control``, of the float8 reference's on the same positions."""
    from bench.drivers import serve
    mix = cell.mix
    cell.seed = seed
    plan = traffic.plan(mix, seed, cell.seconds, dm.vocab)
    engine = serve.build_engine(cell, dm)
    serve.warm(engine, plan, mix, seed, dm.vocab)
    ledger, _, _, _, finished, inflight = serve.serve_window(
        engine, plan, cell.seconds, harness.Tracer(False), counter)
    serve.drain(engine, inflight, finished, mix["check"]["drain_s"])
    checked = serve.sample(finished, mix["check"], seed, ledger)
    del engine
    gc.collect()
    w = make_weights(dm, seed, serve.TAG_WEIGHTS)
    length = mix["engine"]["max_len"]
    out = {"served": sum(len(r.out) for r in checked)}
    out["program"] = serve.logit_gaps(dm, w, checked, length)
    if control:
        out["control"] = serve.logit_gaps(dm, w, checked, length, fp8=True)
    return out


def correct(checks):
    """``correct`` as a run would report it from these checks."""
    return harness.Outcome(e2e={}, facts={}, attempted=0, failed=0,
                           checks=checks).correct


def serve_cell(cell, dm, seeds, control_seeds):
    from bench.drivers import serve
    counter = harness.CompileCounter()
    for seed in seeds:
        r = serve_readings(cell, dm, seed, seed in control_seeds, counter)
        for kind in ("program", "control"):
            if kind in r:
                say(kind=kind, seed=seed, max_logit_gap=max(r[kind]),
                    correct=correct(serve.checks(r[kind], cell.mix)),
                    per_request=r[kind], served=r["served"])
        gc.collect()


def p2_readings(dm, mix, seed, limits):
    """The control's and the half-batch fault's numbers against the plain
    reference, as a run would compare them."""
    from bench.drivers import p2
    ref = p2.reference_readings(dm, mix, seed)
    ctrl = p2.reference_readings(dm, mix, seed, fp8=True)
    half = p2.reference_readings(dm, mix, seed, rows=mix["batch"] // 2)
    return ({n: v for n, v, _ in p2.compare(ctrl, ref, limits)},
            {n: v for n, v, _ in p2.compare(half, ref, limits)})


def p2_cell(cell, dm, seeds, control_seeds):
    from bench.drivers import p2
    mix = cell.mix
    cfg = program.lm_config(dm, cell.config["name"])
    jitted, opt = p2.build(cfg, mix)
    limits = mix["check"]
    for seed in seeds:
        state, first = p2.first_steps(jitted, opt, cfg, dm, mix, seed)
        del state
        gc.collect()
        prog = p2.program_readings(first, dm, seed)
        del first
        ref = p2.reference_readings(dm, mix, seed)
        said = p2.compare(prog, ref, limits)
        say(kind="program", seed=seed, losses=prog["losses"],
            ref_losses=ref["losses"], correct=correct(said),
            **{n: v for n, v, _ in said})
        del ref
        if seed in control_seeds:
            ctrl, half = p2_readings(dm, mix, seed, limits)
            for kind, got in (("control", ctrl), ("fault_half_batch", half)):
                say(kind=kind, seed=seed, correct=correct(
                    [(n, v, limits[n]) for n, v in got.items()]), **got)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=10)
    a = ap.parse_args(argv)
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    w, c = harness.cell_spec(spec, a.workload)
    peaks = harness.load_json(os.path.join(harness.BENCH, "peaks.json"))
    device = harness.device_facts(w["chips"], peaks)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    config = harness.load_json(os.path.join(ROOT, c["file"]))
    mix = harness.load_json(os.path.join(harness.BENCH, "mixes",
                                         w["traffic"] + ".json"))
    cell = harness.Cell(a.workload, w, config, mix, a.seeds[0], a.seconds,
                        False, device, peaks, [harness.BENCH])
    dm = Dims.from_config(config)
    seeds = list(dict.fromkeys(a.seeds + a.control_seeds))
    run = p2_cell if mix["driver"] == "p2" else serve_cell
    run(cell, dm, seeds, set(a.control_seeds))


if __name__ == "__main__":
    main()
