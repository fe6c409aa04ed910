"""Run the full dry-run matrix — or the FL scenario matrix — as parallel
subprocesses.

    PYTHONPATH=src python -m repro.launch.sweep --out experiments/dryrun -j 6
    PYTHONPATH=src python -m repro.launch.sweep --scenarios --out experiments/scenarios -j 2

Default mode: each (arch x shape x mesh) combo runs `repro.launch.dryrun`
in its own process (jax device-count env must be set before init, and
compiles are independent), writing one JSON per combo plus a failures log.

`--scenarios` mode: every named scenario (straggler schedules, random
sampling, partial participation, random delays, and the event-driven
`async_*` simulator scenarios with emergent staleness — see
docs/scenarios.md) runs through the `repro.launch.train` driver, one
subprocess per scenario, writing one log per scenario.  The children run
one at a time (`-j` is ignored) unless `JAX_PLATFORMS=cpu` pins them to the
host: a chip belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor


def combo_list():
    from repro.configs import SHAPES
    from repro.configs import registry
    out = []
    for a in registry.list_archs():
        for s in SHAPES:
            if registry.skip_reason(a, s) is None:
                for mp in (False, True):
                    out.append((a, s, mp))
    return out


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def _run_subprocess(tag, cmd, outdir, save_stdout_to=None):
    """Shared combo runner: subprocess from the repo root with
    PYTHONPATH=src, a .FAILED.log on failure, (tag, status, dt) result."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    t0 = time.time()
    p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       cwd=_repo_root())
    dt = time.time() - t0
    if p.returncode != 0:
        with open(os.path.join(outdir, tag + ".FAILED.log"), "w") as f:
            f.write(p.stdout[-4000:] + "\n==stderr==\n" + p.stderr[-8000:])
        return (tag, "FAILED", dt)
    if save_stdout_to is not None:
        with open(save_stdout_to, "w") as f:
            f.write(p.stdout)
    return (tag, "ok", dt)


def run_combo(arch, shape, multi_pod, outdir, extra=()):
    tag = f"{arch}_{shape}_{'2x16x16' if multi_pod else '16x16'}".replace("/", "-")
    out = os.path.join(outdir, tag + ".json")
    if os.path.exists(out):
        return (tag, "cached", 0.0)
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--out", out, *extra]
    if multi_pod:
        cmd.append("--multi-pod")
    return _run_subprocess(tag, cmd, outdir)


def scenario_list():
    from repro.core.scheduler import SCENARIOS
    return sorted(SCENARIOS)


def run_scenario(name, outdir, rounds, steps, method, loss_backend="auto",
                 transport="none"):
    from repro.core.scheduler import HIER_SCENARIOS
    tag = f"scenario_{name}_{method}"
    if loss_backend != "auto":
        tag += f"_{loss_backend}"
    if transport != "none":
        tag += f"_{transport.replace(':', '').replace('+', '-')}"
    out = os.path.join(outdir, tag + ".log")
    if os.path.exists(out):
        return (tag, "cached", 0.0)
    if name in HIER_SCENARIOS:
        # Two-level region/core streams need the CPU orchestrator (the flat
        # R=1 LLM driver refuses them); loss_backend is a train.py knob.
        cmd = [sys.executable, "-m", "benchmarks.scenarios", "--scenario",
               name, "--method", method, "--rounds", str(rounds),
               "--edges", "6", "--transport", transport]
    else:
        cmd = [sys.executable, "-m", "repro.launch.train", "--scenario", name,
               "--method", method, "--rounds", str(rounds), "--edges", "2",
               "--steps-per-phase", str(steps), "--loss-backend", loss_backend,
               "--transport", transport]
    return _run_subprocess(tag, cmd, outdir, save_stdout_to=out)


def main():
    from repro.core.methods import method_names, resolve_method

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("-j", type=int, default=6)
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--scenarios", action="store_true",
                    help="sweep FL round-scheduling scenarios instead of "
                         "the dry-run matrix")
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--steps-per-phase", type=int, default=10)
    ap.add_argument("--method", default="bkd", choices=list(method_names()),
                    help="FL method (DistillMethod registry name) forwarded "
                         "to repro.launch.train in --scenarios mode")
    ap.add_argument("--loss-backend", default="auto",
                    choices=["auto", "jnp", "pallas", "topk_cached"],
                    help="Phase-2 loss backend forwarded to repro.launch.train"
                         " in --scenarios mode")
    ap.add_argument("--transport", default="none",
                    help="uplink codec spec (repro.transport registry) "
                         "forwarded to the scenario drivers in --scenarios "
                         "mode; see docs/transport.md")
    args = ap.parse_args()
    if args.scenarios and not resolve_method(args.method).llm_driver:
        ap.error(f"--method {args.method} is CPU-scale only; the scenario "
                 f"sweep drives repro.launch.train")
    if args.transport != "none":
        from repro.transport import parse_codec
        try:
            parse_codec(args.transport)
        except ValueError as e:
            ap.error(str(e))
    os.makedirs(args.out, exist_ok=True)
    results = []
    workers = args.j
    if args.scenarios and os.environ.get("JAX_PLATFORMS") != "cpu":
        # Each train child takes the accelerator; a chip holds one process
        # at a time, so unpinned children run one after another.
        workers = 1
    with ThreadPoolExecutor(workers) as ex:
        if args.scenarios:
            names = scenario_list()
            print(f"{len(names)} scenarios -> {args.out} ({workers} workers)")
            futs = [ex.submit(run_scenario, n, args.out, args.rounds,
                              args.steps_per_phase, args.method,
                              args.loss_backend, args.transport)
                    for n in names]
        else:
            combos = combo_list()
            print(f"{len(combos)} combos -> {args.out} ({args.j} workers)")
            futs = [ex.submit(run_combo, a, s, mp, args.out)
                    for a, s, mp in combos]
        for f in futs:
            tag, status, dt = f.result()
            print(f"[{status:6s}] {tag} ({dt:.0f}s)", flush=True)
            results.append((tag, status, dt))
    fails = [r for r in results if r[1] == "FAILED"]
    print(f"done: {len(results) - len(fails)} ok, {len(fails)} failed")
    with open(os.path.join(args.out, "_summary.json"), "w") as f:
        json.dump(results, f, indent=2)
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
