"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Each run is a new process: it takes the chip, makes the cell's weights and
inputs from the seed, warms up the cell's own shapes (from the persistent
compile cache in ``<checkout>/.jax_cache`` after the first run), measures
for ``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as its last line of standard output.
With ``--trace 1`` the window runs under the profiler and the line carries
the per-layer metrics instead of the end-to-end ones.  A run off a TPU, or
on a device kind missing from ``bench/peaks.json``, fails without a result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        res = harness.run_cell(ROOT, a.workload, a.seed, a.seconds,
                               bool(a.trace), t_start=T_START)
    except harness.HarnessError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    harness.emit(res)
    return 0


if __name__ == "__main__":
    sys.exit(main())
