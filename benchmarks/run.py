"""Benchmark entrypoint: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the harness contract, then the
roofline summary if dry-run dumps exist.

    PYTHONPATH=src python -m benchmarks.run [--fast]
"""

from __future__ import annotations

import os
import sys


def main() -> None:
    fast = "--fast" in sys.argv
    rounds = 3 if fast else 5
    from benchmarks import fig4_main, fig5_forget, fig7_aggregation, \
        fig9_straggler

    print("# fig4 — main R=1 comparison (paper Fig. 4)")
    _, c4 = fig4_main.main(rounds=rounds)
    print("# fig5/6 — forgetting metrics (paper Figs. 5-6, supp. 1)")
    _, c5 = fig5_forget.main(rounds=rounds)
    print("# fig7 — lightweight aggregation R=2 (paper Fig. 7)")
    _, c7 = fig7_aggregation.main(rounds=max(rounds - 1, 2))
    print("# fig9/11 — straggler robustness (paper Figs. 9 & 11)")
    _, c9 = fig9_straggler.main(rounds=rounds + 1)

    if os.path.isdir("experiments/dryrun"):
        print("# roofline — from the multi-pod dry-run (EXPERIMENTS.md §Roofline)")
        from benchmarks import roofline
        roofline.main(["--mesh", "16x16"])

    all_checks = {**c4, **c5, **c7, **c9}
    failed = [k for k, v in all_checks.items() if not v]
    print(f"# claim-checks: {sum(all_checks.values())}/{len(all_checks)} passed"
          + (f"  FAILED: {failed}" if failed else ""))


if __name__ == "__main__":
    main()
