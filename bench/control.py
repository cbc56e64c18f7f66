"""Runs a cell with a fault or control planted under its timed path.

    python3 bench/control.py --workload <cell> --plant <name> \\
        --seeds 1 2 3 --seconds <s>

All seeds run in this one process (set-up is paid once per seed).  For
each seed it prints one JSON line with ``correct`` and the compared
numbers; a planted fault must read ``correct: false``.  The
benchmark's own runs never plant anything.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import faults, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plant", required=True, choices=sorted(faults.PLANTS))
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    plant = faults.PLANTS[args.plant]
    for seed in args.seeds:
        with plant():
            result = harness.run_cell(args.workload, seed, args.seconds,
                                      False)
        print(json.dumps({"workload": args.workload, "plant": args.plant,
                          "seed": seed, "correct": result["correct"],
                          "attempted": result["attempted"],
                          "failed": result["failed"],
                          "metrics": result["metrics"],
                          "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
