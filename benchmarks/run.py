"""One run of one cell of the benchmark:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A fresh process each time.  It refuses anything but the chips the cell asks
for, makes inputs and weights from ``--seed``, warms up the cell's own
shapes (set-up), measures for ``--seconds``, checks the timed path against
the plain reference, and prints the contract's one JSON object last.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from benchmarks import harness

    harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace))


if __name__ == "__main__":
    main()
