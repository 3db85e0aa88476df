"""Read the limits' two ends on the chip, in ONE process: for each seed the
program's numbers (what a run compares) and, beside them, the control's.  A
control is the reference computed in the next lower precision and put in the
program's place (``fp8``: read in the same run), or the program's own lower
precision switched on (``kv_int8``, the engine's int8 KV pool: a run of its
own on the same seed).  Not part of a benchmark run; PERF.md records what it
printed.

    python3 benchmarks/control.py --workload <cell> --seeds 11,12,13 \\
        --control-seeds 3 --precision fp8,kv_int8 --seconds 20 \\
        --out chiprun_out/x.json
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: controls that are the program itself with a path switched on
PROGRAM_PATHS = ("kv_int8",)


def read(workload: str, seed: int, seconds: float, control):
    """One run; the numbers of its ``check`` and ``control`` lines."""
    from benchmarks import harness

    line = harness.run_cell(workload, seed, seconds, False, control=control)
    gc.collect()
    extra = ("mean_gap", "tokens_not_top", "served_tokens", "worst_leaves")
    numbers = {rec["phase"]: {
        **{c["number"]: c["value"] for c in rec["compared"]},
        "_also": {k: rec[k] for k in extra if k in rec}}
        for rec in line["log"] if rec.get("phase") in ("check", "control")}
    return line["correct"], numbers


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--precision", default="fp8")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    precisions = args.precision.split(",")
    in_run = [p for p in precisions if p not in PROGRAM_PATHS]
    if len(in_run) > 1:
        raise SystemExit("one reference precision to a call")
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        controlled = i < args.control_seeds
        correct, got = read(args.workload, seed, args.seconds,
                            in_run[0] if controlled and in_run else None)
        row = {"seed": seed, "correct": correct, "check": got["check"],
               "control": {}}
        if "control" in got:
            row["control"][in_run[0]] = got["control"]
        for p in precisions if controlled else ():
            if p in PROGRAM_PATHS:
                low_correct, low = read(args.workload, seed, args.seconds, p)
                row["control"][p] = {**low["check"], "correct": low_correct}
        rows.append(row)
    summary = {"workload": args.workload, "rows": rows}
    for n in sorted(set(rows[0]["check"]) - {"_also"}):
        sound = [r["check"][n] for r in rows]
        summary[n] = {"sound_max": max(sound), "sound": sound}
        for p in precisions:
            low = [r["control"][p][n] for r in rows if p in r["control"]]
            summary[n][p] = {"control_min": min(low) if low else None,
                             "control": low}
    print(json.dumps(summary))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)


if __name__ == "__main__":
    main()
