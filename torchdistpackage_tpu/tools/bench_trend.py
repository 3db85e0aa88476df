"""Bench trajectory: compare the ``BENCH_r0*.json`` rounds in a directory.

A driver round is a ``BENCH_r0N.json`` artifact (``{"n", "tail",
"parsed"}`` — the bench harness's stdout tail holds one JSON line per
measured metric).  None is checked in any more (the rounds of 2026-07/08
were deleted with the remote-chip records they carried; ROADMAP keeps their
numbers), so the tool runs on a directory the caller names —

    python -m torchdistpackage_tpu.tools.bench_trend [--dir REPO]
        [--threshold 0.05] [--glob 'BENCH_r*.json']

parses every round, groups the metric lines per series (``metric`` key:
gpt-125m-train-throughput, gpt-1b-train-throughput, ...), prints the
per-round values with round-over-round deltas, and exits NONZERO with a
loud ``REGRESSION`` warning when the newest round lost more than
``--threshold`` (default 5%) against the best earlier round of the same
series.  Stale lines (``"stale": true`` — the accelerator was
unreachable and the harness replayed the last-good record) are shown but
never counted as fresh evidence in either direction.

Deliberately jax-free (a login-node / CI gate tool, like
``slurm_job_monitor``), hence the bare prints.
"""

from __future__ import annotations

import argparse
import glob as _glob
import json
import os
import sys
from typing import Any, Dict, List, Tuple

#: JSON-line keys treated as secondary metrics worth trending alongside
#: the headline value (shown when present; only ``value`` gates).
#: ``grad_norm_final`` is the PR-7 numerics column: a round whose
#: throughput held but whose final grad norm went to 0/NaN measured a
#: run that trained garbage — visible here, next to the tokens/s.
#: ``comm_bytes_per_dim`` (PR 8) is the wire-bytes column: it renders as
#: the TOTAL across dimensions (``comm_bytes=``), so a regression that
#: re-inflates a compressed collective's bytes shows up in the trend next
#: to the throughput it would eventually cost.
#: ``shed_rate`` / ``preempt_count`` (PR 9) ride the ``serve-overload``
#: line: the gate trends overloaded goodput (``value``), and these
#: columns show whether a goodput hold was bought by shedding more —
#: a scheduler regression that the headline alone would hide.
#: ``prefix_hit_rate`` / ``spec_accept_rate`` (PR 10) ride the
#: ``serve-prefix-*`` / ``serve-spec-*`` fast-path A/B lines: a tokens/s
#: hold with a collapsed hit or accept rate means the win is coming from
#: somewhere else (or the workload changed under the gate) — visible
#: here next to the throughput it buys.
#: ``slo_attainment`` / ``goodput_tok_s`` (PR 11) ride the
#: ``serve-overload`` line too: the headline ``value`` is RAW tokens/s,
#: which can hold while every deadline is missed — goodput (tokens/s of
#: deadline-meeting requests only) and attainment are the columns that
#: catch a scheduler trading SLOs for throughput.
#: ``autoplan_tok_s`` / ``plan_modeled_step_s`` (PR 13) ride the
#: ``bench.py --autoplan`` planned arm's line: the planner-chosen plan's
#: measured tokens/s next to its modeled step time — a throughput hold
#: with a drifting model (the planner steering on stale numbers) is
#: visible here before it mis-ranks a real decision.
#: ``bubble_fraction`` / ``plan_pp_schedule`` (PR 14) ride pipeline A/B
#: lines and the ``--autoplan`` planned arm when a pp plan is in play:
#: the schedule's tick-model bubble fraction and which schedule arm
#: (``1f1b`` vs ``zb``) produced the number — a throughput hold whose
#: bubble fraction crept back up (or whose arm silently flipped back to
#: classic 1F1B) is visible next to the tokens/s it costs.
#: ``fleet_goodput_tok_s`` / ``affinity_hit_rate`` / ``migration_bytes``
#: (PR 15) ride the ``serve-router-fleet`` line: the fleet's headline
#: tokens/s gates (``value``), and these columns show HOW it was earned —
#: a throughput hold with a collapsed affinity hit rate means warm
#: traffic stopped landing on its KV (the routing policy rotting), and
#: ballooning migration bytes mean the disaggregation tier started
#: shipping whole contexts instead of tails.
#: ``moe_pallas_tok_s`` / ``expert_imbalance`` (PR 18) ride the
#: ``serve-moe-ab`` line: the fused-dispatch arm's absolute tokens/s
#: next to the run's accumulated expert-load imbalance — a speedup hold
#: earned while imbalance climbs means the router is feeding the kernel
#: ever-more-skewed batches (capacity drops coming), visible before the
#: dropped-token alarm fires.
#: ``autoscale_actions`` / ``migration_retry_count`` /
#: ``transport_fallback_count`` (PR 19) ride the elastic-fleet lines
#: (``trace-replay``, ``serve-router-fleet``): a goodput hold earned
#: with climbing scale actions means the controller is papering over a
#: shrinking steady state (thrash coming); climbing wire retries mean
#: the migration transport is degrading under the SAME fault plan; any
#: nonzero fallback is a re-prefill the fleet paid for — cheap this
#: release and expensive the next is a regression no headline catches.
#: ``cp_prefill_ttft_s`` / ``long_ctx_tok_s`` (PR 20) ride the
#: ``serve-longctx-ab`` line: the CP arm's absolute TTFT at the longest
#: context and its decode tokens/s, next to the gating cp1/cpN speedup
#: — a speedup hold earned while absolute TTFT creeps up means both
#: arms got slower together (a prefill regression the ratio hides).
AUX_KEYS = ("mfu", "mfu_xla", "peak_hbm_bytes", "mem_headroom_frac",
            "grad_norm_final", "comm_bytes_per_dim", "shed_rate",
            "preempt_count", "prefix_hit_rate", "spec_accept_rate",
            "slo_attainment", "goodput_tok_s", "paged_pallas_tok_s",
            "autoplan_tok_s", "plan_modeled_step_s", "bubble_fraction",
            "plan_pp_schedule", "fleet_goodput_tok_s", "affinity_hit_rate",
            "migration_bytes", "fleet_slo_attainment", "migration_count",
            "moe_pallas_tok_s", "expert_imbalance",
            "autoscale_actions", "migration_retry_count",
            "transport_fallback_count",
            "cp_prefill_ttft_s", "long_ctx_tok_s")


def _aux_str(key: str, val: Any) -> str:
    if key == "comm_bytes_per_dim" and isinstance(val, dict):
        return f"comm_bytes={sum(v for v in val.values() if isinstance(v, (int, float))):,.0f}"
    return f"{key}={val}"


def _metric_lines(tail: str) -> List[Dict[str, Any]]:
    """Every parseable JSON object in a round's stdout tail that looks
    like a bench line (has metric + numeric value)."""
    out = []
    for ln in tail.splitlines():
        ln = ln.strip()
        if not ln.startswith("{"):
            continue
        try:
            rec = json.loads(ln)
        except ValueError:
            continue
        if isinstance(rec, dict) and isinstance(
                rec.get("value"), (int, float)) and rec.get("metric"):
            out.append(rec)
    return out


def load_rounds(paths: List[str]) -> List[Tuple[int, List[Dict[str, Any]]]]:
    """[(round_number, [metric lines...])], sorted by round."""
    rounds = []
    for p in paths:
        try:
            with open(p) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            print(f"bench_trend: skipping unreadable {p}: {e}",
                  file=sys.stderr)
            continue
        lines = _metric_lines(doc.get("tail", "") or "")
        parsed = doc.get("parsed")
        if isinstance(parsed, dict) and isinstance(
                parsed.get("value"), (int, float)) and parsed.get("metric"):
            # the driver's own pick of the headline line; dedup by identity
            if not any(l.get("metric") == parsed["metric"]
                       and l.get("value") == parsed["value"] for l in lines):
                lines.append(parsed)
        n = doc.get("n")
        if not isinstance(n, int):
            # fall back to the digits in the filename (BENCH_r07.json -> 7)
            digits = "".join(c for c in os.path.basename(p) if c.isdigit())
            n = int(digits) if digits else len(rounds)
        rounds.append((n, lines))
    return sorted(rounds)


def trend(
    rounds: List[Tuple[int, List[Dict[str, Any]]]], threshold: float = 0.05
) -> Tuple[List[str], List[str]]:
    """(report_lines, regression_warnings) over the per-metric series."""
    series: Dict[str, List[Tuple[int, Dict[str, Any]]]] = {}
    for n, lines in rounds:
        for rec in lines:
            series.setdefault(rec["metric"], []).append((n, rec))
    report: List[str] = []
    warnings: List[str] = []
    for metric in sorted(series):
        rows = series[metric]
        report.append(f"{metric}:")
        prev_val = None
        for n, rec in rows:
            val = rec["value"]
            stale = rec.get("stale")
            delta = (
                f" ({(val - prev_val) / prev_val:+.1%})"
                if (prev_val and not stale) else "")
            aux = " ".join(
                _aux_str(k, rec[k]) for k in AUX_KEYS if k in rec)
            report.append(
                f"  r{n:02d}  {val:>12,.1f}{delta}"
                + ("  [STALE]" if stale else "")
                + (f"  {aux}" if aux else "")
                + f"  {rec.get('config', '')}")
            if not stale:
                prev_val = val
        fresh = [(n, r["value"]) for n, r in rows if not r.get("stale")]
        if len(fresh) >= 2:
            best_prior = max(v for _, v in fresh[:-1])
            last_n, last = fresh[-1]
            if best_prior > 0 and (best_prior - last) / best_prior > threshold:
                warnings.append(
                    f"REGRESSION {metric}: r{last_n:02d} = {last:,.1f} is "
                    f"{(best_prior - last) / best_prior:.1%} below the best "
                    f"earlier round ({best_prior:,.1f}) — past the "
                    f"{threshold:.0%} gate")
    return report, warnings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m torchdistpackage_tpu.tools.bench_trend",
        description="Per-metric deltas across the checked-in bench rounds; "
                    "nonzero exit + loud warning on >threshold regressions.")
    ap.add_argument("--dir", default=None,
                    help="repo dir holding the round files (default: the "
                         "package checkout root)")
    ap.add_argument("--glob", default="BENCH_r*.json",
                    help="round-file pattern (default BENCH_r*.json)")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="relative loss vs the best earlier round that "
                         "trips the regression gate (default 0.05)")
    args = ap.parse_args(argv)
    root = args.dir or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    paths = sorted(_glob.glob(os.path.join(root, args.glob)))
    if not paths:
        print(f"bench_trend: no files match {args.glob} under {root}",
              file=sys.stderr)
        return 2
    report, warnings = trend(load_rounds(paths), threshold=args.threshold)
    for ln in report:
        print(ln)
    for w in warnings:
        print(f"\n!!! {w}", file=sys.stderr)
    return 1 if warnings else 0


if __name__ == "__main__":
    sys.exit(main())
