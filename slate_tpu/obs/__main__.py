"""CLI: aggregate slate event JSONL; serving SLO verdicts.

    python -m slate_tpu.obs events.jsonl
    python -m slate_tpu.obs --json events.jsonl > summary.json
    python -m slate_tpu.obs --slo budgets.json events.jsonl
    python -m slate_tpu.obs --prom events.jsonl

Accepts any mix of obs event JSONL (slate-obs-v1), span JSONL,
serve_batch and checkpoint records, and prints per-op
latency/device-time/MFU tables, plan-usage, serving (occupancy, waste,
submit->drain latency p50/p99, waste-adjusted throughput) and
durability tables (docs/OBSERVABILITY.md).

Exit codes: 0 clean; 1 a failed ``--slo`` budget; 2 usage / unreadable
input.
"""

from __future__ import annotations

import argparse
import json
import sys

from . import metrics, slo


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m slate_tpu.obs",
        description="Summarize slate_tpu event JSONL files or check "
                    "serving SLO budgets.")
    parser.add_argument("files", nargs="+", help="event JSONL files")
    parser.add_argument("--json", action="store_true",
                        help="print results as JSON instead of tables")
    parser.add_argument("--slo", metavar="BUDGETS.json",
                        help="evaluate serving SLO budgets over the "
                             "given event files (exit 1 on any failed "
                             "budget)")
    parser.add_argument("--prom", action="store_true",
                        help="emit the serving aggregate as "
                             "Prometheus-style text")
    args = parser.parse_args(argv)

    try:
        if args.slo or args.prom:
            return _run_slo(args)
        summary = metrics.summarize(args.files)
    except OSError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.json:
        json.dump(summary, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
    else:
        sys.stdout.write(metrics.render(summary))
    return 0


def _run_slo(args) -> int:
    records, _ = metrics.load_records(args.files)
    stats = slo.aggregate(records)
    if args.prom:
        sys.stdout.write(slo.export_prometheus(stats))
    if not args.slo:
        return 0
    try:
        budgets = slo.load_budgets(args.slo)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    verdicts = slo.evaluate(stats, budgets)
    if args.json:
        json.dump({"stats": stats, "verdicts": verdicts}, sys.stdout,
                  indent=1, sort_keys=True)
        sys.stdout.write("\n")
    elif not args.prom:
        sys.stdout.write(slo.render_verdicts(verdicts))
    return 1 if any(not v["ok"] for v in verdicts) else 0


if __name__ == "__main__":
    sys.exit(main())
