"""CLI: ``python -m slate_tpu.tune [--op OP ...] [--n N ...]``.

Measures every candidate plan for the requested (op, n) grid, prints
one JSON line per candidate, and persists the winners to the plan
cache (unless --dry-run).  Run once per new chip kind.

``--serve-hist SIZES.jsonl`` switches to serve-bucket ladder fitting:
the file holds one recorded request size per line (a bare integer or
an object with an ``n``/``size`` field, e.g. a log of serve submits);
the tuner fits a padded-area-optimal ladder of at most ``--hist-rungs``
rungs and persists one ``serve_bucket`` cache entry per rung, which
``tune.serve_buckets`` / ``serve.bucket.default_ladder`` then serve."""

from __future__ import annotations

import argparse
import json
import sys

from . import autotune, plans


def _read_hist(path: str) -> list[int]:
    sizes = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            if isinstance(rec, dict):
                rec = rec.get("n", rec.get("size"))
            if rec is None:
                raise ValueError(f"--serve-hist: line without n/size: "
                                 f"{line!r}")
            sizes.append(int(rec))
    return sizes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m slate_tpu.tune")
    ap.add_argument("--op", action="append", choices=plans.OPS,
                    help="op(s) to tune (default: all)")
    ap.add_argument("--n", action="append", type=int,
                    help="problem size(s) (default: 256 512 1024)")
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--dry-run", action="store_true",
                    help="measure + print, do not persist")
    ap.add_argument("--serve-hist", metavar="SIZES.jsonl",
                    help="fit + persist the serve_bucket ladder from a "
                         "request-size histogram instead of tuning ops")
    ap.add_argument("--hist-rungs", type=int, default=8,
                    help="max ladder rungs for --serve-hist (default 8)")
    args = ap.parse_args(argv)
    chip = plans.chip_kind()

    if args.serve_hist:
        sizes = _read_hist(args.serve_hist)
        rungs, w_geo, w_tuned = autotune.tune_serve_buckets(
            sizes, dtype=args.dtype, max_rungs=args.hist_rungs,
            persist=not args.dry_run)
        for r in rungs:
            print(json.dumps({"op": plans.SERVE_BUCKET_OP, "chip": chip,
                              "dtype": args.dtype, "rung": int(r)}))
        print(json.dumps({"op": plans.SERVE_BUCKET_OP, "chip": chip,
                          "dtype": args.dtype, "sizes": len(sizes),
                          "rungs": [int(r) for r in rungs],
                          "padding_waste_geometric": round(w_geo, 4),
                          "padding_waste_tuned": round(w_tuned, 4),
                          "persisted": not args.dry_run}))
        return 0

    ops = args.op or list(plans.OPS)
    ns = args.n or [256, 512, 1024]
    for op in ops:
        for n in ns:
            best_plan, best_gf = None, -1.0
            for plan, gf in autotune.sweep(op, n, args.dtype,
                                           iters=args.iters):
                print(json.dumps({"op": op, "n": n, "chip": chip,
                                  "kernel": plan.kernel, "nb": plan.nb,
                                  "bw": plan.bw,
                                  "gflops": round(gf, 3)}))
                if gf > best_gf:
                    best_plan, best_gf = plan, gf
            if not args.dry_run:
                plans.record_plan(op, n, args.dtype, best_plan,
                                  gflops=best_gf)
            print(json.dumps({"op": op, "n": n, "chip": chip,
                              "winner": best_plan.kernel,
                              "nb": best_plan.nb, "bw": best_plan.bw,
                              "persisted": not args.dry_run}))
    return 0


if __name__ == "__main__":
    from ..util.runtime import use_compile_cache
    use_compile_cache()
    sys.exit(main())
