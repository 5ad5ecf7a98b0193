#!/usr/bin/env python3
"""Device time per named phase of a dense solve cell, from one traced run.

    python3 benchmark/phase_table.py --workload linsys.gesv --seed 7 \
        --seconds 51 [--n 2048 --nb 512] [--save DIR]

Runs the cell as ``run.py --trace 1`` does (the same set-up, window,
trace cut and check), with two additions: each solve's jitted call runs
inside a host span ``bench.dispatch`` (so an idle gap inside
``bench.solve`` but outside it is the wait for x and its health), and
after the window the solve program's compiled HLO text is fetched from
the same jitted object.  ``benchmark/phases.py`` joins the two.
Standard error carries the phase table (milliseconds per solve, op
events per execution), the longest instructions of each ``*/other``,
``unscoped`` and ``unjoined`` part, the idle time per solve by host
span and the seconds the text took; the last line of standard output is
a JSON object with the same numbers and the cell's per-layer metrics as
``run.py`` reads them.  ``--n``/``--nb`` cut the configuration's order;
``--save`` keeps, gzipped under DIR, the program's text, each
instruction's own time and op events over the covered executions, and
the trace where it is small.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import harness, phases, trace  # noqa: E402

# a trace larger than this is not kept by --save (gesv at full size
# records 5.5 M op events a solve); the text and the per-instruction
# times are
SAVE_TRACE_BYTES = 32 << 20


def log(msg: str) -> None:
    print(f"phases: {msg}", file=sys.stderr, flush=True)


def program_text(step, args) -> str:
    """The compiled HLO text of ``step`` on ``args``: lowered from the
    same jitted object and arguments as the window's calls, so JAX's
    trace and executable caches answer it."""
    return step.lower(*args).compile().as_text()


def run(workload: str, seed: int, seconds: float, n: int | None,
        nb: int | None, save: str | None, require_tpu: bool = True) -> dict:
    spec = harness.load_spec()
    cell = harness.find_cell(spec, workload)
    config = harness.load_config(spec, cell)
    if n:
        config = dict(config, n=n, nb=nb or config["nb"])
    mix = harness.load_traffic(cell)
    import jax
    import jax.numpy as jnp
    devs = harness.devices_for(jax, int(cell["chips"]), require_tpu)
    ids = [d.id for d in devs]
    peak = harness.peaks(devs[0].device_kind) if require_tpu else None
    harness.prepare_runtime(jax)
    seconds = min(seconds, float(mix.get("trace_seconds", seconds)))
    system = harness.system_module(config).build(config, mix, devs, log)
    system.setup(seed, seconds)
    step = system.step

    def dispatch(*args):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            return step(*args)

    system.step = dispatch
    gc.collect()
    gc.freeze()
    log(f"set-up {time.perf_counter() - T_START:.3f} s")
    trace_dir = tempfile.mkdtemp(prefix="bench-phases-")
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            system.window(seconds)
    finally:
        gc.unfreeze()
        jax.profiler.stop_trace()
    t0 = time.perf_counter()
    hlo = program_text(step, (system.a, system.diag, system.b,
                              jnp.float32(0.0)))
    fetch_s = time.perf_counter() - t0
    log(f"program text {len(hlo)} B in {fetch_s:.3f} s")
    system.release()
    attempted, failed, checks = system.check()
    correct = bool(checks) and all(c.ok() for c in checks) and failed == 0

    t0 = time.perf_counter()
    path = harness._trace_file(trace_dir)
    tr = trace.load_xspace(path)
    red = trace.reduce(tr, window_span="bench.window", devices=ids)
    ctx = {"trace": red, "system": system.context(), "peak": peak}
    metrics = {m["name"]: harness.reader(m["name"])(ctx)
               for m in harness.metrics_for(spec, cell, "per_layer")}
    t1 = time.perf_counter()
    tab = phases.table(tr, hlo, devices=ids)
    solves = len(system.records)
    ms = phases.ms_by_phase(tab, red, solves)
    events = phases.events_per_execution(tab)
    idle = {k: 1e3 * v / max(solves, 1)
            for k, v in phases.idle_by_label(tr, devices=ids).items()}
    log(f"trace read and reduced in {t1 - t0:.3f} s, phases joined in "
        f"{time.perf_counter() - t1:.3f} s")
    if save:
        out = Path(save)
        out.mkdir(parents=True, exist_ok=True)
        stem = f"{mix['op']}_n{config['n']}"
        with gzip.open(out / f"{stem}.hlo.txt.gz", "wt") as dst:
            dst.write(hlo)
        with gzip.open(out / f"{stem}.ops.json.gz", "wt") as dst:
            json.dump({"busy_s": red["busy_s"], "solves": solves,
                       "devices": {dev: {"executions": d["executions"],
                                         "instructions": d["instructions"]}
                                   for dev, d in tab["devices"].items()}},
                      dst)
        if Path(path).stat().st_size <= SAVE_TRACE_BYTES:
            with open(path, "rb") as src, gzip.open(
                    out / f"{stem}.xplane.pb.gz", "wb") as dst:
                shutil.copyfileobj(src, dst)
    shutil.rmtree(trace_dir, ignore_errors=True)

    op_s = sum(d["op_s"] for d in tab["devices"].values())
    share = {b: sum(d["phases"].get(b, (0.0, 0))[0]
                    for d in tab["devices"].values()) / op_s if op_s else 0.0
             for b in (phases.UNSCOPED, phases.UNJOINED)}
    share["inherited"] = sum(d["inherited_s"] for d in
                             tab["devices"].values()) / op_s if op_s else 0.0
    busy_ms = 1e3 * red["busy_s"] / max(solves, 1)
    log(f"solves {solves}, busy {busy_ms!r} ms a solve, phases sum "
        f"{sum(ms.values())!r} ms; of the covered op time "
        f"{100 * share['unscoped']!r}% unscoped, "
        f"{100 * share['unjoined']!r}% unjoined, "
        f"{100 * share['inherited']!r}% in an inherited scope")
    for b, v in sorted(ms.items(), key=lambda kv: -kv[1]):
        log(f"  {b}: {v!r} ms a solve, {events.get(b, 0.0)!r} op events "
            f"an execution")
    for b, top in tab["top"].items():
        log(f"  top of {b}: " + "; ".join(f"{k} {1e3 * v!r} ms"
                                          for k, v in top))
    log("idle ms a solve by host span: " + ", ".join(
        f"{k} {v!r}" for k, v in sorted(idle.items())))
    return {"workload": workload, "seed": seed, "n": config["n"],
            "correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics,
            "phase_metrics": {k: phases.ms_per_solve(tab, red, solves, v)
                              for k, v in phases.METRICS.items()},
            "phases_ms": ms, "events_per_execution": events,
            "busy_ms_per_solve": busy_ms, "unscoped_share": share["unscoped"],
            "unjoined_share": share["unjoined"],
            "inherited_share": share["inherited"], "idle_ms_per_solve": idle,
            "program_text_s": fetch_s, "spans": tab["spans"],
            "top": tab["top"],
            "checks": {c.name: c.value for c in checks}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--n", type=int)
    ap.add_argument("--nb", type=int)
    ap.add_argument("--save")
    args = ap.parse_args()
    try:
        result = run(args.workload, args.seed, args.seconds, args.n, args.nb,
                     args.save)
    except (harness.NoChip, harness.SpecError) as e:
        log(str(e))
        return 2
    result["run_s"] = time.perf_counter() - T_START
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
