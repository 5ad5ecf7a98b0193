"""Find a cell's files by name, run the cell once, print its result.

Nothing here knows a cell, a configuration or a metric by name.  The
cell's entry in ``BENCHMARK.json`` names a configuration and a traffic
mix; the configuration names the system adapter that drives the
program (``systems/<system>.py``); each metric the cell reports is
listed in ``BENCHMARK.json`` and, per layer, read by
``metrics/<metric>.py``.  Adding a cell, a mix or a metric is adding
files and entries.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
# JAX's persistent compilation cache: a fixed directory of the checkout,
# so a later run of the same cell in the same checkout finds every
# program (the path is part of what the cache matches on)
CACHE_DIR = ".jax_cache"


class SpecError(Exception):
    """A name that BENCHMARK.json or the benchmark's files do not hold."""


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass(frozen=True)
class Check:
    """One number that decides ``correct``: it passes at or under its
    limit, and a NaN or an infinity never passes."""
    name: str
    value: float
    limit: float

    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


# ------------------------------------------------------------ discovery


def load_spec(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json", encoding="utf-8") as fh:
        return json.load(fh)


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def load_config(spec: dict, cell: dict, root: Path = ROOT) -> dict:
    for entry in spec["configs"]:
        if entry["name"] == cell["config"]:
            with open(Path(root) / entry["file"], encoding="utf-8") as fh:
                return json.load(fh)
    raise SpecError(f"no config {cell['config']!r} in BENCHMARK.json")


def load_traffic(cell: dict, root: Path = ROOT) -> dict:
    path = Path(root) / "benchmark" / "traffic" / f"{cell['traffic']}.json"
    if not path.is_file():
        raise SpecError(f"no traffic file {path}")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def metrics_for(spec: dict, cell: dict, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: all
    without a ``workloads`` list, and those whose list names the cell."""
    return [m for m in spec[kind]
            if cell["name"] in m.get("workloads", [cell["name"]])]


def load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: Path = ROOT):
    """``read(ctx) -> float | None`` of ``metrics/<metric>.py``."""
    mod = load_module(Path(root) / "benchmark" / "metrics" / f"{metric}.py",
                      f"bench_metric_{metric.replace('.', '_')}")
    return mod.read


def system_module(config: dict, root: Path = ROOT):
    return load_module(
        Path(root) / "benchmark" / "systems" / f"{config['system']}.py",
        f"bench_system_{config['system']}")


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """The chip's published peaks; a kind missing from the table is an
    error, never a default."""
    with open(Path(root) / "benchmark" / "peaks.json", encoding="utf-8") as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"benchmark/peaks.json ({sorted(table)})")
    return table[device_kind]


# -------------------------------------------------------------- devices


def devices_for(jax, chips: int, require_tpu: bool = True) -> list:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def prepare_runtime(jax, root: Path = ROOT) -> None:
    """The program's plan cache is the one its checkout ships
    (``tune_plans.json``, absent means every default plan), never
    ``~/.cache``; every compiled program goes to the checkout's cache."""
    os.environ["SLATE_TUNE_CACHE"] = str(Path(root) / "tune_plans.json")
    jax.config.update("jax_compilation_cache_dir", str(Path(root) / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # no eviction: every cell's programs stay found, whatever the machine
    jax.config.update("jax_compilation_cache_max_size", -1)


class CompileCounter:
    """Counts programs lowered while ``active`` (expected 0 inside the
    measured window): JAX reports each lowering to MLIR as an event."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self, jax):
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if self.active and event == self.EVENT:
            self.count += 1


# ------------------------------------------------------------------ run


def _trace_file(trace_dir: str) -> str:
    for dirpath, _, files in os.walk(trace_dir):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(dirpath, f)
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: Path = ROOT, require_tpu: bool = True,
             log=None) -> dict:
    """Run one cell once and return its result object (the line
    ``main`` prints).  ``t_start`` is the process's start on the
    ``time.perf_counter`` clock: set-up runs from there to the first
    timed operation."""
    log = log or (lambda msg: print(f"bench: {msg}", file=sys.stderr,
                                    flush=True))
    root = Path(root)
    spec = load_spec(root)
    cell = find_cell(spec, workload)
    config = load_config(spec, cell, root)
    traffic = load_traffic(cell, root)
    e2e = metrics_for(spec, cell, "end_to_end")
    layer = metrics_for(spec, cell, "per_layer")

    import jax
    devs = devices_for(jax, int(cell["chips"]), require_tpu)
    dev = device_info(devs)
    peak = peaks(dev["kind"], root) if require_tpu else None
    prepare_runtime(jax, root)
    counter = CompileCounter(jax)

    if trace:
        # the device's trace buffer holds only so many op events: a mix
        # whose programs have very many ops traces a shorter window
        seconds = min(seconds, float(traffic.get("trace_seconds", seconds)))
    system = system_module(config, root).build(config, traffic, devs, log)
    system.setup(seed, seconds)
    # set-up's objects (traced programs among them) leave the cyclic
    # collector's walks, as in a long-lived service; new ones still count
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    counter.active = True
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            system.window(seconds)
    finally:
        counter.active = False
        gc.unfreeze()
        if trace:
            jax.profiler.stop_trace()
    dev["memory_peak_bytes"] = memory_peak_bytes(devs)
    log(f"programs lowered inside the window: {counter.count}")
    log(f"peak device memory: {dev['memory_peak_bytes']} B")
    system.release()

    attempted, failed, checks = system.check()
    correct = bool(checks) and all(c.ok() for c in checks) and failed == 0

    metrics = {}
    breakdown = None
    if trace:
        from benchmark import trace as _trace
        try:
            tr = _trace.load_xspace(_trace_file(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        red = _trace.reduce(tr, window_span="bench.window",
                            devices=[d.id for d in devs])
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        log(f"trace: clock offset {red['clock_offset_s']!r} s; per device "
            + "; ".join(f"{k}: " + ", ".join(f"{n} {v!r}" for n, v in
                                             d.items())
                        for k, d in red["devices"].items()))
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        ctx = {"trace": red, "system": system.context(), "peak": peak}
        for m in layer:
            value = reader(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        measured = dict(system.end_to_end(), setup_s=setup_s)
        for m in e2e:
            metrics[m["name"]] = {"value": measured[m["name"]],
                                  "unit": m["unit"]}
    for line in system.notes():
        log(line)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok() else 'FAILED'}")
    return result


def main(argv=None, t_start: float | None = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start)
    except (NoChip, SpecError) as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0
