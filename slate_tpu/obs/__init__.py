"""slate_tpu.obs — the observability spine (docs/OBSERVABILITY.md).

Six pieces, all host-side and zero-overhead when disabled:

- structured driver events (:mod:`events`): one JSON record per public
  driver call — op, shapes, resolved policy/speculate/abft, path taken,
  HealthInfo counters, tuned plans, wall duration;
- a recording span tracer (:mod:`tracer`): ``util.trace.span`` wall
  times exported as Chrome/Perfetto trace JSON or JSONL;
- a retrace sentinel (:mod:`sentinel`): per-signature trace counters
  with rate-limited warnings on retrace/recompile churn;
- metrics aggregation (:mod:`metrics`) behind the
  ``python -m slate_tpu.obs`` CLI;
- device-time truth (:mod:`flops`): one analytic flop/byte model per
  public op feeding ``device_ms``/``mfu``/``achieved_gbps`` on events
  under the opt-in :func:`timing` mode;
- serving SLOs (:mod:`slo`) over the flight-recorder fields, behind
  ``--slo``.

The jaxpr-identity guarantee: enabling any of this changes NOTHING in
traced computations (no io_callback, no extra ops) — recording reads
returned HealthInfo and host clocks only.
"""

from . import flops, slo
from .events import (SCHEMA, boundary_enter, boundary_exit, clear,
                     configure, disable, enable, enabled, emit_serve_batch,
                     emit_serve_quarantine, emit_serve_shed, note_health,
                     note_path, note_plan, note_resolved, recent, recording,
                     set_timing, timing, timing_enabled)
from .metrics import render, summarize
from .sentinel import SlateRetraceWarning
from .sentinel import reset as reset_sentinel
from .sentinel import stats as sentinel_stats
from .tracer import SpanRecorder, record_spans

__all__ = [
    "SCHEMA", "SlateRetraceWarning", "SpanRecorder", "boundary_enter",
    "boundary_exit", "clear", "configure", "disable", "enable",
    "enabled", "emit_serve_batch", "emit_serve_quarantine",
    "emit_serve_shed", "flops", "note_health", "note_path",
    "note_plan", "note_resolved", "recent", "record_spans", "recording",
    "render", "reset_sentinel", "sentinel_stats", "set_timing", "slo",
    "summarize", "timing", "timing_enabled",
]
