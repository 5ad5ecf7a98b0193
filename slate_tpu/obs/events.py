"""Structured driver events: one host-side record per public driver call.

The reference renders an execution timeline from trace::Block RAII marks
(ref include/slate/internal/Trace.hh); what it never keeps are the
*decisions* — which method ran, whether speculation was accepted, what
the autotuner picked.  This layer captures exactly that at the existing
driver boundaries: the ``@annotate`` wrapper (util/trace.py) opens a
boundary frame, the ``health.finalize`` / ``recovery`` / ``tune`` seams
note what they resolved into it, and the OUTERMOST frame emits one JSON
event when the driver returns.

Contract (the jaxpr-identity guarantee, tested in tests/test_obs.py):

- Recording happens on the HOST only — timestamps, returned HealthInfo
  scalars, trace-time plan decisions.  No ``io_callback`` rides in the
  computation; enabling or disabling observability produces
  byte-identical jaxprs.
- Exactly ONE event per public driver call: nested driver calls (gesv's
  internal getrf/getrs/gemm) open inner frames that are discarded; all
  notes land on the outermost frame, last-write-wins, so the boundary's
  own finalize is what the event reports.
- A driver call executed while TRACING (the user jitted the driver)
  still emits an event, flagged ``"traced": true`` with health counters
  omitted (they are tracers), and always feeds the retrace sentinel.

Event schema ``slate-obs-v1`` is documented in docs/OBSERVABILITY.md.

This module imports only the stdlib and jax (via util/compat_jax) — it
sits below every other slate_tpu package so drivers, robust/, tune/ and
util/ can all hook in without cycles.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from collections import deque

from ..util.compat_jax import tracing

from . import flops as _flops
from . import sentinel as _sentinel

SCHEMA = "slate-obs-v1"
_MAX_PLANS_PER_EVENT = 8          # bound event size for tile-heavy drivers

_TLS = threading.local()
_LOCK = threading.Lock()
_CFG = {"enabled": False, "path": None, "timing": False}
_RING: deque = deque(maxlen=int(os.environ.get("SLATE_OBS_RING", "256")))
_COLLECTORS: list[list] = []


class _Frame:
    """One open driver boundary (host-side bookkeeping only)."""

    __slots__ = ("op", "t0", "traced", "shapes", "dtype", "notes",
                 "plans_seen", "device_ms")

    def __init__(self, op, traced, shapes, dtype):
        self.op = op
        self.t0 = time.perf_counter()
        self.traced = traced
        self.shapes = shapes
        self.dtype = dtype
        self.notes: dict = {}
        self.plans_seen: set = set()
        self.device_ms: float | None = None


def _frames() -> list:
    fs = getattr(_TLS, "frames", None)
    if fs is None:
        fs = _TLS.frames = []
    return fs


def _active() -> bool:
    # slate-lint: disable=CON001 -- designed lock-free peek on the per-call fast path: a stale read only delays one event past a concurrent enable/disable, never tears (dict read is atomic under the GIL)
    return _CFG["enabled"] or bool(_COLLECTORS)


def enabled() -> bool:
    """Is event recording currently on (global switch or a collector)?"""
    return _active()


def configure(enabled: bool | None = None, path: str | None = None) -> None:
    """Flip the global recording switch and/or set the JSONL sink path.

    ``path=None`` keeps events in the in-process ring buffer only (see
    :func:`recent`).  ``SLATE_OBS_EVENTS=<path>`` in the environment
    enables recording to that path at import time."""
    with _LOCK:
        if enabled is not None:
            _CFG["enabled"] = bool(enabled)
        if path is not None:
            _CFG["path"] = path or None


def enable(path: str | None = None) -> None:
    configure(enabled=True, path=path)


def disable() -> None:
    configure(enabled=False)


@contextlib.contextmanager
def recording(path: str | None = None):
    """Collect events for the scope; yields the (live) list of events.

        with obs.recording() as events:
            st.gesv(A, B)
        assert events[0]["op"] == "gesv"

    With ``path`` the events are also appended to a JSONL file."""
    events: list = []
    with _LOCK:
        _COLLECTORS.append(events)
        prev_path = _CFG["path"]
    if path is not None:
        configure(path=path)
    try:
        yield events
    finally:
        with _LOCK:
            _COLLECTORS.remove(events)
            _CFG["path"] = prev_path


def recent(n: int | None = None) -> list:
    """The last ``n`` events from the in-process ring buffer."""
    with _LOCK:
        out = list(_RING)
    return out if n is None else out[-n:]


def clear() -> None:
    with _LOCK:
        _RING.clear()


# ------------------------------------------------------------------ timing
#
# Device timing is OPT-IN: when on, the outermost eager driver boundary
# blocks until its result is device-ready and the event's ``device_ms``
# measures dispatch->ready instead of just host wall time.  The sync
# happens strictly OUTSIDE traced code (the annotate wrapper consults
# :func:`should_time`, which refuses traced frames), so the
# jaxpr-identity guarantee is untouched: timing on or off, the traced
# computation is byte-identical — only the host waits differently.


def timing_enabled() -> bool:
    """Is device-time measurement on (``obs.timing()`` or
    ``SLATE_OBS_TIMING=1``)?"""
    # slate-lint: disable=CON001 -- designed lock-free peek on the per-call fast path: one boundary may miss a concurrent toggle, which is benign (atomic dict read under the GIL)
    return _CFG["timing"]


def set_timing(on: bool) -> None:
    with _LOCK:
        _CFG["timing"] = bool(on)


@contextlib.contextmanager
def timing(on: bool = True):
    """Scope device-time measurement: events gain ``device_ms`` /
    ``mfu`` / ``achieved_gbps`` (None outside the scope)."""
    with _LOCK:
        prev = _CFG["timing"]
    set_timing(on)
    try:
        yield
    finally:
        set_timing(prev)


def should_time(token) -> bool:
    """Should the annotate wrapper block_until_ready for this boundary?
    Only the OUTERMOST eager frame with timing on — nested boundaries
    would double-sync, and traced frames hold tracers, not buffers."""
    # slate-lint: disable=CON001 -- designed lock-free peek on the per-call fast path: one boundary may miss a concurrent toggle, which is benign (atomic dict read under the GIL)
    if token is None or not _CFG["timing"] or token.traced:
        return False
    frames = _frames()
    return bool(frames) and frames[0] is token


def note_device_ready(token) -> None:
    """Stamp the boundary's dispatch->device-ready time (called by the
    annotate wrapper right after ``jax.block_until_ready(out)``)."""
    if token is not None:
        token.device_ms = round((time.perf_counter() - token.t0) * 1e3, 3)


# ---------------------------------------------------------------- describe


def _describe(x):
    """Best-effort (shape, dtype) of one driver argument — Matrix-likes
    expose .m/.n, raw arrays .shape; anything else is skipped."""
    shape = getattr(x, "shape", None)
    if shape is None and hasattr(x, "m") and hasattr(x, "n"):
        shape = (getattr(x, "m"), getattr(x, "n"))
    if shape is None:
        return None
    try:
        shape = tuple(int(s) for s in shape)
    except (TypeError, ValueError):
        return None
    dt = getattr(x, "dtype", None)
    return shape, (str(getattr(dt, "name", dt)) if dt is not None else None)


def _describe_args(args):
    shapes, dtype = [], None
    for a in args:
        d = _describe(a)
        if d is None:
            continue
        shapes.append(list(d[0]))
        if dtype is None:
            dtype = d[1]
    return shapes, dtype


def _signature(shapes, dtype) -> str:
    return f"{dtype}:" + ";".join(
        "x".join(str(s) for s in shape) for shape in shapes)


# ---------------------------------------------------------------- boundary


def boundary_enter(op: str, args=()):
    """Open a driver boundary frame (called by util.trace.annotate).

    Returns an opaque token for :func:`boundary_exit`, or None when
    recording is off — the disabled path does no per-call work beyond a
    depth bump and the traced-ness check that feeds the retrace
    sentinel.  Only the OUTERMOST boundary feeds the sentinel: a single
    user trace of posv stages its internal trsm/gemm boundaries too, and
    counting those would flag the caller for retraces it never made."""
    depth = getattr(_TLS, "depth", 0)
    _TLS.depth = depth + 1
    traced = tracing()
    if traced and depth == 0:
        shapes, dtype = _describe_args(args)
        _sentinel.record_trace(op, _signature(shapes, dtype))
        if not _active():
            return None
    elif not _active():
        return None
    else:
        shapes, dtype = _describe_args(args)
    frame = _Frame(op, traced, shapes, dtype)
    _frames().append(frame)
    return frame


def boundary_exit(token, error: BaseException | None = None) -> None:
    """Close a boundary frame; the outermost frame emits its event."""
    depth = getattr(_TLS, "depth", 0)
    if depth > 0:
        _TLS.depth = depth - 1
    if token is None:
        return
    frames = _frames()
    try:
        i = frames.index(token)
    except ValueError:
        return                      # configure() flipped mid-call: drop
    del frames[i:]
    if i == 0:
        _emit(_build(token, error))


def _outer() -> _Frame | None:
    frames = _frames()
    return frames[0] if frames else None


def _build(frame: _Frame, error) -> dict:
    notes = frame.notes
    op = frame.op[6:] if frame.op.startswith("slate.") else frame.op
    mfu = gbps = None
    if frame.device_ms:
        secs = frame.device_ms * 1e-3
        mfu = _flops.mfu(_flops.op_flops(op, frame.shapes), secs,
                         frame.dtype)
        gbps = _flops.achieved_gbps(
            _flops.op_bytes(op, frame.shapes, frame.dtype), secs)
    return {
        "schema": SCHEMA,
        "kind": "event",
        "ts": time.time(),
        "op": op,
        "shapes": frame.shapes,
        "dtype": frame.dtype,
        "traced": frame.traced,
        "dur_ms": round((time.perf_counter() - frame.t0) * 1e3, 3),
        "device_ms": frame.device_ms,
        "mfu": mfu,
        "achieved_gbps": gbps,
        "policy": notes.get("policy"),
        "speculate": notes.get("speculate"),
        "abft": notes.get("abft"),
        "path": notes.get("path", "direct"),
        "escalations": notes.get("escalations", 0),
        "health": notes.get("health"),
        "plans": notes.get("plans", []),
        "method_trsm": notes.get("method_trsm"),
        "status": ("ok" if error is None
                   else f"error:{type(error).__name__}"),
    }


def emit_serve_batch(payload: dict) -> None:
    """One ``slate-obs-v1`` record per executed serving batch (kind
    ``serve_batch``; slate_tpu.serve.server is the only caller).  The
    payload carries bucket occupancy, padding-waste, escalation and
    executable-cache stats — docs/SERVING.md documents the fields.  Like
    driver boundaries this is host-side only and a no-op while recording
    is off."""
    if not _active():
        return
    _emit({"schema": SCHEMA, "kind": "serve_batch", "ts": time.time(),
           **payload})


def emit_serve_shed(payload: dict) -> None:
    """One record per request shed by serving admission control (kind
    ``serve_shed``; serve/server.py is the only caller).  The payload
    carries op/dtype, the shed ``reason`` (deadline / overflow_* /
    watchdog / shutdown), the victim's age and the queue depth — the
    inputs behind the serving table's ``shed/1k`` column.  ``device_id``
    is always None: shedding happens at admission, before the device
    pool picks a member."""
    if not _active():
        return
    _emit({"schema": SCHEMA, "kind": "serve_shed", "ts": time.time(),
           **payload})


def emit_serve_quarantine(payload: dict) -> None:
    """One record per request quarantined to the singleton slow path
    after exhausting the fresh-batch retry (kind ``serve_quarantine``;
    serve/server.py is the only caller) — the ``quar/1k`` column.
    ``device_id`` is the pool member that served the singleton."""
    if not _active():
        return
    _emit({"schema": SCHEMA, "kind": "serve_quarantine", "ts": time.time(),
           **payload})


def emit_serve_device(payload: dict) -> None:
    """One record per device-pool health transition (kind
    ``serve_device``; serve/pool.py is the only caller).  The payload
    carries ``event`` (failover / quarantine / probe_fail / readmit),
    the pool member's ``device_id``, the triggering ``reason``
    (exception / nonfinite / deadline / canary / flake) and the strike
    count — the inputs behind the serving table's ``failovers``
    column and the kill-a-device drill's assertions."""
    if not _active():
        return
    _emit({"schema": SCHEMA, "kind": "serve_device", "ts": time.time(),
           **payload})


def emit_serve_retune(payload: dict) -> None:
    """One record per online ladder hot-swap (kind ``serve_retune``;
    serve/server.py is the only caller).  The payload carries the
    op/dtype whose ladder was refit, the old and new rungs, the live
    vs fitted padded-waste ratios that justified the swap, and how
    many observed sizes fed the DP fitter — the ``retunes`` column."""
    if not _active():
        return
    _emit({"schema": SCHEMA, "kind": "serve_retune", "ts": time.time(),
           **payload})


def emit_checkpoint(kind: str, payload: dict) -> None:
    """One record per checkpoint save or verified restore (kinds
    ``checkpoint_save`` / ``checkpoint_restore``; robust/checkpoint.py
    is the only caller).  The payload carries the op, the panel-step
    index ``step``, payload ``bytes``, the ``verify`` result ("ok" or
    the typed refusal reason — torn / stale / corrupt / abft /
    fingerprint) and ``wall_ms`` — the inputs behind the metrics CLI's
    durability table (docs/ROBUSTNESS.md "Durable jobs")."""
    if not _active():
        return
    _emit({"schema": SCHEMA, "kind": kind, "ts": time.time(), **payload})


def _emit(event: dict) -> None:
    with _LOCK:
        _RING.append(event)
        for c in _COLLECTORS:
            c.append(event)
        path = _CFG["path"] if _CFG["enabled"] else None
    if path:
        line = json.dumps(event)
        with _LOCK:
            with open(path, "a", encoding="utf-8") as fh:
                fh.write(line + "\n")


# ------------------------------------------------------------------- notes
#
# All note_* calls attach to the OUTERMOST open frame (the one that will
# emit) and are no-ops when no frame is open — so the seams below can call
# unconditionally with zero cost while recording is off.


def note_health(name: str, h, policy: str) -> None:
    """Called by health.finalize with the boundary's resolved policy and
    HealthInfo.  Traced health (a jitted-driver trace) is recorded as
    None — tracers have no values to read.  Last write wins, which makes
    the boundary's own (merged) finalize the one the event reports."""
    frame = _outer()
    if frame is None:
        return
    frame.notes["policy"] = policy
    if h is None or h.is_traced():
        frame.notes["health"] = None
        return
    site = int(h.abft_site)
    frame.notes["health"] = {
        "ok": bool(h.ok),
        "info": int(h.info),
        "nonfinite": bool(h.nonfinite),
        "min_pivot": float(h.min_pivot),
        "min_pivot_index": int(h.min_pivot_index),
        "growth": float(h.growth),
        "iters": int(h.iters),
        "converged": bool(h.converged),
        "abft_detected": int(h.abft_detected),
        "abft_corrected": int(h.abft_corrected),
        "abft_site": ([site >> 16, site & 0xffff] if site >= 0 else None),
    }


def note_resolved(knob: str, value) -> None:
    """Called by options.resolve_speculate / resolve_abft: record the
    once-per-boundary resolution ('speculate' / 'abft')."""
    frame = _outer()
    if frame is not None:
        frame.notes.setdefault(knob, bool(value))


def note_choice(knob: str, name: str) -> None:
    """Called by a driver that picks among kernels (trsm's
    ``method_trsm``): record the name of the one that ran, first write
    wins like :func:`note_resolved`."""
    frame = _outer()
    if frame is not None:
        frame.notes.setdefault(knob, str(name))


def note_path(first: str, rungs, used: int, speculated: bool) -> None:
    """Called by the recovery boundaries: which attempt produced the
    result.  ``first`` names the primary attempt, ``rungs`` the fallback
    ladder in order, ``used`` how many rungs bounded_retry consumed."""
    frame = _outer()
    if frame is None:
        return
    rungs = list(rungs)
    if used <= 0 or used > len(rungs):
        kind = "speculated" if speculated else "direct"
        frame.notes["path"] = f"{kind}:{first}"
    else:
        frame.notes["path"] = f"escalated:{rungs[used - 1]}"
    frame.notes["escalations"] = min(max(used, 0), len(rungs))


def note_plan(op: str, n: int, dtype: str, kernel: str, nb: int,
              source: str, dist: float | None) -> None:
    """Called by tune.resolve_plan: one tuned-dispatch decision.  A
    driver resolves plans per panel, so identical decisions dedupe and
    the list is capped at _MAX_PLANS_PER_EVENT."""
    frame = _outer()
    if frame is None:
        return
    key = (op, n, dtype, kernel, nb, source)
    if key in frame.plans_seen:
        return
    frame.plans_seen.add(key)
    plans = frame.notes.setdefault("plans", [])
    if len(plans) >= _MAX_PLANS_PER_EVENT:
        return
    plans.append({"op": op, "n": int(n), "dtype": dtype, "kernel": kernel,
                  "nb": int(nb), "source": source,
                  "dist": (None if dist is None else round(float(dist), 3))})


def _init_from_env() -> None:
    path = os.environ.get("SLATE_OBS_EVENTS")
    if path:
        configure(enabled=True, path=path)
    if os.environ.get("SLATE_OBS_TIMING", "").lower() in ("1", "true",
                                                          "on", "yes"):
        set_timing(True)


_init_from_env()
