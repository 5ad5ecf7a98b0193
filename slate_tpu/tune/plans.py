"""Persistent tile-plan store + the trace-time plan resolver.

A pre-benchmark chip round showed nb=256/512/1024 each winning at
different n — the panel-kernel choice is a *search* problem per (op, n,
dtype, chip), not a constant (PAPERS.md "Design in Tiles" /
"TileLoom").  This module owns the answer: a small on-disk JSON cache
of winning ``TilePlan``s, written by ``slate_tpu.tune.autotune`` and
read back by the internal dispatch seams (potrf_tile, getrf panel,
geqrf panel) through ONE function, :func:`resolve_plan`.

Trace-safety contract (slate-lint TRC): ``resolve_plan`` takes only
host-static values (python ints from ``.shape``, dtype names) and
returns a plain NamedTuple consumed as static configuration — it runs
at trace time, never on tracer data, so cached-plan dispatch lowers to
a fixed kernel choice with no data-dependent control flow.

Seam contract (slate-lint SEAM011): drivers and internal modules must
NOT touch the raw cache (load_cache / save_cache / record_plan /
cache_path) — they call ``resolve_plan`` only.  The raw accessors exist
for the autotuner and for tests.

Cache schema (version 1)::

    {"version": 1,
     "chips": {"<chip-kind>": {"<op>": {"n=512,dtype=float32":
         {"kernel": "pallas", "nb": 512, "bw": 8, "gflops": 123.4}}}}}

``SLATE_PALLAS`` is REMOVED (deprecated in the previous release): the
variable is IGNORED and setting it warns once per process, pointing at
``plan_override`` and the ``python -m slate_tpu.tune`` CLI.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import threading
import warnings
from typing import NamedTuple

SCHEMA_VERSION = 1
OPS = ("potrf_tile", "potrf_panel", "getrf_panel", "lu_select",
       "geqrf_panel", "batch_potrf", "batch_getrf", "batch_geqrf")
# ``dist_lookahead`` is a pseudo-op: it does not pick a panel kernel but
# the comm/compute pipeline depth of the distributed kernels — kernel
# "xla" means the bulk-synchronous masked-psum path (depth 0, the parity
# oracle), kernel "ring" means the lookahead pipeline with ``bw`` as the
# measured depth (1 or 2).  Resolved only via lookahead_depth(), and —
# like ``serve_bucket`` — schema-accepted but excluded from OPS so the
# kernel autotuner's candidate sweeps never try to measure it (lookahead
# wins are measured end to end by bench_*_lookahead instead).
DIST_LOOKAHEAD_OP = "dist_lookahead"
# The serving layer's bucket ladder rides the same cache file but is NOT a
# kernel-tuning op (no candidate sweep): each recorded entry's ``n`` is one
# ladder rung for this chip (see serve_buckets / docs/SERVING.md).
SERVE_BUCKET_OP = "serve_bucket"
# Out-of-core panel width is another pseudo-op: not a kernel choice but
# the host<->device streaming granularity of the TileMap drivers
# (drivers/cholesky.py potrf_ooc, drivers/lu.py getrf_ooc) — wide enough
# to amortize the H2D/D2H copies, narrow enough that two panels plus one
# trailing window fit HBM.  Resolved only via ooc_panel_width(); like
# the other pseudo-ops it is schema-accepted but excluded from OPS so
# kernel candidate sweeps never measure it (OOC wins are end-to-end,
# bench_potrf_ooc).
OOC_PANEL_OP = "ooc_panel"
ALL_OPS = OPS + (DIST_LOOKAHEAD_OP, SERVE_BUCKET_OP, OOC_PANEL_OP)
KERNELS = ("xla", "pallas", "ring")


class TilePlan(NamedTuple):
    """One tuned dispatch decision: which kernel, at which tile width
    ``nb`` (advisory — drivers tile by Matrix.nb; the tuner records the
    width that won so callers picking a tiling can consult it), with
    which Pallas row-panel width ``bw``."""
    kernel: str = "xla"
    nb: int = 512
    bw: int = 8


XLA_PLAN = TilePlan()

_LOCK = threading.Lock()
_CACHE: dict | None = None          # lazily loaded, keyed by cache_path()
_CACHE_KEY: str | None = None
_OVERRIDES: dict[str, TilePlan] = {}
_WARNED = False


def cache_path() -> str:
    """Plan-cache location: $SLATE_TUNE_CACHE, else
    ~/.cache/slate_tpu/plans.json."""
    env = os.environ.get("SLATE_TUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "slate_tpu",
                        "plans.json")


def _empty() -> dict:
    return {"version": SCHEMA_VERSION, "chips": {}}


def plan_key(n: int, dtype: str) -> str:
    """Cache entry key; dtype spellings normalize through the one shared
    helper (robust/precision.normalize_dtype) so "bf16" and "bfloat16"
    land on the same entry — a typo'd dtype raises instead of silently
    keying a fresh miss."""
    from ..robust.precision import normalize_dtype
    return f"n={int(n)},dtype={normalize_dtype(dtype)}"


def _parse_key(key: str) -> tuple[int, str]:
    n_part, dt_part = key.split(",", 1)
    if not (n_part.startswith("n=") and dt_part.startswith("dtype=")):
        raise ValueError(f"plan cache: bad entry key {key!r}")
    return int(n_part[2:]), dt_part[6:]


def validate_cache(obj) -> None:
    """Raise ValueError unless ``obj`` matches the version-1 schema."""
    if not isinstance(obj, dict):
        raise ValueError("plan cache: top level must be an object")
    if obj.get("version") != SCHEMA_VERSION:
        raise ValueError(
            f"plan cache: version must be {SCHEMA_VERSION}, "
            f"got {obj.get('version')!r}")
    chips = obj.get("chips")
    if not isinstance(chips, dict):
        raise ValueError("plan cache: 'chips' must be an object")
    if set(obj) - {"version", "chips"}:
        raise ValueError("plan cache: unknown top-level keys "
                         f"{sorted(set(obj) - {'version', 'chips'})}")
    for chip, ops in chips.items():
        if not isinstance(ops, dict):
            raise ValueError(f"plan cache: chip {chip!r} must map ops")
        for op, entries in ops.items():
            if op not in ALL_OPS:
                raise ValueError(f"plan cache: unknown op {op!r} "
                                 f"(known: {ALL_OPS})")
            if not isinstance(entries, dict):
                raise ValueError(f"plan cache: {chip}/{op} must be an "
                                 "object")
            for key, ent in entries.items():
                _parse_key(key)
                if not isinstance(ent, dict):
                    raise ValueError(
                        f"plan cache: {chip}/{op}/{key} must be an object")
                if ent.get("kernel") not in KERNELS:
                    raise ValueError(
                        f"plan cache: {chip}/{op}/{key} kernel must be one "
                        f"of {KERNELS}, got {ent.get('kernel')!r}")
                for field in ("nb", "bw"):
                    v = ent.get(field)
                    if not isinstance(v, int) or v <= 0:
                        raise ValueError(
                            f"plan cache: {chip}/{op}/{key} '{field}' must "
                            f"be a positive int, got {v!r}")
                g = ent.get("gflops")
                if g is not None and not isinstance(g, (int, float)):
                    raise ValueError(
                        f"plan cache: {chip}/{op}/{key} 'gflops' must be "
                        f"a number, got {g!r}")


def chip_kind() -> str:
    """Cache key for the local accelerator: the device kind string
    (e.g. 'tpu-v5-lite'), normalized; 'cpu' off-accelerator.  A backend
    that cannot start raises here: it is never read as a CPU."""
    import jax
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "") or dev.platform
    return str(kind).strip().lower().replace(" ", "-")


def load_cache(path: str | None = None) -> dict:
    """Read + validate the plan cache; a missing file is an empty cache."""
    path = path or cache_path()
    if not os.path.exists(path):
        return _empty()
    with open(path, encoding="utf-8") as fh:
        obj = json.load(fh)
    validate_cache(obj)
    return obj


def save_cache(obj: dict, path: str | None = None) -> str:
    """Validate + atomically persist the plan cache; returns the path."""
    validate_cache(obj)
    path = path or cache_path()
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(obj, fh, indent=1, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)
    reload()
    return path


def reload() -> None:
    """Drop the in-memory cache (next resolve_plan re-reads disk)."""
    global _CACHE, _CACHE_KEY
    with _LOCK:
        _CACHE = None
        _CACHE_KEY = None


def _cached() -> dict:
    global _CACHE, _CACHE_KEY
    path = cache_path()
    with _LOCK:
        if _CACHE is None or _CACHE_KEY != path:
            try:
                _CACHE = load_cache(path)
            except (ValueError, OSError) as e:
                warnings.warn(f"slate_tpu.tune: ignoring bad plan cache "
                              f"at {path}: {e}", stacklevel=3)
                _CACHE = _empty()
            _CACHE_KEY = path
        return _CACHE


def record_plan(op: str, n: int, dtype: str, plan: TilePlan,
                gflops: float | None = None, chip: str | None = None,
                path: str | None = None) -> str:
    """Persist one winning plan (autotuner/tests only — drivers resolve
    through resolve_plan)."""
    if op not in ALL_OPS:
        raise ValueError(f"unknown op {op!r} (known: {ALL_OPS})")
    obj = load_cache(path)
    ent = {"kernel": plan.kernel, "nb": int(plan.nb), "bw": int(plan.bw)}
    if gflops is not None:
        ent["gflops"] = float(gflops)
    chip = chip or chip_kind()
    obj.setdefault("chips", {}).setdefault(chip, {}).setdefault(
        op, {})[plan_key(n, dtype)] = ent
    return save_cache(obj, path)


@contextlib.contextmanager
def plan_override(op: str, plan: TilePlan):
    """Force ``resolve_plan(op, ...)`` to return ``plan`` (tests)."""
    if op not in ALL_OPS:
        raise ValueError(f"unknown op {op!r} (known: {ALL_OPS})")
    prev = _OVERRIDES.get(op)
    _OVERRIDES[op] = plan
    try:
        yield
    finally:
        if prev is None:
            _OVERRIDES.pop(op, None)
        else:
            _OVERRIDES[op] = prev


def _warn_removed_env() -> None:
    """SLATE_PALLAS is REMOVED: warn once per process that the variable
    is ignored, pointing at the supported seams."""
    global _WARNED
    if _WARNED or os.environ.get("SLATE_PALLAS") is None:
        return
    _WARNED = True
    warnings.warn(
        "SLATE_PALLAS has been removed and is IGNORED; kernel selection "
        "comes from the autotuner plan cache. Use plan_override() in "
        "tests or tune plans with `python -m slate_tpu.tune` "
        "(see docs/TUNING.md).", stacklevel=3)


def _lookup(op: str, n: int, dtype: str):
    """Nearest tuned plan by |log2(n/n')|, same dtype only.  Returns
    ``(TilePlan, dist)`` — dist 0.0 is an exact size hit — or None."""
    entries = _cached().get("chips", {}).get(chip_kind(), {}).get(op)
    if not entries:
        return None
    best_key, best_dist = None, None
    for key in entries:
        kn, kdt = _parse_key(key)
        if kdt != dtype:
            continue
        dist = abs(math.log2(max(n, 1) / max(kn, 1)))
        if best_dist is None or dist < best_dist:
            best_key, best_dist = key, dist
    if best_key is None:
        return None
    ent = entries[best_key]
    return TilePlan(ent["kernel"], int(ent["nb"]), int(ent["bw"])), best_dist


def resolve_plan(op: str, n: int, dtype: str = "float32") -> TilePlan:
    """The ONLY plan entry point for dispatch seams: the tuned
    ``TilePlan`` for ``op`` at problem size ``n`` (nearest tuned size
    for this chip kind wins; exact match preferred).  Arguments must be
    host-static (shape ints / dtype names) — the result is static
    configuration, safe inside jit-traced drivers.  Each resolution is
    noted into the open obs event frame (cache hit vs nearest-n
    distance), so production events audit plan usage."""
    from ..obs import events as _obs
    from ..robust.precision import normalize_dtype
    if op not in OPS and op not in (DIST_LOOKAHEAD_OP, OOC_PANEL_OP):
        raise ValueError(
            f"unknown op {op!r} "
            f"(known: {OPS + (DIST_LOOKAHEAD_OP, OOC_PANEL_OP)})")
    dtype = normalize_dtype(dtype)
    _warn_removed_env()
    ov = _OVERRIDES.get(op)
    if ov is not None:
        _obs.note_plan(op, int(n), dtype, ov.kernel, ov.nb,
                       "override", None)
        return ov
    found = _lookup(op, int(n), dtype)
    if found is None:
        plan, source, dist = XLA_PLAN, "default", None
    else:
        plan, dist = found
        source = "exact" if dist == 0.0 else "nearest"
    _obs.note_plan(op, int(n), dtype, plan.kernel, plan.nb, source, dist)
    return plan


def lookahead_depth(n: int, dtype: str = "float32") -> int:
    """Tuned comm/compute lookahead depth for the distributed kernels.

    The SINGLE accessor the dist wrappers consult (SEAM011 — same
    contract as resolve_plan, which it rides): host-static arguments,
    static int result.  Untuned chips resolve to the default XLA_PLAN
    (kernel "xla") and get depth 0, the bulk-synchronous bit-exact
    fallback; a tuned ``dist_lookahead`` entry with kernel "ring" turns
    on the pipeline at depth ``bw``, clamped to the supported 1..2."""
    plan = resolve_plan(DIST_LOOKAHEAD_OP, n, dtype)
    if plan.kernel != "ring":
        return 0
    return max(1, min(2, int(plan.bw)))


def ooc_panel_width(n: int, dtype: str = "float32",
                    default: int = 256) -> int:
    """Tuned out-of-core panel width for the TileMap streaming drivers.

    The SINGLE accessor potrf_ooc/getrf_ooc consult when the caller does
    not pin ``nb`` (SEAM011 — rides resolve_plan like lookahead_depth):
    host-static arguments, static int result.  Untuned chips resolve to
    the default XLA_PLAN and get ``default`` (clamped to n); a tuned
    ``ooc_panel`` entry contributes its measured ``nb``.  The width also
    feeds the resumed-run fingerprint (robust/checkpoint.py), so a tuned
    width change between save and resume refuses rather than silently
    changing the panel schedule."""
    plan = resolve_plan(OOC_PANEL_OP, n, dtype)
    width = plan.nb if plan is not XLA_PLAN else default
    return max(1, min(int(width), int(n)))


def serve_buckets(dtype: str = "float32") -> tuple[int, ...] | None:
    """Tuned serving bucket ladder for this chip, or None when untuned.

    The serving layer (slate_tpu.serve.bucket) calls THIS accessor — not
    the raw cache (SEAM011) — to override its default geometric ladder.
    Each ``serve_bucket`` entry recorded via :func:`record_plan` (op
    ``SERVE_BUCKET_OP``, ``n`` = the bucket edge, kernel/nb/bw ignored)
    contributes one rung; the returned tuple is sorted ascending."""
    from ..robust.precision import normalize_dtype
    dtype = normalize_dtype(dtype)
    entries = _cached().get("chips", {}).get(chip_kind(), {}).get(
        SERVE_BUCKET_OP)
    if not entries:
        return None
    rungs = sorted({n for n, dt in map(_parse_key, entries)
                    if dt == dtype})
    return tuple(rungs) or None
