"""Persistent compiled-executable cache for the serving layer.

A warmed server must never retrace and never re-allocate its
steady-state buffers; both properties live here:

- Executables are AOT-compiled once per key — ``(op, bucket_shape,
  dtype, options-fingerprint, batch)`` — and held for the life of the
  process.  A repeat batch is a dictionary hit: zero tracing (the PR 8
  retrace sentinel observes none) and zero compilation.
- The packed right-hand-side buffer is DONATED (``donate_argnums``)
  for the square solves: its shape and dtype equal the result's, so
  XLA reuses the allocation for the output and the steady-state submit
  loop runs allocation-neutral.  The packed operand ``A`` is NOT
  donated (the solve reads it after the factor phase), and least
  squares donates nothing (its result is (nb, kb), not b's (mb, kb) —
  the donation would be unusable).  This is the donation contract of
  docs/SERVING.md: callers hand the packed B to the executable and
  must not reuse that buffer afterwards.

Seam contract (slate-lint SEAM012, the serving mirror of SEAM011):
serve/ modules obtain executables ONLY through this module — no
``jax.jit`` / ``lower`` / ``compile`` anywhere else in the package —
so every compilation is accounted in :meth:`ExecutableCache.stats`
and surfaced in the per-batch obs events.
"""

from __future__ import annotations

import threading
import time

import jax

from ..obs import sentinel as _sentinel
from ..options import Options
from ..robust import faults as _faults
from . import batched as _batched


def options_fingerprint(opts: Options | None) -> tuple:
    """Canonical, hashable digest of an options dict for cache keying.
    Order-insensitive; enum keys and values collapse to their names so
    equivalent spellings ({Option.Abft: 'on'} vs Abft.On) agree."""
    items = []
    for k, v in (opts or {}).items():
        kn = getattr(k, "name", str(k))
        vn = getattr(v, "name", None) or str(v)
        items.append((kn, vn))
    return tuple(sorted(items))


class ExecutableCache:
    """In-process executable store with hit/miss accounting."""

    def __init__(self):
        self._lock = threading.Lock()
        self._exes: dict = {}
        self._hits = 0
        self._misses = 0
        self._compile_ms = 0.0

    def stats(self) -> dict:
        with self._lock:
            return {"entries": len(self._exes), "hits": self._hits,
                    "misses": self._misses,
                    "compile_ms": round(self._compile_ms, 3)}

    def executables(self) -> list:
        """The compiled executables held (inspection: chip_smoke.py reads
        which kernels they contain)."""
        with self._lock:
            return list(self._exes.values())

    def clear(self) -> None:
        with self._lock:
            self._exes.clear()
            self._hits = 0
            self._misses = 0
            self._compile_ms = 0.0

    def get_or_compile(self, op: str, bucket_shape: tuple, dtype,
                       batch: int, opts: Options | None = None,
                       device=None):
        """The compiled batch executable for one bucket, compiling on
        first use.  Returns ``(executable, hit)``.

        ``bucket_shape`` is ``(nb, kb)`` for square solves or
        ``(mb, nb, kb)`` for least squares; ``batch`` the (bucketed)
        problem count.  The executable maps packed stacks
        ``(a [batch, ...], b [batch, mb|nb, kb], sizes [batch] int32)``
        to ``(x, HealthInfo, escalated)`` with leading axis ``batch``,
        donating ``b``.  ``sizes`` carries per-problem live sizes as
        TRACED data — the ragged kernels consume it via scalar
        prefetch, the vmapped fallback ignores it — so mixed-size
        batches never alter the executable's static signature.

        ``device`` pins the executable to one accelerator (the device
        pool compiles the same jaxpr once per member; input specs carry
        a SingleDeviceSharding so dispatch needs no transfer fallback).
        Distinct devices are distinct cache keys, but two pool members
        backed by the SAME physical device (the CPU drill harness)
        share one entry."""
        dtype = str(jax.numpy.dtype(dtype))
        devkey = (None if device is None
                  else (device.platform, int(device.id)))
        key = (op, tuple(int(s) for s in bucket_shape), dtype,
               options_fingerprint(opts), int(batch), devkey)
        # chaos site: a mid-flight eviction forces the recompile path —
        # the serving layer must survive losing its warm executables
        if _faults.host_fire("serve_cache_evict") is not None:
            self.clear()
        with self._lock:
            exe = self._exes.get(key)
            if exe is not None:
                self._hits += 1
                return exe, True
        # compile OUTSIDE the lock (it can take seconds); a racing
        # duplicate compile is wasted work, not a correctness problem —
        # which is also where the chaos compile-stall site lives: the
        # serving watchdog must catch a wedged compile, and a stall
        # under the lock would be the CON003 bug class, not a test
        stall = _faults.host_fire("serve_compile_stall")
        if stall is not None:
            time.sleep(stall.delay_s)
        t0 = time.perf_counter()
        exe = self._compile(op, key[1], dtype, int(batch), opts, device)
        dt_ms = (time.perf_counter() - t0) * 1e3
        with self._lock:
            winner = self._exes.setdefault(key, exe)
            self._misses += 1
            self._compile_ms += dt_ms
        return winner, False

    @staticmethod
    def _compile(op: str, bucket_shape: tuple, dtype: str, batch: int,
                 opts: Options | None, device=None):
        if len(bucket_shape) == 3:
            mb, nb, kb = bucket_shape
        else:
            nb, kb = bucket_shape
            mb = nb
        sharding = (None if device is None
                    else jax.sharding.SingleDeviceSharding(device))
        a_spec = jax.ShapeDtypeStruct((batch, mb, nb), dtype,
                                      sharding=sharding)
        b_spec = jax.ShapeDtypeStruct((batch, mb, kb), dtype,
                                      sharding=sharding)
        s_spec = jax.ShapeDtypeStruct((batch,), "int32",
                                      sharding=sharding)
        fn = _batched.make_batched(op, opts)
        # donate b only where the result aliases it exactly: a square
        # solve's x has b's shape, least squares returns (nb, kb) != b
        # and the donation would be unusable (XLA warns, nothing reused)
        donate = (1,) if mb == nb else ()
        # one executable staging enters many same-shaped driver
        # boundaries; suppress those per-boundary sentinel feeds and
        # account the compile as ONE serve-level trace instead
        with _sentinel.suppressed():
            exe = jax.jit(fn, donate_argnums=donate).lower(
                a_spec, b_spec, s_spec).compile()
        _sentinel.record_trace(
            f"serve.{op}", f"{dtype}:b{batch}:"
            + "x".join(str(s) for s in bucket_shape))
        return exe


_DEFAULT = ExecutableCache()


def default_cache() -> ExecutableCache:
    """The process-wide cache shared by Servers that don't bring one."""
    return _DEFAULT
