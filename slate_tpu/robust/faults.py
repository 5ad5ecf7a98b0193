"""Deterministic, seeded fault injection at named driver sites.

Tile-level fault sensitivity is the production concern for long-running
accelerator kernels ("Design in Tiles" / "Ragged Paged Attention",
PAPERS.md): a single corrupted tile in a factorization propagates into a
finite-but-wrong solution unless detection is explicit.  This module makes
those faults reproducible on CPU so the detection and recovery paths in
:mod:`health` / :mod:`recovery` are testable in tier-1.

Sites are trace-time gates: :func:`maybe_corrupt` is a no-op (returns its
input untouched, traces nothing) unless a plan for that site is active via
the :func:`inject` context manager.  Because activation is decided when the
computation is TRACED, jitted functions must be traced inside the context —
a function compiled without faults will not retroactively corrupt.

Named sites (see docs/ROBUSTNESS.md):

=================  =====================================================
``input``          driver inputs (A's tiles) before factorization
``post_panel``     a just-factored panel, before the trailing update
``post_collective`` a collective result (SUMMA accumulator, the psum'd
                   U12 row in dist_lu, the broadcast panel in dist_chol)
``solve``          the computed solution X
``post_stage1``    the band matrix produced by stage 1 of the two-stage
                   reductions (he2hb / ge2tb), before stage 2 consumes it
``post_chase``     the tri/bidiagonal output of the stage-2 bulge chase
                   (hb2st / tb2bd), before the small-problem eigensolver
``post_secular``   the secular-equation roots inside the stedc D&C merge
``post_backtransform`` the accumulated eigen/singular vectors after the
                   stage-1 back-transform (unmtr_he2hb / unmbr_ge2tb)
``post_rbt``       the butterfly-transformed matrix U^T A V, before the
                   speculative NoPiv factorization consumes it (a strike
                   here yields a finite-but-wrong fast-path solve that
                   only the a-posteriori residual certificate catches)
=================  =====================================================

Payloads: ``nan``, ``inf``, and ``bitflip`` — a high-exponent-bit flip
(value scaled by 2^100), the silent-data-corruption payload that stays
FINITE and is only caught by pivot-growth / residual / checksum checks.

Plans are PERSISTENT by default: the corruption re-fires every time the
site is reached while the plan is active (a stuck-at fault).  Pass
``transient=True`` for single-shot SDC semantics: the strike fires at most
once per :func:`inject` activation, decided at RUN time through an ordered
host callback — so a shape/dtype retrace of the same jitted driver inside
one ``inject`` block neither re-fires the strike nor loses it, and a
recovery retry (e.g. heev escalating Auto -> DC -> QR) sees clean data on
the second attempt, which is exactly how a transient bit-flip behaves in
production.

Strikes can be confined to one tile of the site's array with
``FaultPlan(tile=(i, j), nb=...)``: for 4D tile arrays ``[.., .., mb, nb]``
the strike lands inside ``x[i, j]``; for 3D tile stacks ``[T, mb, nb]``
inside ``x[i]``; for 2D arrays inside the ``nb x nb`` block at block-row
``i``, block-column ``j`` (``nb`` required).  A tile index outside the
array is a miss (no-op) — a persistent plan aimed at the last panel tile
therefore lands exactly once across a blocked factorization's shrinking
panels.  ``tile=None`` keeps the whole-array behavior.
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import io_callback

SITES = ("input", "post_panel", "post_collective", "solve",
         "post_stage1", "post_chase", "post_secular", "post_backtransform",
         "post_rbt")
#: HOST-side serving-layer chaos sites (docs/SERVING.md "Survival"):
#: consumed via :func:`host_fire` by serve/server.py and serve/cache.py,
#: never woven into a trace —
#:
#: ``serve_flush_delay``    the flush loop sleeps ``delay_s`` before
#:                          executing (ages the batch: deadline sheds
#:                          and watermark behavior become testable)
#: ``serve_compile_stall``  the executable cache sleeps ``delay_s``
#:                          before compiling a miss (a stuck compile:
#:                          what the serving watchdog must catch)
#: ``serve_cache_evict``    the executable cache drops every entry at
#:                          the next lookup (mid-flight eviction: the
#:                          recompile path under load)
#: ``serve_device_fail``    one pool member's dispatch fails: kind
#:                          ``nan`` poisons the batch output (the
#:                          non-finite sentinel path), any other kind
#:                          raises at dispatch (the exception sentinel
#:                          path).  ``FaultPlan(device=i)`` confines the
#:                          strike to pool member ``i``; transient plans
#:                          kill the device once, persistent plans keep
#:                          it dead until the plan deactivates (the
#:                          canary probes it back in)
#: ``serve_device_slow``    one pool member sleeps ``delay_s`` around a
#:                          dispatch — past the pool's per-dispatch
#:                          deadline this reads as a wedged device and
#:                          the batch fails over to a survivor
#: ``serve_canary_flake``   the quarantine canary probe fails (the sick
#:                          device is still sick): readmission is
#:                          refused and the next probe is rescheduled
SERVE_SITES = ("serve_flush_delay", "serve_compile_stall",
               "serve_cache_evict", "serve_device_fail",
               "serve_device_slow", "serve_canary_flake")
#: HOST-side durability chaos sites (docs/ROBUSTNESS.md "Durable jobs"):
#: consumed via :func:`host_fire` by robust/checkpoint.py and the
#: out-of-core tile map in core/storage.py —
#:
#: ``ckpt_torn_write``   the checkpoint payload write is truncated
#:                       mid-file after the manifest digest was computed
#:                       (a crash/preemption landing between write and
#:                       fsync): resume must refuse with reason "torn"
#: ``ckpt_stale_read``   the manifest writer re-reads a stale payload —
#:                       the payload write is skipped but the manifest is
#:                       republished against the old bytes: resume must
#:                       refuse with reason "stale"
#: ``ooc_copy_stall``    the tile map sleeps ``delay_s`` around a
#:                       host<->device panel copy (a congested PCIe/DMA
#:                       path): out-of-core results must stay correct,
#:                       merely late
CKPT_SITES = ("ckpt_torn_write", "ckpt_stale_read", "ooc_copy_stall")
#: every host-side site host_fire will serve
HOST_SITES = SERVE_SITES + CKPT_SITES
KINDS = ("nan", "inf", "bitflip")

# flipping exponent bit 6 of an O(1) value: finite, wildly wrong
_BITFLIP_SCALE = 2.0 ** 100


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """One corruption: ``count`` elements of the first array that flows
    through ``site``, positions drawn deterministically from ``seed``."""

    site: str
    kind: str = "nan"
    seed: int = 0
    count: int = 1
    # transient faults strike once per inject() activation (single-shot
    # SDC); the default is a stuck-at fault that re-fires on every pass.
    transient: bool = False
    # confine the strike to one tile: (block-row, block-col), or None for
    # the whole array.  ``nb`` gives the block edge for 2D arrays.
    tile: tuple[int, int] | None = None
    nb: int = 0
    # host-side serving sites only: how long the chaos sleep lasts
    delay_s: float = 0.0
    # host-side device-pool sites only: confine the strike to one pool
    # member index (None = any member that reaches the site first)
    device: int | None = None

    def __post_init__(self):
        if self.site not in SITES and self.site not in HOST_SITES:
            raise ValueError(f"unknown fault site {self.site!r}; "
                             f"sites: {SITES + HOST_SITES}")
        if self.kind not in KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; "
                             f"kinds: {KINDS}")
        if self.tile is not None:
            if (len(self.tile) != 2
                    or any(int(t) != t or t < 0 for t in self.tile)):
                raise ValueError(f"tile must be two non-negative block "
                                 f"indices, got {self.tile!r}")
        if self.device is not None and (int(self.device) != self.device
                                        or self.device < 0):
            raise ValueError(f"device must be a non-negative pool member "
                             f"index, got {self.device!r}")


_ACTIVE: dict[str, FaultPlan] = {}
# per-inject() activation bookkeeping for transient plans: which
# activation a site's plan belongs to, and which (activation, site) pairs
# have already struck.  Consumption is recorded at RUN time (io_callback),
# so retraces of the same driver share one consumption record.
_EPOCH = 0
_PLAN_EPOCH: dict[str, int] = {}
_SPENT: set[tuple[int, str]] = set()


@contextlib.contextmanager
def inject(*plans: FaultPlan):
    """Activate fault plans for the dynamic extent of the block.  Traced
    computations pick up the corruption only if traced inside."""
    global _EPOCH
    saved = dict(_ACTIVE)
    saved_epoch = dict(_PLAN_EPOCH)
    _EPOCH += 1
    epoch = _EPOCH
    try:
        for p in plans:
            _ACTIVE[p.site] = p
            _PLAN_EPOCH[p.site] = epoch
        yield
    finally:
        _ACTIVE.clear()
        _ACTIVE.update(saved)
        _PLAN_EPOCH.clear()
        _PLAN_EPOCH.update(saved_epoch)
        _SPENT.difference_update({k for k in _SPENT if k[0] == epoch})


def active(site: str) -> FaultPlan | None:
    return _ACTIVE.get(site)


def host_fire(site: str, device: int | None = None) -> FaultPlan | None:
    """Consume an active HOST-side chaos plan at ``site``.

    Unlike :func:`maybe_corrupt` this never touches a trace: the serving
    and durability layers call it from plain host code (the flush loop,
    the executable cache, the checkpoint writer, the tile-map copy path)
    and act on the returned plan (sleep, evict, tear a write).  Transient
    plans fire at most once per :func:`inject` activation — one stalled
    compile or one torn checkpoint, not a permanently broken disk.

    ``device`` is the calling pool member's index (serve/pool.py): a
    plan declaring ``FaultPlan(device=i)`` fires only when member ``i``
    reaches the site — a miss neither fires nor consumes, so a transient
    kill-device-1 plan cannot be eaten by member 0 passing by first."""
    if site not in HOST_SITES:
        return None
    plan = _ACTIVE.get(site)
    if plan is None:
        return None
    if plan.device is not None and plan.device != device:
        return None
    if plan.transient:
        epoch = _PLAN_EPOCH.get(site, 0)
        if (epoch, site) in _SPENT:
            return None
        _SPENT.add((epoch, site))
    return plan


def poisson_workload(seed: int, problems: int, rate_hz: float, sizes,
                     nrhs: int = 2, dtype=np.float32,
                     ops=("solve", "chol_solve", "least_squares_solve")):
    """Deterministic seeded open-loop serving workload: ``problems``
    mixed-size requests with exponential (Poisson-process) inter-arrival
    gaps at ``rate_hz``.  Same seed -> same arrival times, sizes and
    operand values, so overload/shed/quarantine behavior is reproducible
    on CPU — the chaos harness's load generator (the survival tests
    replay it).

    Returns ``[(t_arrival_s, op, a, b)]`` sorted by arrival; matrices
    are well-conditioned (diagonally dominated / SPD-shifted), so every
    admitted request should serve healthy unless chaos intervenes."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / max(rate_hz, 1e-9),
                                         size=problems))
    out = []
    for i in range(problems):
        n = int(sizes[i % len(sizes)])
        op = ops[i % len(ops)]
        if op == "least_squares_solve":
            a = rng.standard_normal((n + 8, n)).astype(dtype)
            b = rng.standard_normal((n + 8, nrhs)).astype(dtype)
        else:
            a = rng.standard_normal((n, n)).astype(dtype)
            if op == "chol_solve":
                a = (a @ a.T / n + np.eye(n, dtype=dtype)).astype(dtype)
            else:
                a = a + np.eye(n, dtype=dtype) * 4.0
            b = rng.standard_normal((n, nrhs)).astype(dtype)
        out.append((float(arrivals[i]), op, a, b))
    return out


def _strike_flat(flat, size: int, plan: FaultPlan):
    """Corrupt ``plan.count`` deterministic positions of a flat array."""
    k = min(plan.count, size)
    idx = jnp.asarray(np.random.default_rng(plan.seed).choice(
        size, size=k, replace=False))
    if plan.kind == "nan":
        return flat.at[idx].set(jnp.nan)
    if plan.kind == "inf":
        return flat.at[idx].set(jnp.inf)
    # bitflip: exponent-bit flip — finite but wildly wrong
    return flat.at[idx].multiply(_BITFLIP_SCALE)


def corrupt(x, plan: FaultPlan):
    """Apply ``plan`` to array ``x`` (pure, jit-safe): deterministic flat
    positions from the seed, payload per ``plan.kind``.

    Positions are drawn with HOST numpy at trace time (seed, count and
    x.size are all static), so the corruption lowers to constant-index
    scatters — no jax.random traffic inside jit/shard_map, where this
    jax's replication checker rejects the shuffle primitives.

    With ``plan.tile`` set, the strike is confined to that tile of ``x``
    (see module docstring); an out-of-range tile index is a miss."""
    x = jnp.asarray(x)
    if x.size == 0 or not jnp.issubdtype(x.dtype, jnp.inexact):
        return x
    if plan.tile is None:
        flat = _strike_flat(x.reshape(-1), x.size, plan)
        return flat.reshape(x.shape)
    ti, tj = plan.tile
    if x.ndim == 4:
        if ti >= x.shape[0] or tj >= x.shape[1]:
            return x
        sub = x[ti, tj]
        sub = _strike_flat(sub.reshape(-1), sub.size, plan).reshape(sub.shape)
        return x.at[ti, tj].set(sub)
    if x.ndim == 3:
        if ti >= x.shape[0]:
            return x
        sub = x[ti]
        sub = _strike_flat(sub.reshape(-1), sub.size, plan).reshape(sub.shape)
        return x.at[ti].set(sub)
    if x.ndim == 2:
        if plan.nb <= 0:
            # slate-lint: disable=TRC006 -- plan validation on static config (nb is a host int): raises at trace time, before any tracer exists
            raise ValueError("FaultPlan.tile on a 2D array requires nb > 0")
        r0, c0 = ti * plan.nb, tj * plan.nb
        if r0 >= x.shape[0] or c0 >= x.shape[1]:
            return x
        sub = x[r0:r0 + plan.nb, c0:c0 + plan.nb]
        sub = _strike_flat(sub.reshape(-1), sub.size, plan).reshape(sub.shape)
        return x.at[r0:r0 + sub.shape[0], c0:c0 + sub.shape[1]].set(sub)
    # slate-lint: disable=TRC006 -- dispatch on static ndim: unsupported ranks fail at trace time by design
    raise ValueError(f"FaultPlan.tile targeting needs a 2D/3D/4D array, "
                     f"got ndim={x.ndim}")


def maybe_corrupt(site: str, x):
    """The site hook drivers call: identity unless a plan is active.

    A ``transient`` plan strikes at most once per :func:`inject`
    activation.  Consumption is decided when the computation RUNS, not
    when it is traced: the corrupted and clean values are both woven into
    the trace and an ordered host callback picks one per execution.  A
    retrace under the same activation therefore cannot re-fire a spent
    strike, and tracing at a throwaway shape cannot eat the strike meant
    for the real one."""
    plan = _ACTIVE.get(site)
    if plan is None:
        return x
    if not plan.transient:
        return corrupt(x, plan)
    x = jnp.asarray(x)
    if x.size == 0 or not jnp.issubdtype(x.dtype, jnp.inexact):
        return x
    epoch = _PLAN_EPOCH.get(site, 0)

    def _consume():
        if _PLAN_EPOCH.get(site) != epoch or (epoch, site) in _SPENT:
            return np.asarray(False)
        _SPENT.add((epoch, site))
        return np.asarray(True)

    fire = io_callback(_consume, jax.ShapeDtypeStruct((), np.bool_),
                       ordered=True)
    return jnp.where(fire, corrupt(x, plan), x)
