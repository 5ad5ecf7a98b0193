"""Autotuner: measure candidate (kernel, nb, bw) plans, persist winners.

One measurement = build a representative problem for the op at size n,
jit the candidate's code path, warm it up (compile excluded), then take
the best of ``iters`` timed runs.  Winners go to the plan cache via
plans.record_plan; dispatch seams read them back with resolve_plan.

Off-TPU the Pallas candidates run in interpret mode — functionally
identical, uselessly slow — so tuning there just confirms the XLA
default.  Re-tune on a new chip with ``python -m slate_tpu.tune``
(docs/TUNING.md).
"""

from __future__ import annotations

import time

from .plans import OPS, TilePlan, record_plan

CANDIDATE_NB = (128, 256, 512)
CANDIDATE_BW = (8, 16)
_SEED = 0


def candidates(op: str, n: int, dtype: str = "float32") -> list[TilePlan]:
    """The search space for one (op, n, dtype): always the XLA fallback,
    plus every shape-legal Pallas (nb, bw) pair.  The batched ragged
    panels additionally take bf16 storage (f32 accumulation inside the
    kernels — internal/pallas_*.py), so those three ops sweep Pallas
    candidates for bf16 too; every other kernel is f32-only."""
    if op not in OPS:
        raise ValueError(f"unknown op {op!r} (known: {OPS})")
    plans = [TilePlan("xla", min(n, 512), 8)]
    batch = op in ("batch_potrf", "batch_getrf", "batch_geqrf")
    if dtype != "float32" and not (batch and dtype == "bfloat16"):
        return plans
    if op in ("potrf_tile", "lu_select"):
        nbs = [n] if n % 128 == 0 and 128 <= n <= 1024 else []
    else:
        nbs = [nb for nb in CANDIDATE_NB if nb <= n and n % nb == 0]
    for nb in nbs:
        if op in ("geqrf_panel", "batch_geqrf"):  # no bw knob in QR kernels
            plans.append(TilePlan("pallas", nb, 8))
            continue
        plans.extend(TilePlan("pallas", nb, bw) for bw in CANDIDATE_BW
                     if nb % bw == 0)
    return plans


def _problem(op: str, plan: TilePlan, n: int, dtype: str = "float32"):
    """Returns (thunk, flops): a zero-arg jitted candidate runner and the
    nominal flop count it performs.  ``dtype`` reaches only the batched
    ops (the single-shot kernels are f32-only, see candidates()); their
    XLA fallbacks compute through f32 exactly as the serving route's
    promote/demote emulation does, so the measurement is honest."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..internal import getrf, qr, trsm
    from ..internal.pallas_chol import chol_panel_fused, chol_tile_pallas
    from ..internal.pallas_lu import lu_panel_fused, lu_select_pallas
    from ..internal.pallas_qr import qr_panel_pallas
    from ..util.runtime import pallas_interpret

    rng = np.random.default_rng(_SEED)
    interp = pallas_interpret()
    nb = min(plan.nb, n)
    pallas = plan.kernel == "pallas"

    if op == "potrf_tile":
        g = rng.standard_normal((n, n)).astype(np.float32)
        a = jnp.asarray(g @ g.T + n * np.eye(n, dtype=np.float32))
        if pallas:
            fn = jax.jit(lambda x: chol_tile_pallas(x, bw=plan.bw,
                                                    interpret=interp))
        else:
            fn = jax.jit(jnp.linalg.cholesky)
        return (lambda: fn(a)), n ** 3 / 3

    if op == "potrf_panel":
        g = rng.standard_normal((n, n)).astype(np.float32)
        a = g @ g.T + n * np.eye(n, dtype=np.float32)
        llead = np.linalg.cholesky(a[:nb, :nb]).astype(np.float32)
        col = jnp.asarray(a[:, :nb])
        left = jnp.asarray(np.tile(llead, (n // nb, 1)))
        lead = jnp.asarray(llead.T)
        if pallas:
            fn = jax.jit(lambda c, lf, ld: chol_panel_fused(
                c, lf, ld, bw=plan.bw, interpret=interp))
        else:
            def fn(c, lf, ld):
                upd = c - lf @ ld
                lkk = jnp.linalg.cholesky(upd[:nb])
                return upd, jnp.concatenate(
                    [lkk, upd[nb:] @ trsm.tri_inv_lower(lkk).T])
            fn = jax.jit(fn)
        flops = 2 * n * nb * nb + nb ** 3 / 3 + (n - nb) * nb ** 2
        return (lambda: fn(col, left, lead)), flops

    if op == "getrf_panel":
        p = rng.standard_normal((n, nb)).astype(np.float32)
        p[:nb] += nb * np.eye(nb, dtype=np.float32)
        panel = jnp.asarray(p)
        if pallas:
            fn = jax.jit(lambda x: lu_panel_fused(x, bw=plan.bw,
                                                  interpret=interp))
        else:
            fn = jax.jit(lambda x: getrf.panel_lu_nopiv(x)[0])
        return (lambda: fn(panel)), n * nb ** 2

    if op == "lu_select":
        chunk = jnp.asarray(rng.standard_normal((n, nb)).astype(np.float32))
        if pallas:
            fn = jax.jit(lambda x: lu_select_pallas(x, bw=plan.bw,
                                                    interpret=interp))
        else:
            fn = jax.jit(lambda x: jax.lax.linalg.lu(x)[2][:nb])
        return (lambda: fn(chunk)), n * nb ** 2

    if op == "geqrf_panel":
        panel = jnp.asarray(rng.standard_normal((n, nb)).astype(np.float32))
        if pallas:
            fn = jax.jit(lambda x: qr_panel_pallas(x, interpret=interp))
        else:
            fn = jax.jit(qr.householder_panel_blocked)
        return (lambda: fn(panel)), 2 * n * nb ** 2

    if op in ("batch_potrf", "batch_getrf", "batch_geqrf"):
        # Representative ragged bucket: B identity-augmented slots whose
        # live sizes sweep the bucket (serve/server.py's packing), flops
        # counted over LIVE work only so both routes report waste-adjusted
        # throughput against the same denominator.
        from ..internal import batched

        B = 8
        sizes = np.asarray([max(1, ((i + 1) * n) // B) for i in range(B)],
                           np.int32)
        a = np.zeros((B, n, n), np.float32)
        for i, s in enumerate(sizes):
            s = int(s)
            g = rng.standard_normal((s, s)).astype(np.float32)
            if op == "batch_potrf":
                a[i, :s, :s] = g @ g.T + s * np.eye(s, dtype=np.float32)
            elif op == "batch_getrf":
                a[i, :s, :s] = g + s * np.eye(s, dtype=np.float32)
            else:
                a[i, :s, :s] = g
            idx = np.arange(s, n)
            a[i, idx, idx] = 1.0                 # identity augmentation
        live = sizes.astype(np.float64)
        if op == "batch_geqrf":
            # problem-granular raggedness: live slots factor the whole
            # bucket panel (padding columns own real reflectors), slot 0
            # is a zero filler the kernel passes through
            sizes = np.where(np.arange(B) == 0, 0, n).astype(np.int32)
            a[0] = 0.0
            flops = 2 * n ** 3 / 3 * int((sizes > 0).sum())
        elif op == "batch_potrf":
            flops = float((live ** 3).sum()) / 3
        else:
            flops = 2 * float((live ** 3).sum()) / 3
        aj = jnp.asarray(a).astype(dtype)
        sj = jnp.asarray(sizes)
        f32 = lambda x: x.astype(jnp.float32)             # noqa: E731
        if op == "batch_potrf":
            if pallas:
                fn = jax.jit(lambda x, s: batched.batch_potrf(
                    x, s, nb=nb, bw=plan.bw, interpret=interp)[0])
            else:
                fn = jax.jit(lambda x, s: jax.vmap(jnp.linalg.cholesky)(
                    f32(x)).astype(x.dtype))
        elif op == "batch_getrf":
            if pallas:
                fn = jax.jit(lambda x, s: batched.batch_getrf(
                    x, s, nb=nb, bw=plan.bw, interpret=interp))
            else:
                fn = jax.jit(lambda x, s: jax.vmap(
                    lambda xi: jax.lax.linalg.lu(xi)[0])(
                        f32(x)).astype(x.dtype))
        else:
            if pallas:
                fn = jax.jit(lambda x, s: batched.batch_geqrf(
                    x, s, nb=nb, interpret=interp)[0])
            else:
                fn = jax.jit(lambda x, s: jax.vmap(
                    lambda xi: jnp.linalg.qr(xi, mode="r"))(
                        f32(x)).astype(x.dtype))
        return (lambda: fn(aj, sj)), flops

    raise ValueError(f"unknown op {op!r}")


def measure(op: str, plan: TilePlan, n: int, iters: int = 3,
            dtype: str = "float32") -> float:
    """GFLOP/s of one candidate (best of ``iters``, compile excluded)."""
    import jax

    thunk, flops = _problem(op, plan, n, dtype)
    jax.block_until_ready(thunk())               # compile + warm caches
    best = float("inf")
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        best = min(best, time.perf_counter() - t0)
    return flops / best / 1e9


def sweep(op: str, n: int, dtype: str = "float32", iters: int = 3):
    """Yield (plan, gflops) for every candidate of (op, n, dtype)."""
    for plan in candidates(op, n, dtype):
        yield plan, measure(op, plan, n, iters=iters, dtype=dtype)


def tune_op(op: str, n: int, dtype: str = "float32", iters: int = 3,
            persist: bool = True) -> tuple[TilePlan, float]:
    """Measure all candidates, persist the winner, return it."""
    best_plan, best_gf = None, -1.0
    for plan, gf in sweep(op, n, dtype, iters=iters):
        if gf > best_gf:
            best_plan, best_gf = plan, gf
    if persist:
        record_plan(op, n, dtype, best_plan, gflops=best_gf)
    return best_plan, best_gf


def tune_all(ns=(256, 512, 1024), ops=OPS, dtype: str = "float32",
             iters: int = 3, persist: bool = True):
    """Tune every (op, n) pair; returns {(op, n): (plan, gflops)}."""
    out = {}
    for op in ops:
        for n in ns:
            out[(op, n)] = tune_op(op, n, dtype, iters=iters,
                                   persist=persist)
    return out


# -------------------------------------------------- serve_bucket ladder
#
# Not a kernel sweep: the ``serve_bucket`` pseudo-op records the bucket
# LADDER for this chip from a recorded request-size histogram.  Rungs
# are chosen to minimize total padded area (sum over requests of
# rung^2) by dynamic programming over the distinct tile-rounded sizes,
# then persisted one cache entry per rung so serve.bucket.default_ladder
# picks them up through tune.serve_buckets.


def serve_ladder_from_sizes(sizes, max_rungs: int = 8,
                            base: int = 32) -> tuple:
    """Padded-area-optimal bucket ladder (<= ``max_rungs`` rungs) for a
    request-size sample.  Sizes round up to ``base`` multiples (the tile
    edge — finer rungs cannot change the packed shapes); each rung must
    be one of the distinct rounded sizes and the top rung covers the
    largest, so every recorded request buckets without doubling.

    Two callers: the offline ``--serve-hist`` CLI fit (persisted as the
    ``serve_bucket`` plan) and the live server's online retune
    (``serve.Server.retune_now`` / the background retune tick), which
    hot-swaps the fitted ladder per process without persisting —
    docs/TUNING.md "Hot-swap"."""
    import collections

    pad = [max(base, -(-int(s) // base) * base) for s in sizes if int(s) > 0]
    if not pad:
        raise ValueError("serve_ladder_from_sizes: no positive sizes")
    hist = collections.Counter(pad)
    edges = sorted(hist)
    ne = len(edges)
    if ne <= max_rungs:
        return tuple(edges)
    # cost[lo][hi]: every request in edges[lo..hi] served at edges[hi]
    cost = [[0.0] * ne for _ in range(ne)]
    for lo in range(ne):
        cnt = 0
        for hi in range(lo, ne):
            cnt += hist[edges[hi]]
            cost[lo][hi] = cnt * edges[hi] ** 2
    inf = float("inf")
    dp = [[inf] * ne for _ in range(max_rungs + 1)]
    cut = [[-1] * ne for _ in range(max_rungs + 1)]
    for hi in range(ne):
        dp[1][hi] = cost[0][hi]
    for r in range(2, max_rungs + 1):
        for hi in range(r - 1, ne):
            for mid in range(r - 2, hi):
                c = dp[r - 1][mid] + cost[mid + 1][hi]
                if c < dp[r][hi]:
                    dp[r][hi] = c
                    cut[r][hi] = mid
    best_r = min(range(1, max_rungs + 1), key=lambda r: dp[r][ne - 1])
    rungs, r, hi = [], best_r, ne - 1
    while r > 1:
        rungs.append(edges[hi])
        hi = cut[r][hi]
        r -= 1
    rungs.append(edges[hi])
    return tuple(sorted(rungs))


def ladder_waste(sizes, ladder) -> float:
    """Padding waste (1 - live/padded area) of serving ``sizes`` square
    problems on ``ladder`` (a serve.bucket.BucketLadder)."""
    live = padded = 0
    for s in sizes:
        s = int(s)
        if s <= 0:
            continue
        b = ladder.bucket_for(s)
        live += s * s
        padded += b * b
    return 1.0 - live / padded if padded else 0.0


def tune_serve_buckets(sizes, dtype: str = "float32", max_rungs: int = 8,
                       persist: bool = True):
    """Fit a bucket ladder to a request-size histogram and persist it as
    ``serve_bucket`` plan-cache entries (one per rung).  Returns
    ``(rungs, waste_geometric, waste_tuned)`` so the CLI can report the
    padding-waste improvement over the geometric default."""
    from ..serve import bucket as _bucket
    from .plans import SERVE_BUCKET_OP, XLA_PLAN

    rungs = serve_ladder_from_sizes(sizes, max_rungs=max_rungs)
    w_geo = ladder_waste(sizes, _bucket.geometric_ladder())
    w_tuned = ladder_waste(sizes, _bucket.BucketLadder(rungs, "tuned"))
    if persist:
        for r in rungs:
            record_plan(SERVE_BUCKET_OP, int(r), dtype, XLA_PLAN)
    return rungs, w_geo, w_tuned
