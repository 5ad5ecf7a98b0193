"""LU drivers: getrf (partial pivot / nopiv / tournament), getrs, gesv,
getri.

Analog of the reference's LU chain (ref: src/getrf.cc:23-240,
src/getrf_nopiv.cc, src/getrf_tntpiv.cc:455, src/getrs.cc, src/gesv.cc,
src/getri.cc / src/getriOOP.cc; method dispatch src/gesv.cc + method.hh
MethodLU).

The factorization result is ``LUFactors``: one matrix whose strictly-lower
part is unit-L and upper part U (exactly the reference's overwritten-A
convention) plus a global row-permutation vector ``perm`` with
``A[perm] = L @ U`` — the composition of the reference's per-panel Pivot
lists (ref: getrf.cc pivots bcast :112-117).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core.matrix import Matrix, TriangularMatrix
from ..core.storage import TileStorage
from ..exceptions import SlateSingularError, slate_error
from ..ops.elementwise import entry_mask
from ..options import (ErrorPolicy, MethodLU, Option, Options, Target,
                       get_option, resolve_abft, resolve_target,
                       select_lu_method)
from ..parallel.dist_lu import dist_getrf
from ..robust import abft as _abft
from ..robust import faults
from ..robust import health as _health
from ..types import Diag, Op, Uplo
from ..util.trace import annotate, span
from .blas3 import as_root_general, trsm


class LUFactors(NamedTuple):
    """L\\U packed in one matrix + row permutation (A[perm] = L U)."""
    LU: Matrix
    perm: jax.Array

    def lower(self) -> TriangularMatrix:
        return TriangularMatrix._from_view(self.LU, Uplo.Lower, Diag.Unit)

    def upper(self) -> TriangularMatrix:
        return TriangularMatrix._from_view(self.LU, Uplo.Upper)


def _getrf_dense_blocked(a, nb: int, method: str, tau: float = 1.0,
                         mpt: int = 4, depth: int = 2, abft: bool = False):
    """Blocked right-looking LU, statically-shaped panels (unrolled).

    Panel factor delegates to XLA's native pivoted LU (the analog of the
    reference's lapack panel kernel); the nopiv and tournament panels
    route through internal/getrf.py's tuned seams, which dispatch to the
    fused Pallas panel kernels (internal/pallas_lu.py) when the plan
    cache selects them (slate_tpu.tune, docs/TUNING.md).  Each step's
    row exchange moves whole rows of ``a`` in place, across every
    column, with no slice of a block left or right of the panel to
    write back.  Partial pivoting, threshold pivoting and the
    tournament placement are all products of the w transpositions
    (j, p_j), j < w, p_j >= j, so a row that lands below the top w of
    the panel's rows came from the top w: the w new top rows are
    gathered, the w old ones scattered to where ``perm`` sends them,
    and the new top block written as one contiguous block.  The move
    comes before the factored panel is written: it permutes the
    panel's columns with the rest of each row, and the write then
    overwrites them with ``lu``, already in pivot order (writing ``lu``
    first would permute it twice).  The U12 solve is one MXU gemm
    against the inverted unit-L11 (internal/trsm.py tri_inv_lower) —
    ref: getrf.cc:174-215 trailing task.  ``tau`` < 1 switches to
    threshold pivoting (Option.PivotThreshold); ``mpt``
    (Option.MaxPanelThreads) splits the tournament panel into ~mpt
    independent row blocks (the analog of panel threads: more threads =
    more, smaller blocks) and ``depth`` (Option.Depth) is the
    reduction-tree fan-in.

    With ``abft`` (the resolved Option.Abft boolean) every step carries
    Huang-Abraham checksums (robust/abft.py): the packed panel is
    verified against its pre-factor input, the U12 solve against the
    pre-solve row's checksums, and the trailing update against the
    expected checksum deltas — each an O(n^2)-per-step check that
    locates and repairs a single corrupted element in place.  Returns
    ``(factor, perm, AbftCounts)``."""
    from ..internal.getrf import (panel_lu, panel_lu_nopiv,
                                  panel_lu_threshold, panel_lu_tournament)
    from ..internal.trsm import tri_inv_lower
    m, n = a.shape
    kmax = min(m, n)
    perm_g = jnp.arange(m)
    counts = _abft.zero_counts()
    for k0 in range(0, kmax, nb):
        k1 = min(k0 + nb, kmax)
        w = k1 - k0
        kt = k0 // nb
        with span("slate.getrf/panel"):
            pan = a[k0:, k0:k1]
            if method == "nopiv":
                lu, perm = panel_lu_nopiv(pan)
            elif method == "tntpiv":
                bh = pan.shape[0]
                br = max(nb, (-(-bh // (mpt * nb))) * nb)
                lu, perm = panel_lu_tournament(pan, block_rows=br,
                                               arity=depth)
            elif tau < 1.0:
                lu, perm = panel_lu_threshold(pan, tau)
            else:
                lu, perm = panel_lu(pan)
            lu = faults.maybe_corrupt("post_panel", lu)
            if abft:
                lu, det, cor, pi, _ = _abft.lu_panel_check(pan, lu, perm,
                                                           n_ctx=m)
                counts = _abft.add_counts(counts, _abft.count_event(
                    det, cor, kt + pi // nb, kt))
        if method != "nopiv":
            with span("slate.getrf/swap"):
                # old top rows that stay in the top block are written
                # twice; the contiguous write of the new top block wins
                dest = jnp.argsort(perm)[:w]
                top = a[k0 + perm[:w]]
                a = a.at[k0 + dest].set(a[k0:k1], unique_indices=True)
                a = lax.dynamic_update_slice_in_dim(a, top, k0, 0)
                perm_g = perm_g.at[k0:].set(perm_g[k0:][perm])
        with span("slate.getrf/panel"):
            a = a.at[k0:, k0:k1].set(lu)
        if k1 < n:
            with span("slate.getrf/trsm"):
                l11 = lu[:w, :w]
                r12 = a[k0:k1, k1:]
                u12 = tri_inv_lower(l11, unit_diag=True) @ r12
                if abft:
                    u12, det, cor, _, pj = _abft.left_product_check(
                        l11, u12, jnp.sum(r12, axis=1),
                        jnp.sum(r12, axis=0), unit=True, n_ctx=m)
                    counts = _abft.add_counts(counts, _abft.count_event(
                        det, cor, kt, (k1 + pj) // nb))
                a = a.at[k0:k1, k1:].set(u12)
            if k1 < m:
                with span("slate.getrf/gemm"):
                    l21 = lu[w:, :w]
                    if abft:
                        tb = a[k1:, k1:]
                        exp_row = (jnp.sum(tb, axis=1)
                                   - l21 @ jnp.sum(u12, axis=1))
                        exp_col = (jnp.sum(tb, axis=0)
                                   - jnp.sum(l21, axis=0) @ u12)
                        tb, ev = _abft.sum_check(tb - l21 @ u12, exp_row,
                                                 exp_col, n_ctx=m, nb=nb,
                                                 row0=k1, col0=k1)
                        counts = _abft.add_counts(counts, ev)
                        a = a.at[k1:, k1:].set(tb)
                    else:
                        a = a.at[k1:, k1:].add(-(l21 @ u12))
    return a, perm_g, counts


@annotate("slate.getrf")
def getrf(A: Matrix, opts: Options | None = None) -> LUFactors:
    """LU with partial pivoting (ref: src/getrf.cc).

    Failure contract (Option.ErrorPolicy, see docs/ROBUSTNESS.md): eager
    calls raise :class:`SlateSingularError` on an exactly-zero or
    non-finite pivot; under ``info`` the return is
    ``(LUFactors, HealthInfo)``."""
    return _getrf(A, opts, "partial")


@annotate("slate.getrf_nopiv")
def getrf_nopiv(A: Matrix, opts: Options | None = None) -> LUFactors:
    """LU without pivoting (ref: src/getrf_nopiv.cc)."""
    return _getrf(A, opts, "nopiv")


@annotate("slate.getrf_tntpiv")
def getrf_tntpiv(A: Matrix, opts: Options | None = None) -> LUFactors:
    """CALU tournament-pivoting LU (ref: src/getrf_tntpiv.cc)."""
    return _getrf(A, opts, "tntpiv")


@jax.tree_util.register_pytree_node_class
class RBTFactors:
    """Factors of the butterfly-preconditioned pivot-free LU (getrf_rbt):
    ``F`` is the NoPiv LUFactors of the TRANSFORMED padded matrix
    A~ = U^T diag(A, I_pad) V, ``u``/``v`` the two depth-2 butterflies
    (internal/rbt.py level tuples) and ``n`` the logical (unpadded) size.
    getrs dispatches on this type: x = V (A~^-1 (U^T [b; 0]))[:n]."""

    def __init__(self, F: LUFactors, u, v, n: int):
        self.F = F
        self.u = u
        self.v = v
        self.n = n

    def tree_flatten(self):
        return (self.F, self.u, self.v), (self.n,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], children[2], aux[0])

    def __repr__(self):
        return (f"RBTFactors(n={self.n}, padded={self.F.LU.m}, "
                f"depth={len(self.u)})")


# host-static butterfly seed: the transform is a preconditioner, not a
# security primitive — determinism (bit-reproducible factors, replayable
# fault tests) is worth more than per-call entropy
_RBT_SEED = 0x5B17


def _info(opts: Options | None) -> dict:
    o = dict(opts or {})
    o[Option.ErrorPolicy] = ErrorPolicy.Info
    return o


@annotate("slate.getrf_rbt")
def getrf_rbt(A: Matrix, opts: Options | None = None):
    """Butterfly-preconditioned pivot-free LU (PRBT: Parker '95, Baboulin
    et al. '13): A~ = U^T diag(A, I_pad) V with depth-2 recursive random
    butterflies (internal/rbt.py, O(n^2) elementwise), then
    :func:`getrf_nopiv` on A~ — panels are pure MXU gemms against
    triangular inverses, no pivot hunt.  Returns :class:`RBTFactors`.

    This is the mechanism of the gesv speculative fast path; the policy
    (Option.Speculate resolution, iterative refinement, residual
    certification, escalation to pivoted LU on a failed certificate)
    lives in robust/recovery.py.  Health: the NoPiv factor's pivot/growth
    record over the TRANSFORMED matrix.

    Mesh target: the two-sided transform is applied on the block-cyclic
    storage via all-gathered row/column strips (parallel/dist_lu.py
    dist_rbt_two_sided) when the padded size is butterfly-divisible;
    otherwise falls back to the dense single-device transform."""
    from ..internal import rbt
    slate_error(A.m == A.n, "getrf_rbt: square matrices (gesv path)")
    n, nb = A.m, A.nb
    target = resolve_target(opts, A)
    o = _info(opts)
    if target is Target.mesh and A.grid.mesh is not None:
        from ..parallel.dist_lu import dist_rbt_two_sided
        An = as_root_general(A, nb, nb, grid=A.grid)
        st = An.storage
        m_pad = st.Mt * nb
        if m_pad % (1 << rbt.DEFAULT_DEPTH) == 0:
            u = rbt.generate(m_pad, seed=_RBT_SEED, dtype=A.dtype)
            v = rbt.generate(m_pad, seed=_RBT_SEED + 1, dtype=A.dtype)
            data = faults.maybe_corrupt("input", st.data)
            data = dist_rbt_two_sided(data, u, v, A.grid, n)
            data = faults.maybe_corrupt("post_rbt", data)
            At = Matrix(TileStorage(data, m_pad, m_pad, nb, nb, st.grid))
            Fi, fh = getrf_nopiv(At, o)
            return _health.finalize("getrf_rbt", RBTFactors(Fi, u, v, n),
                                    fh, opts, _singular("getrf_rbt"))
    nt = rbt.padded_size(n)
    ad = faults.maybe_corrupt("input", A.to_dense())
    abar = jnp.zeros((nt, nt), ad.dtype).at[:n, :n].set(ad)
    if nt > n:
        r = jnp.arange(n, nt)
        abar = abar.at[r, r].set(1)
    u = rbt.generate(nt, seed=_RBT_SEED, dtype=ad.dtype)
    v = rbt.generate(nt, seed=_RBT_SEED + 1, dtype=ad.dtype)
    at = rbt.transform(abar, u, v)
    at = faults.maybe_corrupt("post_rbt", at)
    At = Matrix(TileStorage.from_dense(at, nb, nb, A.grid))
    Fi, fh = getrf_nopiv(At, o)
    return _health.finalize("getrf_rbt", RBTFactors(Fi, u, v, n), fh,
                            opts, _singular("getrf_rbt"))


def _lu_health(factor_arr, minpiv, minidx, amax):
    """Assemble the LU HealthInfo: pivot record from the panel min-pivot
    trace + whole-factor finiteness + pivot-growth ratio."""
    h = _health.healthy(factor_arr.dtype)
    fmax = jnp.max(jnp.abs(factor_arr))
    bad = (minpiv == 0) | ~jnp.isfinite(minpiv)
    return h._replace(
        nonfinite=~jnp.all(jnp.isfinite(factor_arr)),
        info=jnp.where(bad, minidx.astype(jnp.int32) + 1, 0),
        min_pivot=minpiv.astype(h.min_pivot.dtype),
        min_pivot_index=minidx.astype(jnp.int32),
        growth=jnp.where(amax > 0, fmax / amax,
                         jnp.inf).astype(h.growth.dtype),
    )


def _abft_fold(h, counts: "_abft.AbftCounts"):
    """Fold checksum-verification counters into a HealthInfo: a detected
    but uncorrected strike flips ``h.ok`` (health.py), which is what the
    recovery ladder escalates on."""
    return h._replace(abft_detected=counts.detected,
                      abft_corrected=counts.corrected,
                      abft_site=counts.site)


def _getrf(A: Matrix, opts: Options | None, method: str):
    target = resolve_target(opts, A)
    nb = A.nb
    tau = float(get_option(opts, Option.PivotThreshold))
    mpt = int(get_option(opts, Option.MaxPanelThreads))
    depth = int(get_option(opts, Option.Depth))
    abft = resolve_abft(opts)

    if target is Target.mesh and A.grid.mesh is not None:
        from ..parallel.dist_chol import SUPERBLOCKS, superblock
        slate_error(A.m == A.n, "mesh getrf: square matrices (gesv path)")
        An = as_root_general(A, nb, nb, grid=A.grid)
        st = An.storage
        data_in = faults.maybe_corrupt("input", st.data)
        with span("slate.getrf/health"):
            amax = jnp.max(jnp.abs(data_in))
        la = max(1, int(get_option(opts, Option.Lookahead)))
        data, perm, minpiv, minidx, adet, acor, asite = dist_getrf(
            data_in, st.Nt, A.grid, st.n, method,
            ib=get_option(opts, Option.InnerBlocking),
            sb=superblock(st.Nt, SUPERBLOCKS * la),
            tau=tau, mpt=mpt, depth=depth, abft=abft)
        out = TileStorage(data, st.m, st.n, nb, nb, st.grid)
        # restore the pad-region-zero invariant (final ragged panel is
        # identity-augmented inside the factorization)
        clean = out.canonical() * entry_mask(st.m, st.n, nb, nb).astype(
            out.dtype)
        out = out.with_canonical(clean)
        F = LUFactors(Matrix(out), perm[: st.m])
        with span("slate.getrf/health"):
            h = _abft_fold(_lu_health(clean, minpiv, minidx, amax),
                           _abft.AbftCounts(adet, acor, asite))
        return _health.finalize(f"getrf[{method}]", F, h, opts,
                                _singular(f"getrf[{method}]"))

    ad = faults.maybe_corrupt("input", A.to_dense())
    with span("slate.getrf/health"):
        amax = jnp.max(jnp.abs(ad))
    lu, perm, counts = _getrf_dense_blocked(ad, nb, method, tau=tau,
                                            mpt=mpt, depth=depth, abft=abft)
    st = TileStorage.from_dense(lu, nb, nb, A.grid)
    F = LUFactors(Matrix(st), perm)
    with span("slate.getrf/health"):
        udiag = jnp.abs(jnp.diagonal(lu))
        minidx = jnp.argmin(udiag)
        h = _abft_fold(_lu_health(lu, udiag[minidx], minidx, amax), counts)
    return _health.finalize(f"getrf[{method}]", F, h, opts,
                            _singular(f"getrf[{method}]"))


def _singular(name: str):
    return lambda h: SlateSingularError(
        f"{name}: exactly-singular or non-finite factor "
        f"({h.describe()})", info=int(h.info))


class OocLUFactors(NamedTuple):
    """Out-of-core LU result: L\\U packed in one HOST numpy array + global
    row permutation (A[perm] = L U).  Host-resident because the whole
    point of getrf_ooc is that the factor need not fit device memory."""
    LU: "np.ndarray"  # noqa: F821 — host array, numpy imported lazily
    perm: "np.ndarray"  # noqa: F821


def _ooc_lu_health(lu_host, minpiv: float, minidx: int, amax: float):
    """LU health from HOST reductions (the OOC factor stays off-device)."""
    import numpy as np
    h = _health.healthy(lu_host.dtype)
    fmax = float(np.max(np.abs(lu_host))) if lu_host.size else 0.0
    bad = (minpiv == 0.0) or not np.isfinite(minpiv)
    growth = fmax / amax if amax > 0 else float("inf")
    return h._replace(
        nonfinite=jnp.asarray(not bool(np.all(np.isfinite(lu_host)))),
        info=jnp.asarray(minidx + 1 if bad else 0, jnp.int32),
        min_pivot=jnp.asarray(minpiv, h.min_pivot.dtype),
        min_pivot_index=jnp.asarray(minidx, jnp.int32),
        growth=jnp.asarray(growth, h.growth.dtype),
    )


@annotate("slate.getrf_ooc")
def getrf_ooc(a, nb: int | None = None, opts: Options | None = None,
              checkpoint=None, resume: bool = False):
    """Out-of-core partially-pivoted LU of a HOST-resident matrix.

    ``a`` is a dense host numpy array that need not fit device memory: a
    :class:`~slate_tpu.core.storage.TileMap` streams the pivot panel and
    one trailing block column at a time through HBM, prefetching the next
    trailing column while the current one updates (PR 15's
    hide-communication discipline on the host-device axis).  Returns
    :class:`OocLUFactors`; Option.ErrorPolicy resolves failures exactly
    like :func:`getrf`.

    Durability (docs/ROBUSTNESS.md "Durable jobs"): with a ``checkpoint``
    :class:`~slate_tpu.robust.checkpoint.CheckpointManager` the host tile
    map plus the accumulated permutation are snapshotted at panel-step
    boundaries; ``resume=True`` verifies the latest snapshot's ABFT
    checksums before continuing and is bit-identical to the
    uninterrupted run, refusing with a typed ``SlateCheckpointError``
    on torn/stale/corrupt state.
    """
    import numpy as np
    from ..core.storage import TileMap
    from ..internal.getrf import ooc_lu_panel, ooc_lu_trailing
    from ..robust.checkpoint import ensure_fingerprint, ooc_fingerprint
    from ..tune import ooc_panel_width

    if resume:
        slate_error(checkpoint is not None,
                    "getrf_ooc: resume=True needs a checkpoint manager")
        ck = checkpoint.load(op="getrf_ooc")
        m, n = ck.matrix.shape
        nb = int(ck.meta["nb"])
        fp = ooc_fingerprint("getrf_ooc", m, n, nb, ck.meta["dtype"])
        ensure_fingerprint(ck, fp)
        tm = TileMap(ck.matrix, nb, nb)
        perm_g = ck.extras["perm"].astype(np.int64, copy=True)
        amax = float(ck.extras["amax"][()])
        k_start = int(ck.step)
    else:
        ad = np.asarray(a)
        slate_error(ad.ndim == 2, "getrf_ooc: 2D host matrix")
        m, n = ad.shape
        nb = int(nb) if nb else ooc_panel_width(max(m, n), ad.dtype.name)
        fp = ooc_fingerprint("getrf_ooc", m, n, nb, ad.dtype.name)
        tm = TileMap(ad, nb, nb)
        perm_g = np.arange(m, dtype=np.int64)
        amax = float(np.max(np.abs(ad))) if ad.size else 0.0
        k_start = 0

    kmax = min(m, n)
    steps = list(range(0, kmax, nb))
    for si in range(k_start, len(steps)):
        k0 = steps[si]
        k1 = min(k0 + nb, kmax)
        if checkpoint is not None and checkpoint.should_save(si):
            checkpoint.save(
                "getrf_ooc", si, tm.host_array(), nb, nb, fp,
                extras={"perm": perm_g,
                        "amax": np.asarray(amax, np.float64)})
        panel = tm.fetch(k0, m, k0, k1)
        lu, perm = ooc_lu_panel(panel)
        perm_h = np.asarray(perm)
        if k0:
            tm.permute_rows(k0, 0, k0, perm_h)
        perm_g[k0:] = perm_g[k0:][perm_h]
        tm.store(k0, m, k0, k1, lu)
        trail = list(range(k1, n, nb))
        if trail:
            tm.prefetch(k0, m, trail[0], min(trail[0] + nb, n))
        for ti, j0 in enumerate(trail):
            j1 = min(j0 + nb, n)
            colj = tm.fetch(k0, m, j0, j1)
            if ti + 1 < len(trail):
                tm.prefetch(k0, m, trail[ti + 1],
                            min(trail[ti + 1] + nb, n))
            tm.store(k0, m, j0, j1, ooc_lu_trailing(colj, lu, perm))
    lu_h = tm.host_array().copy()
    udiag = np.abs(np.diagonal(lu_h[:kmax, :kmax]))
    udiag = np.where(np.isnan(udiag), 0.0, udiag)
    minidx = int(np.argmin(udiag)) if udiag.size else 0
    minpiv = float(udiag[minidx]) if udiag.size else float("inf")
    h = _ooc_lu_health(lu_h, minpiv, minidx, amax)
    return _health.finalize("getrf_ooc", OocLUFactors(lu_h, perm_g), h,
                            opts, _singular("getrf_ooc"))


def _getrs_rbt(F: RBTFactors, B, opts: Options | None) -> Matrix:
    """getrs body for RBT factors: the RAW transformed solve
    x = V (A~^-1 (U^T [b; 0]))[:n] — no refinement, no certification
    (those belong to the speculative gesv seam, robust/recovery.py).
    B is skinny, so the butterfly applies on the dense RHS are O(n nrhs)
    and mesh-safe; the inner triangular sweeps ride the tiled solve."""
    from ..internal import rbt
    slate_error(F.n == B.m, "getrs: dims")
    nt = F.F.LU.m
    bd = B.to_dense()
    bbar = jnp.zeros((nt, bd.shape[1]), bd.dtype).at[: F.n].set(bd)
    yt = rbt.apply_left_t(F.u, bbar)
    Yt = Matrix(TileStorage.from_dense(yt, F.F.LU.nb, B.nb, B.grid))
    Z = getrs(F.F, Yt, opts)
    xbar = rbt.apply_left(F.v, Z.to_dense())
    return Matrix(TileStorage.from_dense(xbar[: F.n], B.mb, B.nb, B.grid))


@annotate("slate.getrs")
def getrs(F: LUFactors, B, opts: Options | None = None) -> Matrix:
    """Solve with LU factors: X = U^-1 L^-1 B[perm] (ref: src/getrs.cc).
    :class:`RBTFactors` dispatch to the butterfly transform sandwich.

    On the mesh the pivot application is sharded (dist_permute_rows —
    each rank holds a 1/q column strip, never a replicated dense B)."""
    from ..parallel.dist_lu import dist_permute_rows
    if isinstance(F, RBTFactors):
        return _getrs_rbt(F, B, opts)
    slate_error(F.LU.m == B.m, "getrs: dims")
    target = resolve_target(opts, B)
    if (target is Target.mesh and B.grid.mesh is not None
            and type(B) is Matrix and B.op is Op.NoTrans
            and B.is_root_view()):
        st = B.storage
        bp_data = dist_permute_rows(st.data, F.perm, B.grid)
        Bp = Matrix(TileStorage(bp_data, st.m, st.n, st.mb, st.nb, st.grid))
    else:
        with span("slate.getrs/permute"):
            bperm = B.to_dense()[F.perm]
            Bp = Matrix(TileStorage.from_dense(bperm, B.mb, B.nb, B.grid))
    Y = trsm("l", 1.0, F.lower(), Bp, opts)
    X = trsm("l", 1.0, F.upper(), Y, opts)
    if faults.active("solve") is not None:
        sx = X.storage
        X = Matrix(TileStorage(faults.maybe_corrupt("solve", sx.data),
                               sx.m, sx.n, sx.mb, sx.nb, sx.grid))
    return X


@annotate("slate.gesv")
def gesv(A: Matrix, B, opts: Options | None = None):
    """Solve A X = B via LU (ref: src/gesv.cc; MethodLU dispatch).
    Returns (LUFactors, X); with Option.UseFallbackSolver an eager call
    escalates pivoting (NoPiv -> PartialPiv -> CALU) on unhealthy
    factors.  Under ``Option.Speculate = on`` the first attempt is the
    RBT-preconditioned pivot-free fast path (:func:`getrf_rbt` + 2 steps
    of iterative refinement), certified by its relative residual; only a
    failed certificate escalates to the pivoted chain — see
    robust/recovery.py and docs/ROBUSTNESS.md."""
    from ..robust.recovery import gesv_with_recovery
    return gesv_with_recovery(A, B, opts)


def gesv_nopiv(A: Matrix, B, opts: Options | None = None):
    """ref: src/gesv_nopiv.cc — no escalation: the raw NoPiv contract."""
    from ..robust.recovery import gesv_nopiv_raw
    return gesv_nopiv_raw(A, B, opts)


def _getri_health(F: LUFactors, X: Matrix):
    """Inverse health: a zero/non-finite U pivot means the factor is
    exactly singular (LAPACK getri's info = k contract) — checked here
    because getri is often handed factors produced under Info/Nan
    policies that deliberately did not raise at factor time."""
    udiag = jnp.diagonal(F.LU.to_dense())
    return _health.merge(_health.from_pivots(udiag),
                         _health.from_result(X.storage.data))


@annotate("slate.getri")
def getri(F: LUFactors, opts: Options | None = None) -> Matrix:
    """In-place-style inverse from LU factors (ref: src/getri.cc):
    A^-1 = U^-1 L^-1 P.

    Failure contract: a singular factor (zero U pivot) resolves per
    ``Option.ErrorPolicy`` — eager raise of :class:`SlateSingularError`
    with ``info = k``, NaN-fill, or ``(X, HealthInfo)``."""
    n = F.LU.m
    eye = jnp.eye(n, dtype=F.LU.dtype)
    I = Matrix(TileStorage.from_dense(eye, F.LU.mb, F.LU.nb, F.LU.grid))
    X = getrs(F, I, opts)
    return _health.finalize("getri", X, _getri_health(F, X), opts,
                            _singular("getri"))


@annotate("slate.getriOOP")
def getriOOP(A: Matrix, opts: Options | None = None) -> Matrix:
    """Out-of-place inverse (ref: src/getriOOP.cc): factor + solve vs I.
    Under ``ErrorPolicy.Info`` returns ``(X, HealthInfo)`` with the
    factor and solve healths merged."""
    from ..options import ErrorPolicy
    if _health.error_policy(opts) is ErrorPolicy.Info:
        F, fh = getrf(A, opts)
        X, ih = getri(F, opts)
        return X, _health.merge(fh, ih)
    F = getrf(A, opts)
    return getri(F, opts)
