"""Parallel BLAS-3 drivers.

Analog of the reference's L6 BLAS-3 routine set (ref: src/gemm.cc, gemmC.cc,
gemmA.cc, hemm.cc, symm.cc, trmm.cc, trsm.cc/trsmA.cc/trsmB.cc, herk.cc,
syrk.cc, her2k.cc, syr2k.cc).  Each driver:

- validates shapes and resolves the execution target (Option::Target),
- single target: statically-shaped dense/blocked computation under jit —
  the analog of the HostTask path but feeding the whole problem to the MXU,
- mesh target: shard_map pipeline over the 2D block-cyclic grid with ICI
  collectives (SUMMA for gemm; masked-panel pipelines for triangular ops).

All drivers are functional: they RETURN the updated matrix instead of
mutating C (XLA buffer donation recovers in-place performance).
"""

from __future__ import annotations

import jax.numpy as jnp

from ..core.grid import Grid
from ..core.matrix import BaseMatrix, Matrix
from ..core.storage import TileStorage
from ..exceptions import slate_error
from ..obs import events as _obs_events
from ..options import (MethodGemm, Option, Options, Target, resolve_abft,
                       resolve_target, select_gemm_method)
from ..parallel import summa
from ..robust import abft as _abft
from ..types import Diag, Op, Side, Uplo
from ..util.trace import annotate


def as_root_general(A: BaseMatrix, mb: int | None = None,
                    nb: int | None = None,
                    grid: Grid | None = None) -> Matrix:
    """Normalise any view/op/structure to a root general Matrix with the given
    tile sizes on the given grid (materialises/redistributes only when
    needed).  Mesh drivers use this so the shard_map body sees plain cyclic
    storage laid out for the OUTPUT's grid."""
    mb = mb or A.mb
    nb = nb or A.nb
    grid = grid or A.grid
    if (type(A) is Matrix and A.op is Op.NoTrans and A.is_root_view()
            and A.mb == mb and A.nb == nb and A.grid is grid):
        return A
    dense = A.to_dense()
    return Matrix(TileStorage.from_dense(dense, mb, nb, grid))


def _result_mat(C: BaseMatrix, data) -> Matrix:
    st = C.storage
    return Matrix(TileStorage(data, st.m, st.n, st.mb, st.nb, st.grid))


# ---------------------------------------------------------------- gemm

@annotate("slate.gemm")
def gemm(alpha, A: BaseMatrix, B: BaseMatrix, beta=0.0,
         C: Matrix | None = None, opts: Options | None = None) -> Matrix:
    """C = alpha op(A) op(B) + beta C (ref: src/gemm.cc:66-89 dispatch,
    src/gemmC.cc:29-192 stationary-C algorithm)."""
    slate_error(A.n == B.m, "gemm: inner dims differ")
    if C is None:
        dt = jnp.result_type(A.dtype, B.dtype)
        C = Matrix.zeros(A.m, B.n, A.mb, B.nb, A.grid, dt)
        beta = 0.0
    slate_error(C.m == A.m and C.n == B.n, "gemm: C dims differ")
    target = resolve_target(opts, C)
    method = select_gemm_method(opts, C.nt)
    abft = resolve_abft(opts)  # the one Option.Abft read (driver boundary)

    if target is Target.mesh and C.grid.mesh is not None:
        # All operands are normalised onto C's grid (redistributing if they
        # live elsewhere — the analog of the reference's requirement that all
        # three matrices share one MPI communicator).
        Cn = as_root_general(C, grid=C.grid)
        An = as_root_general(A, Cn.storage.mb, None, grid=C.grid)
        Bn = as_root_general(B, An.storage.nb, Cn.storage.nb, grid=C.grid)
        slate_error(An.storage.Nt == Bn.storage.Mt, "gemm: k tiling differs")
        if method is MethodGemm.gemmA:
            # stationary-A, replicate-B + reduce-over-C (ref: gemmA.cc)
            from ..parallel.gemm_a import dist_gemmA_data
            data = dist_gemmA_data(
                An.storage.data, Bn.storage.data, Cn.storage.data,
                alpha, beta, An.storage.Nt, Cn.grid)
        elif abft:
            # gemm has no health channel, so ABFT here is SILENT repair:
            # a single struck accumulator tile is fixed in place, the
            # counters are dropped (an uncorrectable multi-strike leaves
            # the data for the caller's certification to catch)
            data, _, _, _ = summa.summa_gemm_data(
                An.storage.data, Bn.storage.data, Cn.storage.data,
                alpha, beta, An.storage.Nt, Cn.grid, abft=True)
        else:
            data = summa.summa_gemm_data(
                An.storage.data, Bn.storage.data, Cn.storage.data,
                alpha, beta, An.storage.Nt, Cn.grid)
        return _result_mat(Cn, data)

    # single target: one fused MXU contraction.  Literal alpha=1 / beta=0
    # skip their passes entirely — XLA cannot fold 0*C itself (0*NaN
    # semantics), and the beta=0 path otherwise materialises and reads a
    # zeros C for nothing (measured ~35% of the n=8192 gemm wall-clock)
    Ad, Bd = A.to_dense(), B.to_dense()
    Cd = Ad @ Bd
    if abft:
        # additive checksums of the raw product (silent repair, as above)
        Cd, _ = _abft.sum_check(Cd, Ad @ jnp.sum(Bd, axis=1),
                                jnp.sum(Ad, axis=0) @ Bd, n_ctx=A.n)
    if not (isinstance(alpha, (int, float)) and alpha == 1.0):
        Cd = jnp.asarray(alpha, Cd.dtype) * Cd
    if not (isinstance(beta, (int, float)) and beta == 0.0):
        Cd = Cd + jnp.asarray(beta, Cd.dtype) * C.to_dense()
    return C.with_dense(Cd) if type(C) is Matrix else _dense_to_like(C, Cd)


def _dense_to_like(C: BaseMatrix, dense) -> Matrix:
    g = Matrix.zeros(C.m, C.n, C.mb, C.nb, C.grid, dense.dtype)
    return g.with_dense(dense)


def _side(side) -> Side:
    if isinstance(side, Side):
        return side
    return Side.Left if str(side).lower().startswith("l") else Side.Right


# ---------------------------------------------------------------- trsm/trmm

@annotate("slate.trsm")
def trsm(side, alpha, A, B, opts: Options | None = None) -> Matrix:
    """Solve op(A) X = alpha B (Left) or X op(A) = alpha B (Right), A
    triangular (ref: src/trsm.cc method dispatch -> src/trsmB.cc ->
    work/work_trsm.cc; trsmA variant src/trsmA.cc).

    single: one XLA triangular_solve (blocked internally, MXU-shaped).
    mesh: parallel.dist_trsm substitution pipeline.  MethodTrsm (Auto:
    trsmA where B is one tile column, else trsmB) names the stationary
    operand (ref: src/trsmA.cc vs src/trsmB.cc).  Under trsmA a Left
    solve with B of one tile column keeps every tile of A on its chip
    and moves only x and the updates; any other solve broadcasts A's
    panels.  When A and B live on different grids, trsmA redistributes
    B onto A's grid and trsmB moves A's triangle onto B's.  The event
    notes the kernel that ran as ``method_trsm``.
    """
    from ..core.matrix import BaseTrapezoidMatrix
    from ..options import MethodTrsm, select_trsm_method
    from ..parallel.dist_trsm import dist_trsm_left, dist_trsm_right
    sd = _side(side)
    slate_error(isinstance(A, BaseTrapezoidMatrix), "trsm: A not triangular")
    slate_error(A._m_store() == A._n_store(), "trsm: A not square")
    if sd is Side.Left:
        slate_error(A.n == B.m, "trsm: dims")
    else:
        slate_error(B.n == A.m, "trsm: dims")
    target = resolve_target(opts, B)
    unit = A.diag is Diag.Unit
    abft = resolve_abft(opts)  # the one Option.Abft read (driver boundary)

    if target is Target.mesh and B.grid.mesh is not None:
        meth = select_trsm_method(opts, B.nt)
        grid = A.grid if (meth is MethodTrsm.trsmA
                          and A.grid.mesh is not None) else B.grid
        lower = A.uplo is Uplo.Lower       # storage triangle
        nb = A.storage.nb
        An = _root_storage_triangular(A, grid=grid)
        stationary_a = False
        if sd is Side.Right:
            # direct column-substitution kernel: no dense transpose
            Bn = as_root_general(B, None, nb, grid=grid)
            data = dist_trsm_right(An.storage.data, Bn.storage.data,
                                   jnp.asarray(alpha, Bn.dtype),
                                   Nt=An.storage.Nt, grid=grid,
                                   lower=lower, op_a=A.op, unit_diag=unit,
                                   n=An.storage.n)
        else:
            Bn = as_root_general(B, nb, None, grid=grid)
            stationary_a = (meth is MethodTrsm.trsmA
                            and Bn.storage.Nt < 2)
            data = dist_trsm_left(An.storage.data, Bn.storage.data,
                                  jnp.asarray(alpha, Bn.dtype),
                                  Nt=An.storage.Nt, grid=grid, lower=lower,
                                  op_a=A.op, unit_diag=unit, n=An.storage.n,
                                  nrhs=Bn.storage.n if stationary_a else None)
        _obs_events.note_choice("method_trsm",
                                "trsmA" if stationary_a else "trsmB")
        st = Bn.storage
        return Matrix(TileStorage(data, st.m, st.n, st.mb, st.nb, st.grid))

    ad = A._dense_store()                  # storage triangle, op separate
    bd = alpha * B.to_dense()
    lower = A.uplo is Uplo.Lower
    nb = A.storage.nb
    if ad.shape[0] >= 2 * nb:
        # block substitution with batched diagonal inversions — every op
        # an MXU gemm (internal/trsm.py; XLA's per-column solve measured
        # 4.1 TFLOP/s at [16384, 256]); ragged n identity-augmented inside
        from ..internal.trsm import trsm_left_blocked, trsm_right_blocked
        kw = dict(lower=lower, trans=(A.op is not Op.NoTrans),
                  conj=(A.op is Op.ConjTrans), unit=unit, nb=nb,
                  check=abft)  # checksum-verify + silent single repair
        xd = (trsm_left_blocked(ad, bd, **kw) if sd is Side.Left
              else trsm_right_blocked(ad, bd, **kw))
        return _dense_to_like(B, xd)
    from jax import lax as _lax
    xd = _lax.linalg.triangular_solve(
        ad, bd, left_side=(sd is Side.Left), lower=lower,
        transpose_a=(A.op is not Op.NoTrans),
        conjugate_a=(A.op is Op.ConjTrans), unit_diagonal=unit)
    return _dense_to_like(B, xd)


def _root_storage_triangular(A, grid=None):
    """Root general matrix holding A's STORAGE triangle (op ignored —
    callers pass A.op separately)."""
    grid = grid or A.grid
    if (A.op in (Op.NoTrans, Op.Trans, Op.ConjTrans) and A.is_root_view()
            and A.grid is grid and A.storage.mb == A.storage.nb):
        return Matrix(A.storage)
    d = A._dense_store()
    nb = A.storage.nb
    return Matrix(TileStorage.from_dense(d, nb, nb, grid))


@annotate("slate.trmm")
def trmm(side, alpha, A, B, opts: Options | None = None) -> Matrix:
    """B = alpha op(A) B (Left) or alpha B op(A) (Right), A triangular
    (ref: src/trmm.cc -> work/work_trmm.cc).

    mesh: triangle-aware packed-pair kernel over A's STORED tiles only —
    half a gemm's flops, no dense expansion (parallel/dist_herk.py
    dist_trmm_data).  Transposed-A views fall back to the dense path."""
    sd = _side(side)
    if (resolve_target(opts, B) is Target.mesh and B.grid.mesh is not None
            and A.op is Op.NoTrans and A.is_root_view()
            and A.storage.mb == A.storage.nb
            # the kernel reads A.storage raw, so its cyclic layout must be
            # B's grid's; cross-grid operands fall back to the dense path
            and A.grid is B.grid):
        from ..parallel.dist_herk import (dist_trmm_data,
                                          dist_trmm_right_data)
        lower = A.uplo is Uplo.Lower
        unit = A.diag is Diag.Unit
        nb = A.storage.nb
        An = Matrix(A.storage)
        if sd is Side.Left:
            Bn = as_root_general(B, nb, None, grid=B.grid)
            data = dist_trmm_data(
                An.storage.data, Bn.storage.data, alpha,
                Kt=An.storage.Nt, Mt=An.storage.Mt, grid=B.grid,
                lower=lower, unit_diag=unit, n=An.storage.n)
        else:
            Bn = as_root_general(B, None, nb, grid=B.grid)
            data = dist_trmm_right_data(
                An.storage.data, Bn.storage.data, alpha,
                Kt=An.storage.Mt, Nt=An.storage.Nt, grid=B.grid,
                lower=lower, unit_diag=unit, n=An.storage.n)
        return _result_mat(Bn, data)
    ad = A.to_dense()                      # expands triangle incl. unit diag
    if resolve_target(opts, B) is Target.mesh and B.grid.mesh is not None:
        Ag = Matrix(TileStorage.from_dense(ad, A.mb, A.nb, B.grid))
        return gemm(alpha, Ag, B, 0.0, None, opts) if sd is Side.Left \
            else gemm(alpha, B, Ag, 0.0, None, opts)
    bd = B.to_dense()
    out = alpha * (ad @ bd) if sd is Side.Left else alpha * (bd @ ad)
    return _dense_to_like(B, out)


# ---------------------------------------------------------------- rank-k

def _rank_k_mesh(alpha, A, beta, C, opts, conj: bool, B=None, alpha2=None):
    """Shared mesh fast path for herk/syrk/her2k/syr2k: triangle-aware
    packed-pair kernel over C's STORED triangle tiles — half a full gemm's
    flops and comm (ref: internal_herk.cc diagonal herk + off-diag gemm).
    Returns the updated general storage Matrix, or None when the operands
    don't qualify (caller falls back to the gemm composition)."""
    from ..parallel.dist_herk import dist_herk_data
    if not (resolve_target(opts, C) is Target.mesh
            and C.grid.mesh is not None and C.op is Op.NoTrans
            and C.is_root_view() and C.storage.mb == C.storage.nb):
        return None
    nb = C.storage.nb
    An = as_root_general(A, nb, None, grid=C.grid)
    b_data = None
    if B is not None:
        Bn = as_root_general(B, nb, An.storage.nb, grid=C.grid)
        slate_error(Bn.storage.Nt == An.storage.Nt, "rank-2k: k tiling")
        b_data = Bn.storage.data
    cs = C.storage
    data = dist_herk_data(
        An.storage.data, cs.data, alpha, beta, Kt=An.storage.Nt,
        Mt=cs.Mt, Nt=cs.Nt, grid=C.grid, lower=C.uplo is Uplo.Lower,
        conj=conj, b_data=b_data, alpha2=alpha2)
    return _result_mat(C, data)


@annotate("slate.herk")
def herk(alpha, A, beta, C, opts: Options | None = None):
    """C = alpha A A^H + beta C, C Hermitian (ref: src/herk.cc,
    internal_herk.cc:843).  mesh: triangle-aware, half-gemm cost."""
    from ..core.matrix import BaseTrapezoidMatrix, HermitianMatrix
    slate_error(isinstance(C, BaseTrapezoidMatrix),
                "herk: C must be Hermitian/Symmetric")
    slate_error(A.m == C.m, "herk: dims")
    out = _rank_k_mesh(alpha, A, beta, C, opts, conj=True)
    if out is None:
        out = gemm(alpha, A, A.conj_transpose(), beta, _general_of(C), opts)
    return HermitianMatrix._from_view(out, C._uplo_logical())


@annotate("slate.syrk")
def syrk(alpha, A, beta, C, opts: Options | None = None):
    """C = alpha A A^T + beta C, C symmetric (ref: src/syrk.cc)."""
    from ..core.matrix import BaseTrapezoidMatrix, SymmetricMatrix
    slate_error(isinstance(C, BaseTrapezoidMatrix),
                "syrk: C must be Symmetric")
    out = _rank_k_mesh(alpha, A, beta, C, opts, conj=False)
    if out is None:
        out = gemm(alpha, A, A.transpose(), beta, _general_of(C), opts)
    return SymmetricMatrix._from_view(out, C._uplo_logical())


@annotate("slate.her2k")
def her2k(alpha, A, B, beta, C, opts: Options | None = None):
    """C = alpha A B^H + conj(alpha) B A^H + beta C (ref: src/her2k.cc,
    internal_her2k.cc:1062).  mesh: one triangle-aware pass."""
    from ..core.matrix import BaseTrapezoidMatrix, HermitianMatrix
    slate_error(isinstance(C, BaseTrapezoidMatrix),
                "her2k: C must be Hermitian")
    out = _rank_k_mesh(alpha, A, beta, C, opts, conj=True, B=B,
                       alpha2=jnp.conj(jnp.asarray(alpha)))
    if out is None:
        t1 = gemm(alpha, A, B.conj_transpose(), beta, _general_of(C), opts)
        out = gemm(jnp.conj(jnp.asarray(alpha)), B, A.conj_transpose(), 1.0,
                   t1, opts)
    return HermitianMatrix._from_view(out, C._uplo_logical())


@annotate("slate.syr2k")
def syr2k(alpha, A, B, beta, C, opts: Options | None = None):
    """C = alpha A B^T + alpha B A^T + beta C (ref: src/syr2k.cc)."""
    from ..core.matrix import BaseTrapezoidMatrix, SymmetricMatrix
    slate_error(isinstance(C, BaseTrapezoidMatrix),
                "syr2k: C must be Symmetric")
    out = _rank_k_mesh(alpha, A, beta, C, opts, conj=False, B=B,
                       alpha2=alpha)
    if out is None:
        t1 = gemm(alpha, A, B.transpose(), beta, _general_of(C), opts)
        out = gemm(alpha, B, A.transpose(), 1.0, t1, opts)
    return SymmetricMatrix._from_view(out, C._uplo_logical())


@annotate("slate.hemm")
def hemm(side, alpha, A, B, beta=0.0, C=None, opts=None) -> Matrix:
    """C = alpha A B + beta C with A Hermitian (ref: src/hemm.cc method
    dispatch, hemmA variant src/hemmA.cc).  A.to_dense() expands the stored
    triangle, then the multiply rides gemm (SUMMA on mesh); MethodHemm
    selects the stationary-A comm pattern (hemmA) explicitly or by the
    single-block-column heuristic (ref: method.hh MethodHemm::select_algo)."""
    from ..options import MethodHemm, get_option
    sd = _side(side)
    meth = get_option(opts, Option.MethodHemm)
    if meth is MethodHemm.Auto and sd is Side.Left and B.nt < 2:
        meth = MethodHemm.hemmA
    if meth is MethodHemm.hemmA and sd is Side.Left:
        o = dict(opts or {})
        o[Option.MethodGemm] = MethodGemm.gemmA
        return gemm(alpha, A, B, beta, C, o)
    if meth is MethodHemm.hemmA and sd is Side.Right:
        # honor the stationary-A request on the Right via the Hermitian
        # identity alpha B A = (conj(alpha) A B^H)^H — a left hemmA on B^H
        # followed by one elementwise add (never silently ignored)
        from .auxiliary import add as _add
        G = hemm(Side.Left, jnp.conj(jnp.asarray(alpha)), A,
                 B.conj_transpose(), 0.0, None,
                 {**(opts or {}), Option.MethodHemm: MethodHemm.hemmA})
        if C is None:
            dtc = jnp.result_type(A.dtype, B.dtype)
            C = Matrix.zeros(B.m, A.n, B.mb, A.nb, B.grid, dtc)
            beta = 0.0
        return _add(1.0, G.conj_transpose(), beta, C)
    if sd is Side.Left:
        return gemm(alpha, A, B, beta, C, opts)
    return gemm(alpha, B, A, beta, C, opts)


def symm(side, alpha, A, B, beta=0.0, C=None, opts=None) -> Matrix:
    """C = alpha A B + beta C with A symmetric (ref: src/symm.cc)."""
    return hemm(side, alpha, A, B, beta, C, opts)


def hemmA(side, alpha, A, B, beta=0.0, C=None, opts=None) -> Matrix:
    """Stationary-A hemm (ref: src/hemmA.cc): the expanded Hermitian A
    stays put while skinny B is replicated and C is reduce-scattered to
    its owners — gemmA's comm pattern (parallel/gemm_a.py).  Side.Right
    swaps the operands into gemm's replicated slot, which would replicate
    the LARGE Hermitian matrix, so only Side.Left forces gemmA."""
    o = dict(opts or {})
    if _side(side) is Side.Left:
        o[Option.MethodGemm] = MethodGemm.gemmA
    return hemm(side, alpha, A, B, beta, C, o)


def _general_of(C) -> Matrix:
    """General matrix holding C's expanded structure."""
    return C if type(C) is Matrix else C.general()


def gemmA(alpha, A, B, beta=0.0, C=None, opts=None) -> Matrix:
    """Stationary-A gemm (ref: src/gemmA.cc): A never moves; skinny B is
    replicated and partial C is psum_scattered to its owners
    (parallel/gemm_a.py).  Auto-selected for single-block-column C
    (method.hh:87-98); force with Option.MethodGemm."""
    o = dict(opts or {})
    o[Option.MethodGemm] = MethodGemm.gemmA
    return gemm(alpha, A, B, beta, C, o)


def gemmC(alpha, A, B, beta=0.0, C=None, opts=None) -> Matrix:
    """Stationary-C gemm (ref: src/gemmC.cc)."""
    o = dict(opts or {})
    o[Option.MethodGemm] = MethodGemm.gemmC
    return gemm(alpha, A, B, beta, C, o)
