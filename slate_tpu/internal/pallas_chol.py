"""Pallas TPU kernel: Cholesky of one diagonal tile, VMEM-resident.

The reference factors diagonal tiles with vendor LAPACK potrf
(ref: src/internal/internal_potrf.cc:132).  XLA's TPU Cholesky runs a
per-column While loop — 2.07 ms for a 512x512 f32 tile (a
pre-benchmark v5e measurement), which times 32 sequential panel
steps is the single largest cost in a 16k potrf.  This kernel keeps the
whole tile in VMEM for the entire factorization.

Formulation: the UPPER factor U with A = U^T U, processed in ``bw``-ROW
panels — Mosaic only allows dynamic slicing in 128-multiples along the
lane (last) dimension, but sublane (row) slices may move in multiples of
8, so an 8-row panel keeps every sequential step's operand at one vreg
row [8, n] instead of a [n, 128] half-tile.  The diagonal block is
mirrored into a [bw, bw] array via a one-hot MXU contraction (no lane
slicing), scalars come from mask+reduce, and the inter-panel trailing
update is a single MXU dot P^T P.  The caller transposes U once to
return the conventional lower L.

Fused panel variant (chol_panel_fused): one pallas_call grid performs
the whole left-looking panel step — the rank-k update from the already
factored block row, the diagonal-tile factorization, and the TRSM that
forms L21 — without the panel ever leaving VMEM between stages.  Grid
(Mt, Kc) walks row tiles (major) x K chunks (minor, auto double-buffered
HBM->VMEM by the BlockSpec pipeline); an accumulator scratch carries the
updated tile across K chunks, and a second scratch carries U^-1 from the
diagonal tile (row tile 0) to every trailing row tile, whose TRSM is
then a single MXU gemm A21 U^-1 (pallas_tri.upper_tri_inv).  Both the
pre-factor update (for the ABFT checksum rungs) and the factored panel
are emitted.

Ragged batched variant (chol_panel_batched): the same fused panel step
with a leading batch grid dimension and a per-problem size-in-tiles
vector delivered via scalar prefetch (PrefetchScalarGridSpec) — each
problem computes only its own live tiles; dead tiles identity-complete
by copying their input through, so a bucket of mixed-size problems
never burns MXU cycles on padding.

Real f32 tiles everywhere; the batched variant additionally accepts
bf16 storage with fp32 accumulation — every MXU dot carries
``preferred_element_type=f32``, the VMEM accumulator and the factor
scratch are f32, and only the final panel write demotes back to the
input dtype (the MXU's native bf16xbf16->f32 contract; the certified
acceptance story lives in serve/batched.py + robust/precision.py).
Complex/f64 tiles use the XLA fallback (potrf_tile).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_tri import PANEL_PARAMS, upper_tri_inv

_HI = lax.Precision.HIGHEST


def _chol_factor_in_place(o_ref, *, bw: int):
    """Factor the SPD tile held in ``o_ref`` into its UPPER factor U
    (A = U^T U, lower triangle exactly zero), in bw-row panels."""
    n = o_ref.shape[0]
    dt = o_ref.dtype
    rows = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    pr = lax.broadcasted_iota(jnp.int32, (bw, n), 0)
    cn = lax.broadcasted_iota(jnp.int32, (1, n), 1)
    br = lax.broadcasted_iota(jnp.int32, (bw, bw), 0)
    bc = lax.broadcasted_iota(jnp.int32, (bw, bw), 1)
    bc1 = lax.broadcasted_iota(jnp.int32, (1, bw), 1)

    def block_step(b, _):
        j0 = b * bw
        P = o_ref[pl.ds(j0, bw), :]                  # [bw, n] row panel
        selT = (lax.broadcasted_iota(jnp.int32, (n, bw), 0)
                == j0 + lax.broadcasted_iota(jnp.int32, (n, bw), 1))
        D = jnp.dot(P, selT.astype(dt), preferred_element_type=dt,
                    precision=_HI)                   # P[:, j0:j0+bw]

        def col_step(i, PD):
            P, D = PD
            j = j0 + i
            piv = jnp.sqrt(jnp.sum(jnp.where((br == i) & (bc == i), D, 0)))
            inv = 1.0 / piv
            drow = jnp.sum(jnp.where(br == i, D, 0), axis=0,
                           keepdims=True)            # [1, bw] row i of D
            # u_j = row j of U: row i of P scaled, left-of-diag zeroed
            prow = jnp.sum(jnp.where(pr == i, P, 0), axis=0, keepdims=True)
            urow = jnp.where(cn < j, 0.0, prow * inv)
            # block-row couplings: u_j restricted to this panel's columns
            ublk = jnp.where(bc1 == i, piv, drow * inv)
            ublk = jnp.where(bc1 < i, 0.0, ublk)     # [1, bw]
            coefT = ublk.reshape(bw, 1)
            P = jnp.where(pr == i, urow,
                          jnp.where(pr > i, P - coefT * urow, P))
            D = jnp.where(br == i, ublk,
                          jnp.where(br > i, D - coefT * ublk, D))
            return P, D

        P, _ = lax.fori_loop(0, bw, col_step, (P, D))
        o_ref[pl.ds(j0, bw), :] = P
        # trailing rows: A -= P^T P (contract the panel-row axis)
        upd = lax.dot_general(P, P, (((0,), (0,)), ((), ())),
                              preferred_element_type=dt, precision=_HI)
        av = o_ref[:]
        o_ref[:] = jnp.where(rows >= j0 + bw, av - upd, av)
        return 0

    lax.fori_loop(0, n // bw, block_step, 0)


def _chol_kernel(a_ref, o_ref, *, bw: int):
    o_ref[:] = a_ref[:]
    _chol_factor_in_place(o_ref, bw=bw)


def _chol_panel_kernel(col_ref, left_ref, lead_ref, upd_ref, fac_ref,
                       acc_ref, uinv_ref, *, bw: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    kc = pl.num_programs(1)
    nb = col_ref.shape[0]
    dt = col_ref.dtype

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = col_ref[:]

    # left-looking rank-k chunk: acc -= A[i-tile, chunk] @ lead[chunk]
    acc_ref[:] = acc_ref[:] - jnp.dot(left_ref[:], lead_ref[:],
                                      preferred_element_type=dt,
                                      precision=_HI)

    @pl.when(j == kc - 1)
    def _finish():
        upd_ref[:] = acc_ref[:]              # pre-factor tile (ABFT rungs)

        @pl.when(i == 0)
        def _factor():
            _chol_factor_in_place(acc_ref, bw=bw)
            u = acc_ref[:]
            eye = (lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
                   == lax.broadcasted_iota(jnp.int32, (nb, nb), 1))
            # L00 = U^T via one-hot MXU contraction (no transpose op)
            fac_ref[:] = lax.dot_general(u, eye.astype(dt),
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=dt,
                                         precision=_HI)
            uinv_ref[:] = upper_tri_inv(u)

        @pl.when(i != 0)
        def _trsm():
            # L21 solves L21 L00^T = A21, i.e. L21 = A21 U^-1 (U = L00^T)
            fac_ref[:] = jnp.dot(acc_ref[:], uinv_ref[:],
                                 preferred_element_type=dt, precision=_HI)


@functools.partial(jax.jit, static_argnames=("bw", "interpret"))
def chol_panel_fused(col, left, lead, bw: int = 8, interpret: bool = False):
    """Fused left-looking Cholesky panel step.

    col:  [M, nb] trailing block column A[k0:, k0:k0+nb]
    left: [M, K]  factored block row A[k0:, :k0] (K == 0 on panel 0)
    lead: [K, nb] conj(A[k0:k0+nb, :k0])^T

    Returns (upd, fac): ``upd`` = col - left @ lead, the pre-factor panel
    the ABFT checksum rungs verify; ``fac`` = [L00; L21], the factored
    panel.  Caller guarantees f32, M % nb == 0, nb % bw == 0, M >= nb.
    """
    m, nb = col.shape
    k = left.shape[1]
    kb = nb
    kp = max(kb, -(-k // kb) * kb)
    if k != kp:                              # pad K chunks with zeros
        left = jnp.pad(left, ((0, 0), (0, kp - k)))
        lead = jnp.pad(lead, ((0, kp - k), (0, 0)))
    upd, fac = pl.pallas_call(
        functools.partial(_chol_panel_kernel, bw=bw),
        grid=(m // nb, kp // kb),
        in_specs=[pl.BlockSpec((nb, nb), lambda i, j: (i, 0)),
                  pl.BlockSpec((nb, kb), lambda i, j: (i, j)),
                  pl.BlockSpec((kb, nb), lambda i, j: (j, 0))],
        out_specs=[pl.BlockSpec((nb, nb), lambda i, j: (i, 0)),
                   pl.BlockSpec((nb, nb), lambda i, j: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((m, nb), col.dtype),
                   jax.ShapeDtypeStruct((m, nb), col.dtype)],
        scratch_shapes=[pltpu.VMEM((nb, nb), col.dtype),
                        pltpu.VMEM((nb, nb), col.dtype)],
        compiler_params=PANEL_PARAMS,
        interpret=interpret,
    )(col, left, lead)
    return upd, fac


def _chol_panel_batched_kernel(tiles_ref, col_ref, left_ref, lead_ref,
                               upd_ref, fac_ref, acc_ref, uinv_ref,
                               *, k: int, bw: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    kc = pl.num_programs(2)
    nb = col_ref.shape[1]
    dt = col_ref.dtype
    f32 = jnp.float32
    # Row tile i of this panel is global tile k + i of problem b; tiles
    # past the problem's own count are DEAD — identity-augmented packing
    # makes their factor exactly the input tile (I on the diagonal, 0
    # off it), so they skip every MXU dot and just copy through.
    live = k + i < tiles_ref[b]

    @pl.when(j == 0)
    def _init():
        # accumulate in f32 regardless of storage dtype (bf16 inputs ride
        # the MXU's native bf16xbf16->f32 path; f32 inputs are unchanged)
        acc_ref[:] = col_ref[0].astype(f32)

    @pl.when(live)
    def _update():
        # left-looking rank-k chunk: acc -= A[b, i-tile, chunk] @ lead
        # bf16 x bf16 products are exact on the MXU; HIGHEST asks for
        # the f32 multi-pass product, which Mosaic refuses for bf16 (an
        # explicit DEFAULT survives a caller's default_matmul_precision)
        acc_ref[:] = acc_ref[:] - jnp.dot(
            left_ref[0], lead_ref[0], preferred_element_type=f32,
            precision=_HI if dt == f32 else lax.Precision.DEFAULT)

    @pl.when(j == kc - 1)
    def _finish():
        @pl.when(live)
        def _live():
            upd_ref[0] = acc_ref[:].astype(dt)   # pre-factor tile (ABFT)

            @pl.when(i == 0)
            def _factor():
                _chol_factor_in_place(acc_ref, bw=bw)
                u = acc_ref[:]
                eye = (lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
                       == lax.broadcasted_iota(jnp.int32, (nb, nb), 1))
                fac_ref[0] = lax.dot_general(u, eye.astype(f32),
                                             (((0,), (0,)), ((), ())),
                                             preferred_element_type=f32,
                                             precision=_HI).astype(dt)
                uinv_ref[:] = upper_tri_inv(u)

            @pl.when(i != 0)
            def _trsm():
                fac_ref[0] = jnp.dot(acc_ref[:], uinv_ref[:],
                                     preferred_element_type=f32,
                                     precision=_HI).astype(dt)

        @pl.when(jnp.logical_not(live))
        def _dead():
            upd_ref[0] = col_ref[0]
            fac_ref[0] = col_ref[0]


@functools.partial(jax.jit, static_argnames=("k", "bw", "interpret"))
def chol_panel_batched(col, left, lead, tiles, k: int = 0, bw: int = 8,
                       interpret: bool = False):
    """Ragged batched fused Cholesky panel step.

    col:   [B, M, nb] trailing block columns A[:, k0:, k0:k0+nb]
    left:  [B, M, K]  factored block rows A[:, k0:, :k0]
    lead:  [B, K, nb] conj(A[:, k0:k0+nb, :k0])^T per problem
    tiles: [B] int32 per-problem live tile counts ceil(size / nb)
    k:     static panel index (number of block columns already factored)

    Per-problem-size grids via scalar prefetch: the ``tiles`` vector
    rides ahead of the grid, row tiles at or past a problem's own count
    copy their (identity/zero) input through untouched, and the LEFT
    operand's index map clamps dead tiles onto the last live row so
    their HBM->VMEM streams are never issued for fresh data.  Outputs
    are never clamped — every block is written (dead blocks with the
    exact identity-completion values), keeping HBM initialized.

    Returns (upd, fac) stacked over B, same per-problem contract as
    chol_panel_fused.  Caller guarantees real f32 OR bf16 storage
    (accumulation is f32 either way), M % nb == 0, nb % bw == 0.
    """
    bsz, m, nb = col.shape
    kk = left.shape[2]
    kb = nb
    kp = max(kb, -(-kk // kb) * kb)
    if kk != kp:                             # pad K chunks with zeros
        left = jnp.pad(left, ((0, 0), (0, 0), (0, kp - kk)))
        lead = jnp.pad(lead, ((0, 0), (0, kp - kk), (0, 0)))
    upd, fac = pl.pallas_call(
        functools.partial(_chol_panel_batched_kernel, k=k, bw=bw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, m // nb, kp // kb),
            in_specs=[
                pl.BlockSpec((1, nb, nb), lambda b, i, j, tiles: (b, i, 0)),
                pl.BlockSpec(
                    (1, nb, kb),
                    lambda b, i, j, tiles: (
                        b,
                        jnp.minimum(i, jnp.maximum(tiles[b] - k, 1) - 1),
                        j)),
                pl.BlockSpec((1, kb, nb), lambda b, i, j, tiles: (b, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, nb, nb), lambda b, i, j, tiles: (b, i, 0)),
                pl.BlockSpec((1, nb, nb), lambda b, i, j, tiles: (b, i, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((nb, nb), jnp.float32),
                            pltpu.VMEM((nb, nb), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((bsz, m, nb), col.dtype),
                   jax.ShapeDtypeStruct((bsz, m, nb), col.dtype)],
        compiler_params=PANEL_PARAMS,
        interpret=interpret,
    )(tiles, col, left, lead)
    return upd, fac


@functools.partial(jax.jit, static_argnames=("bw", "interpret"))
def chol_tile_pallas(a, bw: int = 8, interpret: bool = False):
    """Lower Cholesky factor of an SPD tile [n, n], n % bw == 0,
    bw % 8 == 0."""
    n = a.shape[0]
    u = pl.pallas_call(
        functools.partial(_chol_kernel, bw=bw),
        out_shape=jax.ShapeDtypeStruct((n, n), a.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(a)
    return u.T
