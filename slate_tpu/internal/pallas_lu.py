"""Pallas TPU kernel: partial-pivot row selection for one LU panel chunk.

The CALU tournament (internal/getrf.py panel_lu_tournament) needs each
row block's nb partial-pivot rows (ref: internal_getrf_tntpiv.cc round-1
LUs).  XLA's pivoted LU streams the whole [W, nb] chunk from HBM once
per column — measured 31 us/column at [4096, 512] (docs/ceiling.jsonl
xla_lu_4096x512), i.e. 15.8 ms for work whose flops cost ~0.1 ms.  This
kernel keeps the chunk in VMEM TRANSPOSED ([nb, W]: columns of A on
sublanes, rows of A on lanes) so each elimination step touches one 8-row
slab; pivoted rows are MASKED out of the search instead of physically
swapped, and each slab's trailing update is two MXU dots against the
recorded multiplier/selection slabs.

Output: the pivot ROW indices [1, nb] int32, in elimination order —
exactly lax.linalg.lu's perm[:nb] for the same chunk (up to argmax tie
order).  Round 1 of the tournament needs nothing else: the candidate
values it forwards are the ORIGINAL rows, gathered by these indices.

Fused panel variant (lu_panel_fused): the unpivoted panel factor plus
the TRSM that scales every row tile below it, one pallas_call grid over
[nb, nb] row tiles.  Tile 0 is factored in place in bw-row slabs (panel
rows eliminate against themselves; the rows below the slab inside the
tile get a block solve against the slab's Ub^-1 plus one MXU trailing
update), then the full-tile U^-1 (pallas_tri.upper_tri_inv) rides a
scratch to the remaining tiles, whose TRSM is one gemm each — matching
getrf.panel_lu_nopiv's semantics (packed L\\U, unit lower implied).

Ragged batched variant (lu_panel_batched): the fused left-looking panel
step (rank-k update + tile factor + TRSM) with a leading batch grid
dimension and per-problem tile counts via scalar prefetch — dead tiles
identity-complete by copying their input through, so mixed-size batches
skip the padding work entirely.

Real f32 everywhere; the batched variant additionally accepts bf16
storage with fp32 accumulation (f32 VMEM accumulator + factor scratch,
``preferred_element_type=f32`` on every MXU dot, demote on the final
panel write — see pallas_chol.py for the contract).  The XLA LU remains
the fallback (and the test oracle).
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


def _lu_select_kernel(at_ref, mask_ref, piv_ref, ws_ref, lbuf_ref,
                      sbuf_ref, *, bw: int):
    nb, W = at_ref.shape
    dt = at_ref.dtype
    ws_ref[:] = at_ref[:]
    lane = lax.broadcasted_iota(jnp.int32, (1, W), 1)
    lane_nb = lax.broadcasted_iota(jnp.int32, (1, nb), 1)
    sl = lax.broadcasted_iota(jnp.int32, (bw, W), 0)
    rows_nb = lax.broadcasted_iota(jnp.int32, (nb, W), 0)
    piv_ref[:] = jnp.zeros((1, nb), jnp.int32)
    # allowed lanes: live rows only (caller masks ragged padding).
    # Kept as an f32 0/1 mask — Mosaic cannot carry bool vectors through
    # its loop lowering ("failed to legalize scf.for").
    allowed0 = mask_ref[:]

    def slab_step(b, allowed):
        j0 = b * bw
        slab = ws_ref[pl.ds(j0, bw), :]              # [bw, W]
        lbuf = jnp.zeros((bw, W), dt)                # multiplier rows
        sbuf = jnp.zeros((bw, W), dt)                # one-hot pivot rows

        def col_step(i, carry):
            slab, lbuf, sbuf, allowed = carry
            mrow = jnp.sum(jnp.where(sl == i, slab, 0), axis=0,
                           keepdims=True)            # [1, W]
            cand = jnp.where(allowed > 0, jnp.abs(mrow), -1.0)
            p = jnp.argmax(cand)                     # scalar lane index
            onehot = lane == p
            pivval = jnp.sum(jnp.where(onehot, mrow, 0))
            safe = jnp.where(pivval == 0, 1.0, pivval)
            lmask = (allowed > 0) & ~onehot
            l = jnp.where(lmask & (pivval != 0), mrow / safe, 0.0)
            # eliminate within the slab: rows r > i lose their p-lane
            # coupling times l
            colp = jnp.sum(jnp.where(onehot, slab, 0), axis=1,
                           keepdims=True)            # [bw, 1]
            slab = jnp.where(sl > i, slab - colp * l, slab)
            lbuf = jnp.where(sl == i, l, lbuf)
            sbuf = jnp.where(sl == i, jnp.where(onehot, 1.0, 0.0), sbuf)
            piv_ref[:] = jnp.where(lane_nb == j0 + i,
                                   p.astype(jnp.int32), piv_ref[:])
            return slab, lbuf, sbuf, jnp.where(onehot, 0.0, allowed)

        slab, lbuf, sbuf, allowed = lax.fori_loop(
            0, bw, col_step, (slab, lbuf, sbuf, allowed))
        ws_ref[pl.ds(j0, bw), :] = slab
        lbuf_ref[:] = lbuf
        sbuf_ref[:] = sbuf
        # Deferred trailing update.  A trailing row's pivot-lane values
        # EVOLVE during the slab (lane p_k is updated by steps i < k), so
        # the one-shot coefficients are u = (I + N)^-1 c0 with
        # N[k, i] = l_i[p_k] strictly lower (nilpotent), c0 the pivot-lane
        # values at slab start — then ws[r, :] -= sum_i u_i l_i.
        eye = (lax.broadcasted_iota(jnp.int32, (bw, bw), 0)
               == lax.broadcasted_iota(jnp.int32, (bw, bw), 1)).astype(dt)
        B = lax.dot_general(lbuf, sbuf, (((1,), (1,)), ((), ())),
                            preferred_element_type=dt, precision=_HI)
        N = jnp.where(lax.broadcasted_iota(jnp.int32, (bw, bw), 0)
                      > lax.broadcasted_iota(jnp.int32, (bw, bw), 1),
                      B.T, 0.0)
        # (I + N)^-1 = (I - N)(I + N^2)(I + N^4) ... (N nilpotent)
        inv = eye - N
        N2 = jnp.dot(N, N, preferred_element_type=dt, precision=_HI)
        steps = 1
        while 2 * steps < bw:
            inv = jnp.dot(inv, eye + N2, preferred_element_type=dt,
                          precision=_HI)
            N2 = jnp.dot(N2, N2, preferred_element_type=dt, precision=_HI)
            steps *= 2
        wsv = ws_ref[:]
        c0 = lax.dot_general(wsv, sbuf_ref[:], (((1,), (1,)), ((), ())),
                             preferred_element_type=dt, precision=_HI)
        u = jnp.dot(c0, inv.T, preferred_element_type=dt, precision=_HI)
        upd = jnp.dot(u, lbuf_ref[:], preferred_element_type=dt,
                      precision=_HI)                 # [nb, W]
        ws_ref[:] = jnp.where(rows_nb > j0 + bw - 1, wsv - upd, wsv)
        return allowed

    lax.fori_loop(0, nb // bw, slab_step, allowed0)


def _lu_factor_in_place(o_ref, *, bw: int):
    """Unpivoted LU of the square [nb, nb] tile in ``o_ref``, in place:
    packed L\\U (multipliers strictly below the diagonal, unit lower
    implied), processed in bw-row slabs."""
    nb = o_ref.shape[0]
    dt = o_ref.dtype
    lane = lax.broadcasted_iota(jnp.int32, (1, nb), 1)
    pr = lax.broadcasted_iota(jnp.int32, (bw, nb), 0)
    pc = lax.broadcasted_iota(jnp.int32, (bw, nb), 1)
    slr = lax.broadcasted_iota(jnp.int32, (bw, 1), 0)
    rows = lax.broadcasted_iota(jnp.int32, (nb, nb), 0)
    cols = lax.broadcasted_iota(jnp.int32, (nb, nb), 1)

    def slab_step(b, _):
        j0 = b * bw
        P = o_ref[pl.ds(j0, bw), :]                  # [bw, nb]

        def col_step(i, P):
            j = j0 + i
            prow = jnp.sum(jnp.where(pr == i, P, 0), axis=0, keepdims=True)
            piv = jnp.sum(jnp.where(lane == j, prow, 0))
            safe = jnp.where(piv == 0, 1.0, piv)
            cpan = jnp.sum(jnp.where(pc == j, P, 0), axis=1,
                           keepdims=True)            # [bw, 1] column j
            l = jnp.where(slr > i, cpan / safe, 0.0)
            urow = jnp.where(lane > j, prow, 0.0)
            P = jnp.where(pr > i, P - l * urow, P)
            return jnp.where((pr > i) & (pc == j), l, P)

        P = lax.fori_loop(0, bw, col_step, P)
        o_ref[pl.ds(j0, bw), :] = P
        # rows below the slab (within the tile): block solve + trailing
        A = o_ref[:]
        selT = (lax.broadcasted_iota(jnp.int32, (nb, bw), 0)
                == j0 + lax.broadcasted_iota(jnp.int32, (nb, bw), 1))
        sel = (j0 + lax.broadcasted_iota(jnp.int32, (bw, nb), 0)
               == lax.broadcasted_iota(jnp.int32, (bw, nb), 1))
        C = jnp.dot(A, selT.astype(dt), preferred_element_type=dt,
                    precision=_HI)                   # [nb, bw] slab cols
        D = jnp.dot(P, selT.astype(dt), preferred_element_type=dt,
                    precision=_HI)                   # [bw, bw] diag block
        l21 = jnp.dot(C, upper_tri_inv(D), preferred_element_type=dt,
                      precision=_HI)                 # [nb, bw]
        u12 = jnp.where(lane >= j0 + bw, P, 0.0)     # [bw, nb] slab U rows
        upd = jnp.dot(l21, u12, preferred_element_type=dt, precision=_HI)
        scat = jnp.dot(l21, sel.astype(dt), preferred_element_type=dt,
                       precision=_HI)                # l21 into slab lanes
        below = rows >= j0 + bw
        inblk = (cols >= j0) & (cols < j0 + bw)
        o_ref[:] = jnp.where(below, jnp.where(inblk, scat, A - upd), A)
        return 0

    lax.fori_loop(0, nb // bw, slab_step, 0)


def _lu_panel_kernel(p_ref, o_ref, uinv_ref, *, bw: int):
    i = pl.program_id(0)
    nb = p_ref.shape[0]
    dt = p_ref.dtype

    @pl.when(i == 0)
    def _top():
        o_ref[:] = p_ref[:]
        _lu_factor_in_place(o_ref, bw=bw)
        uinv_ref[:] = upper_tri_inv(o_ref[:])        # triu of packed tile

    @pl.when(i != 0)
    def _body():
        o_ref[:] = jnp.dot(p_ref[:], uinv_ref[:], preferred_element_type=dt,
                           precision=_HI)            # L21 = A21 U^-1


@functools.partial(jax.jit, static_argnames=("bw", "interpret"))
def lu_panel_fused(panel, bw: int = 8, interpret: bool = False):
    """Fused unpivoted LU panel: packed L\\U of [W, nb], W % nb == 0.

    Row tile 0 gets the in-place tile factor; every tile below it is one
    MXU gemm against the broadcast U^-1.  Same contract as
    getrf.panel_lu_nopiv's value output (unit lower implied)."""
    w, nb = panel.shape
    return pl.pallas_call(
        functools.partial(_lu_panel_kernel, bw=bw),
        grid=(w // nb,),
        in_specs=[pl.BlockSpec((nb, nb), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((nb, nb), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((w, nb), panel.dtype),
        scratch_shapes=[pltpu.VMEM((nb, nb), panel.dtype)],
        compiler_params=PANEL_PARAMS,
        interpret=interpret,
    )(panel)


def _lu_panel_batched_kernel(tiles_ref, col_ref, left_ref, lead_ref,
                             upd_ref, fac_ref, acc_ref, uinv_ref,
                             *, k: int, bw: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    kc = pl.num_programs(2)
    dt = col_ref.dtype
    f32 = jnp.float32
    # Tiles past problem b's own count are DEAD: identity-augmented
    # packing makes their no-pivot LU exactly the input tile (the
    # diagonal tile is I = its own packed L\\U, off-diagonal tiles are
    # 0), so they copy through without touching the MXU.
    live = k + i < tiles_ref[b]

    @pl.when(j == 0)
    def _init():
        # f32 accumulation regardless of storage dtype (bf16 inputs ride
        # the MXU's native bf16xbf16->f32 path; f32 inputs unchanged)
        acc_ref[:] = col_ref[0].astype(f32)

    @pl.when(live)
    def _update():
        # left-looking rank-k chunk: acc -= L[b, i-tile, chunk] @ U chunk
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
            upd_ref[0] = acc_ref[:].astype(dt)   # pre-factor tile

            @pl.when(i == 0)
            def _factor():
                _lu_factor_in_place(acc_ref, bw=bw)
                fac_ref[0] = acc_ref[:].astype(dt)
                uinv_ref[:] = upper_tri_inv(acc_ref[:])

            @pl.when(i != 0)
            def _trsm():
                fac_ref[0] = jnp.dot(acc_ref[:], uinv_ref[:],
                                     preferred_element_type=f32,
                                     precision=_HI).astype(dt)
                # L21 = A21 U^-1

        @pl.when(jnp.logical_not(live))
        def _dead():
            upd_ref[0] = col_ref[0]
            fac_ref[0] = col_ref[0]


@functools.partial(jax.jit, static_argnames=("k", "bw", "interpret"))
def lu_panel_batched(col, left, lead, tiles, k: int = 0, bw: int = 8,
                     interpret: bool = False):
    """Ragged batched fused no-pivot LU panel step.

    col:   [B, M, nb] trailing block columns A[:, k0:, k0:k0+nb]
    left:  [B, M, K]  packed L block rows A[:, k0:, :k0]
    lead:  [B, K, nb] packed U block column A[:, :k0, k0:k0+nb]
    tiles: [B] int32 per-problem live tile counts ceil(size / nb)
    k:     static panel index

    Same scalar-prefetch raggedness as chol_panel_batched: the grid adds
    a leading batch dimension, dead row tiles (k + i >= tiles[b]) copy
    their identity/zero input straight to both outputs, and the LEFT
    stream's index map clamps dead tiles onto the last live row so no
    fresh HBM->VMEM copies are issued for them.  Returns (upd, fac) with
    lu_panel_fused's packed L\\U contract per problem (unit lower
    implied).  Caller guarantees real f32 OR bf16 storage (accumulation
    is f32 either way), M % nb == 0, nb % bw == 0.
    """
    bsz, m, nb = col.shape
    kk = left.shape[2]
    kb = nb
    kp = max(kb, -(-kk // kb) * kb)
    if kk != kp:                             # pad K chunks with zeros
        left = jnp.pad(left, ((0, 0), (0, 0), (0, kp - kk)))
        lead = jnp.pad(lead, ((0, 0), (0, kp - kk), (0, 0)))
    upd, fac = pl.pallas_call(
        functools.partial(_lu_panel_batched_kernel, k=k, bw=bw),
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
def lu_select_pallas(chunk, nrows: jax.Array | None = None, bw: int = 8,
                     interpret: bool = False):
    """Pivot row indices [nb] of a chunk [W, nb] (W % 128 == 0 after the
    caller's padding; ``nrows`` masks the live rows, default all)."""
    W, nb = chunk.shape
    at = chunk.T
    live = (jnp.arange(W, dtype=jnp.int32)[None, :]
            < (W if nrows is None else nrows)).astype(jnp.float32)
    piv = pl.pallas_call(
        functools.partial(_lu_select_kernel, bw=bw),
        out_shape=jax.ShapeDtypeStruct((1, nb), jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((nb, W), chunk.dtype),
                        pltpu.VMEM((bw, W), chunk.dtype),
                        pltpu.VMEM((bw, W), chunk.dtype)],
        interpret=interpret,
    )(at, live)
    return piv[0]
