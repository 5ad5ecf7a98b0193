"""Distributed triangular solve over the 2D block-cyclic mesh.

Analog of the reference's trsm driver bodies (ref: src/trsmB.cc ->
src/work/work_trsm.cc:395 task loops with lookahead, panel bcasts via
listBcastMT, and internal::trsm single-block-row solves).

Left-side solve op(A) X = alpha B with A triangular, B distributed
[Mt_b x Nt_b] on the same grid.  The four (uplo, op) combinations reduce to
forward substitution on an effective-lower factor or backward substitution
on an effective-upper factor; the panel of effective column k is A's column
k (op == NoTrans) or A's row k op-applied (op == Trans/ConjTrans), mirroring
how work::trsm walks the transposed matrix (work_trsm.cc).

Right-side solves run their own kernel, the mirror of the left one with
the q axis in the starring role (:func:`_trsm_right_local`).

Two left-side kernels, chosen by the driver from B's width as the
reference's method selection does (ref: method.hh select_algo):

- stationary B (:func:`_trsm_local`, trsmB).  Structure per step k
  (inside ONE shard_map program, superblocked like dist_chol —
  ~SUPERBLOCKS unrolled bodies, lax.fori_loop inside each):
    1. gather diag tile A(k,k), build effective triangle, replicate
    2. ranks owning B(k, :) solve their RHS tiles (vmapped
       triangular_solve)
    3. broadcast X(k, :) along the p axis; broadcast the effective panel
       column of A via scatter + psum (the listBcast analog)
    4. every rank updates its not-yet-solved local B rows:
       B(i, :) -= Aeff(i, k) @ X(k, :)
- stationary A (:func:`_trsmA_local`, trsmA, ref: src/trsmA.cc) for B of
  one tile column: no tile of A leaves its chip.  B's nrhs real columns
  ride replicated on every chip, lane-dense as [nrhs, Mt, nb]; per step
  the owner of A(k,k) solves x_k and broadcasts its nb*nrhs entries, the
  owners of the effective panel tiles form their updates in place, and
  one reduction of at most Mt*nb*nrhs entries hands them to every chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core.grid import AXIS_P, AXIS_Q, TILE_SPEC, Grid
from ..internal.trsm import apply_op_tile
from ..robust import faults
from ..types import Op, Uplo
from ..util.trace import span
from .dist_chol import superblock


def _trsm_local(a_loc, b_loc, alpha, *, Nt, n, p, q, lower, op_a, unit_diag,
                mtl_a, ntl_a, mtl_b, ntl_b, sb):
    r = lax.axis_index(AXIS_P)
    c = lax.axis_index(AXIS_Q)
    nb = a_loc.shape[-1]
    nbr = b_loc.shape[-1]
    dt = b_loc.dtype
    idx = jnp.arange(nb)
    zi = jnp.zeros((), jnp.int32)

    b_loc = alpha * b_loc

    eff_lower = lower if op_a is Op.NoTrans else not lower

    def step(k, b_loc):
        """Solve block row k and broadcast X(k,:) + A's effective panel."""
        with span("slate.trsm/bcast"):
            rk, ck = k % p, k % q
            kkr, kkc = k // p, k // q

            # -- effective diagonal tile (pad diagonal identity-augmented
            # so the ragged last tile stays nonsingular; B's pad rows are
            # zero so the pad solution is exactly zero) --
            vk = jnp.where(k < Nt - 1, nb, n - (Nt - 1) * nb)
            pad_eye = jnp.diag((idx >= vk).astype(a_loc.dtype))
            dtile = lax.dynamic_index_in_dim(
                lax.dynamic_index_in_dim(a_loc, kkr, axis=0, keepdims=False),
                kkc, axis=0, keepdims=False)
            dtile = jnp.where((r == rk) & (c == ck), dtile,
                              jnp.zeros((nb, nb), a_loc.dtype))
            dtile = lax.psum(lax.psum(dtile, AXIS_P), AXIS_Q)
            deff = apply_op_tile(dtile, op_a) + pad_eye

            # -- solve block row k of B on its owner row, bcast along p --
            brow = lax.dynamic_index_in_dim(b_loc, kkr, axis=0,
                                            keepdims=False)
            xk = jax.vmap(lambda bb: lax.linalg.triangular_solve(
                deff, bb, left_side=True, lower=eff_lower,
                unit_diagonal=unit_diag))(brow)
            xk = jnp.where(r == rk, xk, jnp.zeros_like(xk))
            xk = lax.psum(xk, AXIS_P)               # replicated down columns
            xk = faults.maybe_corrupt("post_collective", xk)
            row_sel = jnp.where(r == rk, xk, brow)
            b_loc = lax.dynamic_update_slice(
                b_loc, row_sel[None], (kkr.astype(jnp.int32), zi, zi, zi))

            # -- effective panel column k of A, as a global buffer --
            # op == NoTrans: tiles A(i, k) live in mesh col ck, local col kkc
            # op != NoTrans: tiles op(A(k, i)) in mesh row rk, local row kkr
            if op_a is Op.NoTrans:
                pan = lax.dynamic_index_in_dim(a_loc, kkc, axis=1,
                                               keepdims=False)
                gi_a = r + p * jnp.arange(mtl_a)
                buf = jnp.zeros((p * mtl_a, nb, nb), a_loc.dtype)
                buf = buf.at[gi_a].set(pan)
                buf = jnp.where(c == ck, buf, jnp.zeros_like(buf))
            else:
                arow = lax.dynamic_index_in_dim(a_loc, kkr, axis=0,
                                                keepdims=False)
                pan = apply_op_tile(arow, op_a)     # [ntl_a, nb, nb]
                gj_a = c + q * jnp.arange(ntl_a)
                buf = jnp.zeros((q * ntl_a, nb, nb), a_loc.dtype)
                buf = buf.at[gj_a].set(pan)
                buf = jnp.where(r == rk, buf, jnp.zeros_like(buf))
            gpan = lax.psum(lax.psum(buf, AXIS_P), AXIS_Q)
            return b_loc, xk, gpan

    def update(b_loc, k, xk, gpan, S, sr):
        """B(i,:) -= Aeff(i,k) @ X(k,:) on the not-yet-solved window."""
        with span("slate.trsm/update"):
            gi = r + p * (sr + jnp.arange(S))
            arow = gpan[gi]                         # [S, nb, nb]
            cur = lax.dynamic_slice(b_loc, (sr, zi, zi, zi),
                                    (S, ntl_b, nb, nbr))
            upd = jnp.einsum("iab,jbc->ijac", arow, xk,
                             preferred_element_type=dt)
            if eff_lower:
                mask = (gi > k)[:, None, None, None]
            else:
                mask = (gi < k)[:, None, None, None]
            new = jnp.where(mask, cur - upd, cur)
            return lax.dynamic_update_slice(b_loc, new, (sr, zi, zi, zi))

    if eff_lower:
        for k0 in range(0, Nt, sb):
            k1 = min(k0 + sb, Nt)
            S = mtl_b - ((k0 + 1) // p)             # rows that can be > k0
            S = max(S, 1)                           # degenerate, masked out

            def fwd(k, b_loc, S=S):
                b_loc, xk, gpan = step(k, b_loc)

                def upd_fn(b):
                    sr = jnp.clip(-(-(k0 + 1 - r) // p), 0,
                                  mtl_b - S).astype(jnp.int32)
                    return update(b, k, xk, gpan, S, sr)

                return lax.cond(k < Nt - 1, upd_fn, lambda b: b, b_loc)

            b_loc = lax.fori_loop(k0, k1, fwd, b_loc)
    else:
        for k0 in reversed(range(0, Nt, sb)):
            k1 = min(k0 + sb, Nt)
            S = max(-(-k1 // p), 1)                 # rows that can be < k1

            def bwd(t, b_loc, S=S, k1=k1):
                k = k1 - 1 - t

                b_loc, xk, gpan = step(k, b_loc)

                def upd_fn(b):
                    sr = jnp.zeros((), jnp.int32)
                    return update(b, k, xk, gpan, S, sr)

                return lax.cond(k > 0, upd_fn, lambda b: b, b_loc)

            b_loc = lax.fori_loop(0, k1 - k0, bwd, b_loc)

    return b_loc


def _trsmA_local(a_loc, b_loc, alpha, *, Nt, n, nrhs, p, q, lower, op_a,
                 unit_diag):
    """Left solve op(A) X = alpha B for B of one tile column, A stationary.

    B's tile column 0 lives in local column 0 of mesh column 0; its first
    ``nrhs`` columns are real, the rest of the tile is zero padding that
    never travels.  Every chip carries the whole right-hand side
    transposed, ``xb[j, i, :]`` = column j of B's tile row i, so nb is
    the minor (lane) dimension.  Each global tile of a reduced buffer has
    exactly one contributor, so a psum delivers it unchanged."""
    r = lax.axis_index(AXIS_P)
    c = lax.axis_index(AXIS_Q)
    nb = a_loc.shape[-1]
    mtl_b = b_loc.shape[0]
    dt = b_loc.dtype
    idx = jnp.arange(nb)
    eff_lower = lower if op_a is Op.NoTrans else not lower

    def to_all(tiles, own, stride, mine):
        """[nrhs, t, nb] tiles of global rows own + stride*j, from the
        chips where ``mine`` holds -> [nrhs, Nt, nb] on every chip."""
        t = tiles.shape[1]
        sel = mine & (jnp.arange(stride) == own)[None, None, :, None]
        buf = jnp.where(sel, tiles[:, :, None, :], jnp.zeros((), dt))
        buf = buf.reshape(nrhs, t * stride, nb)[:, :Nt]
        return lax.psum(buf, (AXIS_P, AXIS_Q))

    with span("slate.trsm/bcast"):
        bcol = jnp.transpose(b_loc[:, 0, :, :nrhs], (2, 0, 1))
        xb = to_all(alpha * bcol, r, p, c == 0)

    def step(t, xb):
        k = t if eff_lower else Nt - 1 - t
        rk, ck = k % p, k % q
        kkr, kkc = k // p, k // q
        with span("slate.trsm/bcast"):
            # the owner of A(k,k) solves x_k from its own tile (pad
            # diagonal identity-augmented so the ragged last tile stays
            # nonsingular; b's pad entries are zero, so x's are too)
            vk = jnp.where(k < Nt - 1, nb, n - (Nt - 1) * nb)
            pad_eye = jnp.diag((idx >= vk).astype(a_loc.dtype))
            dtile = lax.dynamic_index_in_dim(
                lax.dynamic_index_in_dim(a_loc, kkr, axis=0, keepdims=False),
                kkc, axis=0, keepdims=False)
            deff = apply_op_tile(dtile, op_a) + pad_eye
            bk = lax.dynamic_index_in_dim(xb, k, axis=1, keepdims=False)
            # rows of bk are right-hand sides: x_k deff^T = b_k
            xk = lax.linalg.triangular_solve(
                deff, bk, left_side=False, lower=eff_lower,
                transpose_a=True, unit_diagonal=unit_diag)
            xk = lax.psum(jnp.where((r == rk) & (c == ck), xk,
                                    jnp.zeros_like(xk)), (AXIS_P, AXIS_Q))
            # the site sees x_k as a stack of one tile, as it sees the
            # stationary-B kernel's block row of X
            xk = faults.maybe_corrupt("post_collective", xk[None])[0]
            xb = lax.dynamic_update_index_in_dim(xb, xk, k, axis=1)

        with span("slate.trsm/update"):
            # Aeff(i, k) x_k on the chips holding the panel's tiles:
            # op == NoTrans: A(i, k) in mesh col ck, local col kkc
            # op != NoTrans: op(A(k, i)) in mesh row rk, local row kkr
            if op_a is Op.NoTrans:
                pan = lax.dynamic_index_in_dim(a_loc, kkc, axis=1,
                                               keepdims=False)
                upd = jnp.einsum("iab,rb->ria", pan, xk,
                                 preferred_element_type=dt)
                own, stride, mine = r, p, c == ck
            else:
                pan = lax.dynamic_index_in_dim(a_loc, kkr, axis=0,
                                               keepdims=False)
                # conj(A)^T x = conj(A^T conj(x)): no pass over the tiles
                cj = jnp.conj if op_a is Op.ConjTrans else (lambda v: v)
                upd = cj(jnp.einsum("iba,rb->ria", pan, cj(xk),
                                    preferred_element_type=dt))
                own, stride, mine = c, q, r == rk
            gi = own + stride * jnp.arange(upd.shape[1])
            live = gi > k if eff_lower else gi < k
            upd = jnp.where(live[None, :, None], upd, jnp.zeros_like(upd))
            return xb - to_all(upd, own, stride, mine)

    xb = lax.fori_loop(0, Nt, step, xb)

    # X back into B's cyclic layout: my tile rows, pad rows and columns
    # zero
    xp = jnp.pad(xb, ((0, 0), (0, p * mtl_b - Nt), (0, 0)))
    xl = lax.dynamic_index_in_dim(xp.reshape(nrhs, mtl_b, p, nb), r,
                                  axis=2, keepdims=False)
    xt = jnp.transpose(xl, (1, 2, 0))                # [mtl_b, nb, nrhs]
    out = jnp.zeros_like(b_loc).at[:, 0, :, :nrhs].set(xt)
    return jnp.where(c == 0, out, jnp.zeros_like(out))


def _trsm_right_local(a_loc, b_loc, alpha, *, Nt, n, p, q, lower, op_a,
                      unit_diag, mtl_a, ntl_a, mtl_b, ntl_b, sb):
    """Right-side solve X op(A) = alpha B by column-block substitution —
    the mirror of _trsm_local with the q axis in the starring role (so no
    dense transpose round-trip is ever needed, ref: trsm.cc handles Right
    with views the same way)."""
    r = lax.axis_index(AXIS_P)
    c = lax.axis_index(AXIS_Q)
    nb = a_loc.shape[-1]
    mbr = b_loc.shape[-2]
    dt = b_loc.dtype
    idx = jnp.arange(nb)
    zi = jnp.zeros((), jnp.int32)

    b_loc = alpha * b_loc

    eff_lower = lower if op_a is Op.NoTrans else not lower
    # X Aeff = B: lower Aeff couples column k to LATER columns -> walk
    # k downward; upper walks upward

    def step(k, b_loc):
        with span("slate.trsm/bcast"):
            rk, ck = k % p, k % q
            kkr, kkc = k // p, k // q

            vk = jnp.where(k < Nt - 1, nb, n - (Nt - 1) * nb)
            pad_eye = jnp.diag((idx >= vk).astype(a_loc.dtype))
            dtile = lax.dynamic_index_in_dim(
                lax.dynamic_index_in_dim(a_loc, kkr, axis=0, keepdims=False),
                kkc, axis=0, keepdims=False)
            dtile = jnp.where((r == rk) & (c == ck), dtile,
                              jnp.zeros((nb, nb), a_loc.dtype))
            dtile = lax.psum(lax.psum(dtile, AXIS_P), AXIS_Q)
            deff = apply_op_tile(dtile, op_a) + pad_eye

            # -- solve block column k of B on its owner column, bcast
            # along q --
            bcol = lax.dynamic_index_in_dim(b_loc, kkc, axis=1,
                                            keepdims=False)
            xk = jax.vmap(lambda bb: lax.linalg.triangular_solve(
                deff, bb, left_side=False, lower=eff_lower,
                unit_diagonal=unit_diag))(bcol)
            xk = jnp.where(c == ck, xk, jnp.zeros_like(xk))
            xk = lax.psum(xk, AXIS_Q)               # replicated across rows
            xk = faults.maybe_corrupt("post_collective", xk)
            col_sel = jnp.where(c == ck, xk, bcol)
            b_loc = lax.dynamic_update_slice(
                b_loc, col_sel[:, None], (zi, kkc.astype(jnp.int32), zi, zi))

            # -- effective row k of A as a global buffer over tile columns --
            # op == NoTrans: tiles A(k, j) live in mesh row rk, local row kkr
            # op != NoTrans: tiles op(A(j, k)) in mesh col ck, local col kkc
            if op_a is Op.NoTrans:
                pan = lax.dynamic_index_in_dim(a_loc, kkr, axis=0,
                                               keepdims=False)
                gj_a = c + q * jnp.arange(ntl_a)
                buf = jnp.zeros((q * ntl_a, nb, nb), a_loc.dtype)
                buf = buf.at[gj_a].set(pan)
                buf = jnp.where(r == rk, buf, jnp.zeros_like(buf))
            else:
                acol = lax.dynamic_index_in_dim(a_loc, kkc, axis=1,
                                                keepdims=False)
                pan = apply_op_tile(acol, op_a)     # [mtl_a, nb, nb]
                gi_a = r + p * jnp.arange(mtl_a)
                buf = jnp.zeros((p * mtl_a, nb, nb), a_loc.dtype)
                buf = buf.at[gi_a].set(pan)
                buf = jnp.where(c == ck, buf, jnp.zeros_like(buf))
            gpan = lax.psum(lax.psum(buf, AXIS_P), AXIS_Q)
            return b_loc, xk, gpan

    def update(b_loc, k, xk, gpan, T, sc):
        with span("slate.trsm/update"):
            gj = c + q * (sc + jnp.arange(T))
            acol = gpan[gj]                         # [T, nb, nb] Aeff(k, j)
            cur = lax.dynamic_slice(b_loc, (zi, sc, zi, zi),
                                    (mtl_b, T, mbr, nb))
            upd = jnp.einsum("iab,jbc->ijac", xk, acol,
                             preferred_element_type=dt)
            if eff_lower:
                mask = (gj < k)[None, :, None, None]
            else:
                mask = (gj > k)[None, :, None, None]
            new = jnp.where(mask, cur - upd, cur)
            return lax.dynamic_update_slice(b_loc, new, (zi, sc, zi, zi))

    if eff_lower:
        # columns solved from high k downward; updates hit columns < k
        for k0 in reversed(range(0, Nt, sb)):
            k1 = min(k0 + sb, Nt)
            T = max(-(-k1 // q), 1)

            def bwd(t, b_loc, T=T, k1=k1):
                k = k1 - 1 - t
                b_loc, xk, gpan = step(k, b_loc)

                def upd_fn(b):
                    return update(b, k, xk, gpan, T, jnp.zeros((), jnp.int32))

                return lax.cond(k > 0, upd_fn, lambda b: b, b_loc)

            b_loc = lax.fori_loop(0, k1 - k0, bwd, b_loc)
    else:
        for k0 in range(0, Nt, sb):
            k1 = min(k0 + sb, Nt)
            T = max(ntl_b - ((k0 + 1) // q), 1)

            def fwd(k, b_loc, T=T):
                b_loc, xk, gpan = step(k, b_loc)

                def upd_fn(b):
                    sc = jnp.clip(-(-(k0 + 1 - c) // q), 0,
                                  ntl_b - T).astype(jnp.int32)
                    return update(b, k, xk, gpan, T, sc)

                return lax.cond(k < Nt - 1, upd_fn, lambda b: b, b_loc)

            b_loc = lax.fori_loop(k0, k1, fwd, b_loc)

    return b_loc


def dist_trsm_right(a_data, b_data, alpha, *, Nt, grid: Grid, lower: bool,
                    op_a: Op, unit_diag: bool, n: int | None = None,
                    sb: int | None = None):
    """Solve X op(A) = alpha B; returns X in B's cyclic storage layout."""
    mtl_a = a_data.shape[0] // grid.p
    ntl_a = a_data.shape[1] // grid.q
    mtl_b = b_data.shape[0] // grid.p
    ntl_b = b_data.shape[1] // grid.q
    n = n if n is not None else Nt * a_data.shape[-1]
    sb = sb if sb is not None else superblock(Nt)
    spec = TILE_SPEC
    fn = jax.shard_map(
        lambda a, b: _trsm_right_local(
            a, b, alpha, Nt=Nt, n=n, p=grid.p, q=grid.q, lower=lower,
            op_a=op_a, unit_diag=unit_diag, mtl_a=mtl_a, ntl_a=ntl_a,
            mtl_b=mtl_b, ntl_b=ntl_b, sb=sb),
        mesh=grid.mesh, in_specs=(spec, spec), out_specs=spec)
    return fn(a_data, b_data)


def dist_trsm_left(a_data, b_data, alpha, *, Nt, grid: Grid, lower: bool,
                   op_a: Op, unit_diag: bool, n: int | None = None,
                   sb: int | None = None, nrhs: int | None = None):
    """Solve op(A) X = alpha B; returns X in B's cyclic storage layout.

    ``nrhs``, B's real column count, asks for A to stay stationary
    (:func:`_trsmA_local`); it is honoured where B is one tile column
    (``nrhs`` <= B's tile width), and a wider B keeps the stationary-B
    kernel."""
    mtl_a = a_data.shape[0] // grid.p
    ntl_a = a_data.shape[1] // grid.q
    mtl_b = b_data.shape[0] // grid.p
    ntl_b = b_data.shape[1] // grid.q
    n = n if n is not None else Nt * a_data.shape[-1]
    spec = TILE_SPEC
    if nrhs is not None and nrhs <= b_data.shape[-1]:
        local = functools.partial(
            _trsmA_local, alpha=alpha, Nt=Nt, n=n, nrhs=nrhs, p=grid.p,
            q=grid.q, lower=lower, op_a=op_a, unit_diag=unit_diag)
    else:
        local = functools.partial(
            _trsm_local, alpha=alpha, Nt=Nt, n=n, p=grid.p, q=grid.q,
            lower=lower, op_a=op_a, unit_diag=unit_diag, mtl_a=mtl_a,
            ntl_a=ntl_a, mtl_b=mtl_b, ntl_b=ntl_b,
            sb=sb if sb is not None else superblock(Nt))
    fn = jax.shard_map(local, mesh=grid.mesh, in_specs=(spec, spec),
                       out_specs=spec)
    # one compiled program per call: an eager caller would otherwise
    # dispatch the substitution loop's body op by op
    return jax.jit(fn)(a_data, b_data)
