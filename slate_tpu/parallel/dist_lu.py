"""Distributed LU with partial pivoting over the 2D block-cyclic mesh.

Analog of the reference's getrf driver task graph (ref: src/getrf.cc:23-240):

reference step k                          | here (ONE shard_map program)
----------------------------------------- | ---------------------------------
getrf_panel: threads + panel-rank MPI,    | panel tile-column gathered to all
  MPI_Allreduce(MAXLOC) per column        |   ranks (scatter + psum), factored
  (internal_getrf.cc:20-119,              |   REPLICATED with XLA's pivoted
   Tile_getrf.hh:199-315)                 |   LU — no per-column MAXLOC
                                          |   latency (see internal/getrf.py)
listBcast(A(i,k) -> row i) + pivot bcast  | (absorbed: panel replicated)
internal::permuteRows row exchange        | batched bundle exchange: the
  (internal_swap.cc:199-320 row batches   |   <=2nb displaced rows are
   per rank pair)                         |   top_k-extracted, gathered with
                                          |   one psum along p, re-scattered
trsm U12 row + listBcast (getrf.cc:174+)  | row-k owners solve, psum-bcast
batched trailing gemm                     | one einsum per rank on its
                                          |   static-size trailing slice
pivot-left task (getrf.cc:154-172)        | bundle exchange covers all
                                          |   columns, left included

Compile-time scaling mirrors dist_chol: ~SUPERBLOCKS unrolled superblocks,
each a lax.fori_loop over its k steps.  The replicated panel buffer is the
superblock-start size (Nt-k0 tiles); each inner step ROLLS the active rows
to the top and zeroes the factored tail (zero rows lose every pivot
contest, so XLA's pivoted LU of the padded panel equals the LU of the
active panel with identity tail permutation).

The permutation is tracked as a full row-permutation vector ``perm`` with
``A[perm] == L @ U`` (identical semantics to composing the reference's
Pivot lists).  Square matrices only (gesv path); ragged last tiles handled
by identity-augmenting the pad block of the final panel.

The replicated panel factor routes through internal/getrf.py's seams
(panel_lu_nopiv / panel_lu_tournament), whose kernel choice — fused
Pallas panel, Pallas pivot selection, or XLA — comes from the autotuner
plan cache (slate_tpu.tune, docs/TUNING.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..comm.collectives import ring_bcast_from_col
from ..core.grid import AXIS_P, AXIS_Q, TILE_SPEC, Grid
from ..internal.getrf import (panel_lu, panel_lu_nopiv, panel_lu_threshold,
                              panel_lu_tournament)
from ..robust import abft as _abft
from ..robust import faults
from ..util.compat_jax import shard_map_unchecked
from ..util.trace import span
from .dist_chol import superblock


def _gather_panel(a_loc, k, p, q, mtl, r, c):
    """Replicate panel tile-column k on every rank: [p*mtl, nb, nb]."""
    nb = a_loc.shape[-1]
    kkc = k // q
    ck = k % q
    pan = lax.dynamic_index_in_dim(a_loc, kkc, axis=1, keepdims=False)
    gi_all = r + p * jnp.arange(mtl)
    buf = jnp.zeros((p * mtl, nb, nb), a_loc.dtype)
    buf = buf.at[gi_all].set(pan)
    buf = jnp.where(c == ck, buf, jnp.zeros_like(buf))
    return lax.psum(lax.psum(buf, AXIS_P), AXIS_Q)


def _gather_panel_ring(a_loc, k, p, q, mtl, r, c):
    """Ring variant of :func:`_gather_panel` for the lookahead pipeline.

    The p-axis merge stays a psum (disjoint row slots scatter-merge, not
    a broadcast), but the q-axis owner-column replication becomes a
    ppermute ring so the next panel's hops can slide underneath the
    trailing einsum that runs between issue and consumption.  Pure data
    movement — bit-identical to the psum-masked gather."""
    nb = a_loc.shape[-1]
    kkc = k // q
    ck = k % q
    pan = lax.dynamic_index_in_dim(a_loc, kkc, axis=1, keepdims=False)
    gi_all = r + p * jnp.arange(mtl)
    buf = jnp.zeros((p * mtl, nb, nb), a_loc.dtype)
    buf = buf.at[gi_all].set(pan)
    buf = jnp.where(c == ck, buf, jnp.zeros_like(buf))
    buf = lax.psum(buf, AXIS_P)
    return ring_bcast_from_col(buf, ck, q)


def _row_bundle_exchange(a_loc, out_rows, in_rows, p, r, nbundle):
    """Move rows: new A[out_rows[b], :] = old A[in_rows[b], :] for all local
    columns, with one psum along the p axis (permuteRows analog).

    out_rows/in_rows: [nbundle] global element-row indices (padded entries
    are fixed points out==in, harmless rewrites)."""
    mtl, ntl, nb, _ = a_loc.shape
    # one gather of exactly the [ntl, nb] element rows: a vmapped
    # dynamic index would gather each row's whole [ntl, nb, nb] tile row
    # first (20 GiB at n=20480 on 2x2 — more than the chip holds)
    own = ((in_rows // nb) % p) == r
    bundle = a_loc[(in_rows // nb) // p, :, in_rows % nb, :]
    bundle = jnp.where(own[:, None, None], bundle,
                       jnp.zeros_like(bundle))   # [nbundle, ntl, nb]
    bundle = lax.psum(bundle, AXIS_P)

    def scatter(a_loc, b):
        g = out_rows[b]
        lt = (g // nb) // p
        tg = g % nb
        own = ((g // nb) % p) == r
        cur = lax.dynamic_index_in_dim(a_loc, lt, axis=0, keepdims=False)
        cur = lax.dynamic_index_in_dim(cur, tg, axis=1, keepdims=False)
        new = jnp.where(own, bundle[b], cur)
        return a_loc.at[lt, :, tg, :].set(new), None

    a_loc, _ = lax.scan(scatter, a_loc, jnp.arange(nbundle))
    return a_loc


def _dist_getrf_local(a_loc, Nt, n, p, q, mtl, ntl, method: str,
                      ib: int, sb: int, tau: float = 1.0, mpt: int = 4,
                      depth: int = 2, abft: bool = False, la: int = 0):
    r = lax.axis_index(AXIS_P)
    c = lax.axis_index(AXIS_Q)
    nb = a_loc.shape[-1]
    dt = a_loc.dtype
    m_pad = p * mtl * nb
    # sb*nb slack so the dynamic window slice below never clips
    perm_g = jnp.arange(m_pad + sb * nb)
    gi_all = r + p * jnp.arange(mtl)
    idx = jnp.arange(nb)
    zi = jnp.zeros((), jnp.int32)
    # health trace: smallest |U diagonal| seen and its global element row.
    # The panel is psum-replicated, so every rank tracks identical values
    # (valid for out_specs P(); the scan-carry replication checker cannot
    # prove it, hence shard_map_unchecked in dist_getrf).
    rdt = jnp.zeros((), dt).real.dtype
    minpiv = jnp.asarray(jnp.inf, rdt)
    minidx = jnp.zeros((), jnp.int32)
    # ABFT counters, two accumulation disciplines (docs/ROBUSTNESS.md):
    # ``rep`` counts checks of psum-REPLICATED data (the panel) — every
    # rank computes the identical value, so it is never summed across the
    # mesh.  ``loc`` counts checks each rank performs on its OWN tiles
    # (U12 columns masked to the owner row, trailing tiles) and is
    # psum'd over both axes once at the end.  (det, cor, site) int32.
    neg1 = jnp.asarray(-1, jnp.int32)
    rep = (zi, zi, neg1)
    loc = (zi, zi, neg1)

    # Lookahead prologue: panel 0's gather is already in flight (carried as
    # G) when the first step starts; each step then issues step k+1's ring
    # gather before its late trailing update so the broadcast rides under
    # the einsum (ref getrf.cc lookahead task priorities).  G is the full
    # [p*mtl, nb, nb] pre-factor column — superblock-independent shape, so
    # it crosses superblock boundaries; the window slice happens at
    # consumption with the consuming superblock's static bounds.
    if la > 0:
        with span("slate.getrf/bcast_ahead"):
            G = _gather_panel_ring(a_loc, 0, p, q, mtl, r, c)

    for k0 in range(0, Nt, sb):
        k1 = min(k0 + sb, Nt)
        W0 = Nt - k0                             # panel tiles this superblock
        W = W0 * nb
        nbundle = min(2 * nb, W)
        S = mtl - ((k0 + 1) // p)                # static trailing bounds
        T = ntl - ((k0 + 1) // q)

        def super_step(k, carry, W0=W0, W=W, nbundle=nbundle, S=S, T=T,
                       k0=k0):
            if la == 0:
                a_loc, perm_g, minpiv, minidx, rep, loc = carry
            else:
                a_loc, perm_g, minpiv, minidx, rep, loc, G = carry
            rk, ck = k % p, k % q
            kkr = k // p
            vk = jnp.where(k < Nt - 1, nb, n - (Nt - 1) * nb)

            # ---- gather + factor the panel (replicated).  At la >= 1 the
            #      gather already happened at the previous step (carried in
            #      G, issued before that step's late trailing update) ----
            with span("slate.getrf/panel"):
                gpan = (_gather_panel(a_loc, k, p, q, mtl, r, c)
                        if la == 0 else G)
                panel = gpan[k0:Nt].reshape(W, nb)   # static slice
                # roll active rows (>= k) to the top, zero the factored
                # tail
                shift = (k - k0) * nb
                panel = jnp.roll(panel, -shift, axis=0)
                rows = jnp.arange(W)
                panel = jnp.where((rows < (Nt - k) * nb)[:, None], panel,
                                  jnp.zeros_like(panel))
                # ragged final tile: identity-augment its pad block (only
                # the last panel has vk < nb, and it is then the top tile)
                panel = panel + jnp.concatenate(
                    [jnp.diag((idx >= vk).astype(dt)),
                     jnp.zeros((W - nb, nb), dt)], axis=0)
                if method == "nopiv":
                    lu, perm = panel_lu_nopiv(panel)
                elif method == "tntpiv":
                    br = max(ib, nb,
                             (-(-panel.shape[0] // (mpt * nb))) * nb)
                    lu, perm = panel_lu_tournament(panel, block_rows=br,
                                                   arity=depth)
                elif tau < 1.0:
                    lu, perm = panel_lu_threshold(panel, tau)
                else:
                    lu, perm = panel_lu(panel)
                lu = faults.maybe_corrupt("post_panel", lu)
            if abft:
                # verify L\U against the pre-factor panel's checksums
                # (replicated data -> replicated counters).  Rolled row
                # i0 is global element row k*nb + i0.
                lu, det, cor, pi_, _ = _abft.lu_panel_check(
                    panel, lu, perm, n_ctx=n)
                ev = _abft.count_event(det, cor, k + pi_ // nb, k)
                rep = (rep[0] + ev.detected, rep[1] + ev.corrected,
                       jnp.where(rep[2] >= 0, rep[2], ev.site))
            lut = lu.reshape(W0, nb, nb)

            # ---- health trace: this step's U diagonal is diag(lut[0]);
            #      NaN entries count as zero pivots, pad entries (ragged
            #      final tile, idx >= vk) are excluded ----
            d = jnp.abs(jnp.diagonal(lut[0]))
            d = jnp.where(jnp.isnan(d), jnp.zeros_like(d), d)
            d = jnp.where(idx < vk, d, jnp.full_like(d, jnp.inf))
            j = jnp.argmin(d).astype(jnp.int32)
            upd = d[j] < minpiv
            minpiv = jnp.where(upd, d[j], minpiv)
            minidx = jnp.where(upd, (k * nb + j).astype(jnp.int32), minidx)

            # ---- batched row exchange for ALL columns (left + right +
            #      panel; panel values rewritten below) ----
            if method != "nopiv":
                with span("slate.getrf/swap"):
                    iota = jnp.arange(W)
                    displaced = lax.top_k((perm != iota).astype(jnp.int32),
                                          nbundle)[1]
                    out_rows = displaced + k * nb
                    in_rows = perm[displaced] + k * nb
                    a_loc = _row_bundle_exchange(a_loc, out_rows, in_rows,
                                                 p, r, nbundle)
                    pw = lax.dynamic_slice(perm_g, (k * nb,), (W,))
                    perm_g = lax.dynamic_update_slice(perm_g, pw[perm],
                                                      (k * nb,))

            # ---- write the factored panel column back (owners col ck) ----
            ltiles_all = jnp.take(lut, jnp.clip(gi_all - k, 0, W0 - 1),
                                  axis=0)        # [mtl, nb, nb]
            oldcol = lax.dynamic_index_in_dim(a_loc, k // q, axis=1,
                                              keepdims=False)
            newcol = jnp.where((gi_all >= k)[:, None, None], ltiles_all,
                               oldcol)
            col_sel = jnp.where(c == ck, newcol, oldcol)
            a_loc = lax.dynamic_update_slice(
                a_loc, col_sel[:, None],
                (zi, (k // q).astype(jnp.int32), zi, zi))

            def solve_u12(a_loc, loc):
                # ---- U12: row-k owners solve vs unit-lower L11, bcast ----
                with span("slate.getrf/trsm"):
                    l11 = lut[0]
                    urow = lax.dynamic_index_in_dim(a_loc, kkr, axis=0,
                                                    keepdims=False)
                    u12 = jax.vmap(lambda t: lax.linalg.triangular_solve(
                        l11, t, left_side=True, lower=True,
                        unit_diagonal=True))(urow)
                    gj_all = c + q * jnp.arange(ntl)
                    if abft:
                        # R's checksums ride the SAME psum as the solved
                        # tiles: the payload grows to [ntl, nb+1, nb+1] but
                        # no collective round is added.  After the bcast
                        # every rank re-verifies L11 @ U12 = R per local
                        # column tile and repairs a single struck element.
                        aug = jnp.zeros((ntl, nb + 1, nb + 1), dt)
                        aug = aug.at[:, :nb, :nb].set(u12)
                        aug = aug.at[:, :nb, nb].set(jnp.sum(urow, axis=2))
                        aug = aug.at[:, nb, :nb].set(jnp.sum(urow, axis=1))
                        aug = jnp.where(r == rk, aug, jnp.zeros_like(aug))
                        aug = lax.psum(aug, AXIS_P)
                        u12 = faults.maybe_corrupt("post_collective",
                                                   aug[:, :nb, :nb])
                        r_row, r_col = aug[:, :nb, nb], aug[:, nb, :nb]
                        u12, det_t, cor_t, _, _ = jax.vmap(
                            lambda xx, rr, cc: _abft.left_product_check(
                                l11, xx, rr, cc, unit=True,
                                n_ctx=n))(u12, r_row, r_col)
                        # count each global tile once: owner row rk only
                        live = (gj_all > k) & (r == rk)
                        det_n = jnp.sum(live & det_t, dtype=jnp.int32)
                        cor_n = jnp.sum(live & cor_t, dtype=jnp.int32)
                        tj_loc = jnp.argmax(live & det_t)
                        s = jnp.where(
                            det_n > 0,
                            _abft.site_code(k, c + q * tj_loc),
                            jnp.asarray(-1, jnp.int32))
                        loc = (loc[0] + det_n, loc[1] + cor_n,
                               jnp.where(loc[2] >= 0, loc[2], s))
                    else:
                        u12 = jnp.where(r == rk, u12, jnp.zeros_like(u12))
                        u12 = lax.psum(u12, AXIS_P)  # all ranks, own cols
                        u12 = faults.maybe_corrupt("post_collective", u12)
                    newrow = jnp.where((gj_all > k)[:, None, None], u12, urow)
                    row_sel = jnp.where(r == rk, newrow, urow)
                    a_loc = lax.dynamic_update_slice(
                        a_loc, row_sel[None], (kkr.astype(jnp.int32), zi, zi, zi))
                return a_loc, loc, u12

            def early_cols(a_loc, loc, u12):
                # ---- lookahead priority columns k+1 .. k+la: update them
                #      FIRST so the next panel gather (issued before the
                #      late trailing update below) reads finished tiles.
                #      Each rank's u12 slot cd//q holds column cd's solved
                #      tile exactly on the owner column cd % q; elsewhere
                #      (and on dead steps near the edge) the operand is
                #      zeroed, so the ABFT expectation collapses to cur's
                #      own sums and the check is clean by construction ----
                for dcol in range(1, la + 1):
                    cd = jnp.minimum(k + dcol, Nt - 1)
                    act = (k + dcol < Nt) & (c == cd % q)
                    slot = (cd // q).astype(jnp.int32)
                    lrows_e = jnp.take(lut, jnp.clip(gi_all - k, 0, W0 - 1),
                                       axis=0)
                    lrows_e = jnp.where((gi_all > k)[:, None, None], lrows_e,
                                        jnp.zeros_like(lrows_e))
                    ucol = lax.dynamic_index_in_dim(u12, slot, axis=0,
                                                    keepdims=False)[None]
                    ucol = jnp.where(act, ucol, jnp.zeros_like(ucol))
                    upd = jnp.einsum("iab,jbc->ijac", lrows_e, ucol,
                                     preferred_element_type=dt)
                    cur = lax.dynamic_slice(a_loc, (zi, slot, zi, zi),
                                            (mtl, 1, nb, nb))
                    mask = (gi_all > k)[:, None, None, None] & act
                    new = cur - upd
                    if abft:
                        exp_r = (jnp.sum(cur, axis=3)
                                 - _abft.tile_product_row_sums(
                                     lrows_e[:, None], ucol[None]))
                        exp_c = (jnp.sum(cur, axis=2)
                                 - _abft.tile_product_col_sums(
                                     lrows_e[:, None], ucol[None]))
                        new, ev, ti_l, _ = _abft.tile_sum_check(
                            new, exp_r, exp_c, n_ctx=n)
                        s = jnp.where(ev.detected > 0,
                                      _abft.site_code(gi_all[ti_l], cd),
                                      jnp.asarray(-1, jnp.int32))
                        loc = (loc[0] + ev.detected, loc[1] + ev.corrected,
                               jnp.where(loc[2] >= 0, loc[2], s))
                    a_loc = lax.dynamic_update_slice(
                        a_loc, jnp.where(mask, new, cur), (zi, slot, zi, zi))
                return a_loc, loc

            def late_gemm(a_loc, loc, u12, gj_min):
                # ---- trailing update on the static-size slice (columns
                #      > gj_min; gj_min = k at depth 0, k+la pipelined).
                #      Storage pad columns (gj >= Nt) are always late:
                #      early_cols clamps to real columns, so the junk
                #      tiles must follow the depth-0 schedule here or
                #      bit-exact storage parity between depths breaks ----
                with span("slate.getrf/gemm"):
                    sr = jnp.clip(-(-(k0 + 1 - r) // p), 0,
                                  mtl - S).astype(jnp.int32)
                    sc = jnp.clip(-(-(k0 + 1 - c) // q), 0,
                                  ntl - T).astype(jnp.int32)
                    gi = r + p * (sr + jnp.arange(S))
                    gj = c + q * (sc + jnp.arange(T))
                    lrows = jnp.take(lut, jnp.clip(gi - k, 0, W0 - 1), axis=0)
                    lrows = jnp.where((gi > k)[:, None, None], lrows,
                                      jnp.zeros_like(lrows))
                    ucols = lax.dynamic_slice(u12, (sc, zi, zi), (T, nb, nb))
                    ucols = jnp.where(((gj > gj_min) | (gj >= Nt))[:, None, None],
                                      ucols, jnp.zeros_like(ucols))
                    upd = jnp.einsum("iab,jbc->ijac", lrows, ucols,
                                     preferred_element_type=dt)
                    cur = lax.dynamic_slice(a_loc, (sr, sc, zi, zi),
                                            (S, T, nb, nb))
                    mask = ((gi > k)[:, None, None, None] &
                            ((gj > gj_min) | (gj >= Nt))[None, :, None, None])
                    new = cur - upd
                    if abft:
                        # per-tile checksum maintenance of the rank-local
                        # GEMM (masked-out tiles have lrows/ucols zeroed, so
                        # their expectation collapses to cur's own sums and
                        # they verify clean by construction)
                        exp_r = (jnp.sum(cur, axis=3)
                                 - _abft.tile_product_row_sums(
                                     lrows[:, None], ucols[None]))
                        exp_c = (jnp.sum(cur, axis=2)
                                 - _abft.tile_product_col_sums(
                                     lrows[:, None], ucols[None]))
                        new, ev, ti_l, tj_l = _abft.tile_sum_check(
                            new, exp_r, exp_c, n_ctx=n)
                        s = jnp.where(ev.detected > 0,
                                      _abft.site_code(gi[ti_l], gj[tj_l]),
                                      jnp.asarray(-1, jnp.int32))
                        loc = (loc[0] + ev.detected, loc[1] + ev.corrected,
                               jnp.where(loc[2] >= 0, loc[2], s))
                    a_loc = lax.dynamic_update_slice(
                        a_loc, jnp.where(mask, new, cur), (sr, sc, zi, zi))
                return a_loc, loc

            if la == 0:
                def tail(cr):
                    a_loc, perm_g, loc = cr
                    a_loc, loc, u12 = solve_u12(a_loc, loc)
                    a_loc, loc = late_gemm(a_loc, loc, u12, k)
                    return a_loc, perm_g, loc

                if S > 0 and T > 0:
                    # slate-lint: disable=COL003,COL005 -- k is the replicated fori_loop index and Nt is static: every rank evaluates the same predicate, so the psum branch is taken mesh-uniformly
                    a_loc, perm_g, loc = lax.cond(k < Nt - 1, tail,
                                                  lambda cr: cr,
                                                  (a_loc, perm_g, loc))
                return a_loc, perm_g, minpiv, minidx, rep, loc

            # ---- la >= 1 pipeline: solve U12 + finish the priority
            #      columns, issue step k+1's panel gather, THEN run the
            #      late trailing update (columns > k+la) so the ring hops
            #      overlap the big einsum.  The final step's issue is
            #      clamped to column Nt-1 (already factored, pure read)
            #      and its result dies with the dropped carry ----
            def head(cr):
                a_loc, loc, u12 = cr
                a_loc, loc, u12 = solve_u12(a_loc, loc)
                a_loc, loc = early_cols(a_loc, loc, u12)
                return a_loc, loc, u12

            u12 = jnp.zeros((ntl, nb, nb), dt)
            if S > 0 and T > 0:
                # slate-lint: disable=COL003,COL005 -- k is the replicated fori_loop index and Nt is static: every rank evaluates the same predicate, so the psum branch is taken mesh-uniformly
                a_loc, loc, u12 = lax.cond(k < Nt - 1, head,
                                           lambda cr: cr,
                                           (a_loc, loc, u12))
            with span("slate.getrf/bcast_ahead"):
                G = _gather_panel_ring(a_loc, jnp.minimum(k + 1, Nt - 1),
                                       p, q, mtl, r, c)
            if S > 0 and T > 0:
                a_loc, loc = lax.cond(
                    k < Nt - 1,
                    lambda cr: late_gemm(cr[0], cr[1], u12, k + la),
                    lambda cr: cr, (a_loc, loc))
            return a_loc, perm_g, minpiv, minidx, rep, loc, G

        carry = (a_loc, perm_g, minpiv, minidx, rep, loc)
        if la > 0:
            carry = carry + (G,)
        carry = lax.fori_loop(k0, k1, super_step, carry)
        if la > 0:
            a_loc, perm_g, minpiv, minidx, rep, loc, G = carry
        else:
            a_loc, perm_g, minpiv, minidx, rep, loc = carry

    ldet = lax.psum(lax.psum(loc[0], AXIS_P), AXIS_Q)
    lcor = lax.psum(lax.psum(loc[1], AXIS_P), AXIS_Q)
    lsite = lax.pmax(lax.pmax(loc[2], AXIS_P), AXIS_Q)
    adet = rep[0] + ldet
    acor = rep[1] + lcor
    asite = jnp.where(rep[2] >= 0, rep[2], lsite)
    return a_loc, perm_g[:m_pad], minpiv, minidx, adet, acor, asite


def dist_permute_rows(b_data, perm, grid: Grid):
    """Sharded application of a global row permutation:
    new B[g, :] = old B[perm[g], :] (the getrs pivot-apply,
    ref: src/getrs.cc permuteRows + internal_swap.cc batches).

    Each rank all-gathers its tile-COLUMN strip along the p axis — memory
    m x n/q per rank, a 1/q slice of the matrix, never a replicated dense
    copy — then gathers its own rows from the strip.

    Works for any B row tiling, including mb != the LU's nb: ``perm``
    entries for real rows are always < m (dist_getrf zeroes factored/pad
    tail rows so they lose every pivot contest, and the ragged pad block is
    identity-augmented), real element-row indices are tiling-independent,
    and ``perm_pad`` extends identity over B's OWN padded row space here.
    tests/test_lu.py::test_mesh_getrs_mismatched_b_tiling covers this."""
    p, q = grid.p, grid.q
    mtl = b_data.shape[0] // p
    mb = b_data.shape[2]
    m_pad = p * mtl * mb
    perm_pad = jnp.concatenate(
        [jnp.asarray(perm),
         jnp.arange(perm.shape[0], m_pad)]).astype(jnp.int32)

    def local(b_loc, perm_pad):
        r = lax.axis_index(AXIS_P)
        ntl = b_loc.shape[1]
        nbr = b_loc.shape[3]
        allb = lax.all_gather(b_loc, AXIS_P)       # [p, mtl, ntl, mb, nbr]
        # element-rows-major view of the full column strip:
        # global row g at strip index (g//mb % p, g//mb // p, :, g % mb, :)
        strip = allb.transpose(0, 1, 3, 2, 4).reshape(p * mtl * mb,
                                                      ntl, nbr)
        gt = r + p * jnp.arange(mtl)               # my global tile rows
        gr = (gt[:, None] * mb + jnp.arange(mb)[None, :]).reshape(-1)
        src = perm_pad[gr]                         # source element rows
        st_, so = src // mb, src % mb
        strip_idx = (st_ % p) * (mtl * mb) + (st_ // p) * mb + so
        mine = strip[strip_idx]                    # [mtl*mb, ntl, nbr]
        return mine.reshape(mtl, mb, ntl, nbr).transpose(0, 2, 1, 3)

    spec = TILE_SPEC
    fn = jax.shard_map(local, mesh=grid.mesh, in_specs=(spec, P()),
                       out_specs=spec)
    return fn(b_data, perm_pad)


def dist_rbt_two_sided(data, u_levels, v_levels, grid: Grid, n: int):
    """Sharded two-sided butterfly transform U^T diag(A, I_pad) V on
    block-cyclic storage (drivers/lu.py getrf_rbt mesh path; butterflies
    from internal/rbt.py).

    Each rank all-gathers its tile-COLUMN strip along the p axis to apply
    the row butterflies in global element order, then its tile-ROW strip
    along the q axis for the column butterflies — memory is a 1/q (then
    1/p) slice of the matrix, never a replicated dense copy (the
    dist_permute_rows discipline).  The butterfly diagonals are host-seeded
    trace constants replicated on every rank, so each application is pure
    elementwise work on the gathered strip: O(d m^2/q) flops per rank, no
    matmuls, and only the two all_gathers as communication."""
    from ..internal import rbt
    p, q = grid.p, grid.q
    mtl = data.shape[0] // p
    ntl = data.shape[1] // q
    nb = data.shape[-1]
    m_pad = p * mtl * nb
    # identity-augment the pad diagonal: the transform must act on
    # diag(A, I), not diag(A, 0) (pads are zero by the canonical invariant)
    if m_pad > n:
        g = jnp.arange(n, m_pad)
        data = data.at[g // nb, g // nb, g % nb, g % nb].set(1)

    def local(a_loc, lu, lv):
        r = lax.axis_index(AXIS_P)
        c = lax.axis_index(AXIS_Q)
        gidx = jnp.arange(m_pad)
        # strip index of global element row/col g (see dist_permute_rows)
        si = ((gidx // nb % p) * (mtl * nb) + (gidx // nb // p) * nb
              + gidx % nb)
        sj = ((gidx // nb % q) * (ntl * nb) + (gidx // nb // q) * nb
              + gidx % nb)
        # row pass: U^T @ (.) on the full column strip in global row order
        allp = lax.all_gather(a_loc, AXIS_P)      # [p, mtl, ntl, nb, nb]
        strip = allp.transpose(0, 1, 3, 2, 4).reshape(m_pad, ntl, nb)
        ordered = rbt.apply_axis(lu, strip[si], "t", 0)
        gr = ((r + p * jnp.arange(mtl))[:, None] * nb
              + jnp.arange(nb)[None, :]).reshape(-1)
        a_loc = ordered[gr].reshape(mtl, nb, ntl, nb).transpose(0, 2, 1, 3)
        # column pass: (.) @ V on the full row strip in global column order
        allq = lax.all_gather(a_loc, AXIS_Q)      # [q, mtl, ntl, nb, nb]
        cstrip = allq.transpose(1, 3, 0, 2, 4).reshape(mtl, nb,
                                                       q * ntl * nb)
        cordered = rbt.apply_axis(lv, cstrip[:, :, sj], "t", 2)
        gc = ((c + q * jnp.arange(ntl))[:, None] * nb
              + jnp.arange(nb)[None, :]).reshape(-1)
        return cordered[:, :, gc].reshape(mtl, nb, ntl, nb).transpose(
            0, 2, 1, 3)

    spec = TILE_SPEC
    fn = jax.shard_map(local, mesh=grid.mesh, in_specs=(spec, P(), P()),
                       out_specs=spec)
    return fn(data, u_levels, v_levels)


def dist_getrf(data, Nt: int, grid: Grid, n: int, method: str = "partial",
               ib: int = 16, sb: int | None = None, tau: float = 1.0,
               mpt: int = 4, depth: int = 2, abft: bool = False,
               la: int | None = None):
    """Factor square cyclic storage in place; returns
    (data, perm, minpiv, minidx, abft_detected, abft_corrected,
    abft_site) with A[perm] = L @ U (perm over the padded row space,
    identity on pads).  ``minpiv``/``minidx`` are the smallest
    |U diagonal| encountered and its global element row — replicated
    scalars feeding drivers/lu.py's HealthInfo.

    ``abft`` (static) turns on Huang-Abraham checksum verification of
    every panel, U12 bcast and trailing update (robust/abft.py): single
    struck elements are repaired in place and counted in the three
    trailing replicated int32 scalars (all zero / -1 when off or clean).

    ``tau`` (Option.PivotThreshold) < 1 switches the partial-pivot panel to
    threshold pivoting; ``mpt`` (Option.MaxPanelThreads) sizes the CALU
    tournament row blocks; ``depth`` (Option.Depth) its tree fan-in.

    ``la`` (0/1/2, static) is the lookahead pipeline depth — NOT the CALU
    ``depth`` above: at la >= 1 each step rings the NEXT panel's gather
    ahead of its late trailing update (and finishes columns k+1..k+la
    first so the gather reads complete tiles).  Bit-identical to la=0.
    None resolves the tuned depth via the ``dist_lookahead`` plan
    (SEAM011)."""
    if la is None:
        from ..tune import lookahead_depth
        la = lookahead_depth(n, data.dtype.name)
    mtl = data.shape[0] // grid.p
    ntl = data.shape[1] // grid.q
    sb = sb if sb is not None else superblock(Nt)
    spec = TILE_SPEC
    fn = shard_map_unchecked(
        lambda a: _dist_getrf_local(a, Nt, n, grid.p, grid.q, mtl, ntl,
                                    method, ib, sb, tau, mpt, depth, abft,
                                    la=la),
        mesh=grid.mesh, in_specs=(spec,),
        out_specs=(spec, P(), P(), P(), P(), P(), P()))
    return fn(data)
