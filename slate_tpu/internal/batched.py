"""Ragged batched factorization drivers over the batched Pallas panels.

The serving layer packs mixed-size problems into one identity-augmented
bucket stack (serve/server.py pad_square/pad_tall): problem i of size
s_i occupies the top-left s_i x s_i of its [n, n] slot, the rest of the
diagonal is I, and filler slots are whole identity (or zero for QR row
padding).  The vmapped XLA cores then factor every slot at the FULL
bucket size — ``serve_batch`` events record the padding waste that burns.

These drivers are the ragged alternative: a left-looking blocked loop
over the bucket's block columns where every panel step is ONE batched
Pallas call (pallas_chol.chol_panel_batched / pallas_lu.lu_panel_batched
/ pallas_qr.qr_panel_batched) whose grid carries the per-problem sizes
via scalar prefetch — each problem computes only its own live tiles and
identity-completes the rest EXACTLY (dead tiles copy their input
through, which for identity-augmented packing IS their factor), so the
batched factor is bit-identical in the padding region to factoring the
augmented matrix whole and numerically equal on the live region.

Raggedness granularity: Cholesky/LU skip per row TILE (k + i >=
ceil(s_i / nb)); QR skips per PROBLEM only — its identity-augmented
padding columns own real reflectors, so a live problem factors its
whole bucket panel while zero-row filler slots pass through.

ABFT: batch_potrf re-uses the exact checksum rungs of the single-shot
driver (robust/abft.py chol_tile_check + left_product_check), vmapped
over the batch against the pre-factor panels the kernel emits — a
transient post_panel strike is detected and repaired in-batch.  The
block-column gemm checksum rung (sum_check) is not replicated: a fault
inside the fused rank-k update surfaces as a stale factored element
that the tile/panel rungs see, matching the fused single-shot path's
coverage argument (drivers/cholesky.py potrf_nopiv).

Selection is the tune/ plan cache's job: serve/batched.py routes here
only when `tune.resolve_plan` hands back a Pallas plan for the
`batch_potrf`/`batch_getrf`/`batch_geqrf` ops (SEAM011) — nothing else
imports these drivers for dispatch.

Real f32 or bf16 storage (the Pallas panels' contract); callers gate on
dtype via the serving route's normalized check.  On bf16 input every
panel accumulates in f32 inside the kernel, and the XLA glue between
panels (U12 solves, WY trailing updates, the solve readers) promotes
factor blocks to f32, computes, and demotes only the values stored back
in bf16 — solves against a bf16 factor always RETURN f32 (the refine
side of the factor-low/refine-high split; robust/precision.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..robust import abft as _abft
from ..robust import faults
from .pallas_chol import chol_panel_batched
from .pallas_lu import lu_panel_batched
from .pallas_qr import qr_panel_batched

_HI = lax.Precision.HIGHEST


def _f32(x):
    """Promote a factor block to f32 for the XLA glue between panels —
    a no-op on f32 input, so the f32 route's numerics are unchanged."""
    return x.astype(jnp.float32)


def tile_counts(sizes, nb: int):
    """Per-problem live tile counts ceil(sizes / nb), int32 [B]."""
    return ((sizes + (nb - 1)) // nb).astype(jnp.int32)


def batch_potrf(a, sizes, *, nb: int, bw: int = 8, interpret: bool = False,
                abft: bool = False):
    """Ragged batched Cholesky: lower factors of identity-augmented SPD
    slots ``a`` [B, n, n] with live sizes ``sizes`` [B], n % nb == 0.

    Returns ``(fa, counts)``: ``fa`` carries L in its lower triangle
    (the strict upper triangle keeps input values, as the single-shot
    blocked driver leaves it); ``counts`` is a batched AbftCounts —
    zeros unless ``abft``.
    """
    bsz, n, _ = a.shape
    tiles = tile_counts(sizes, nb)
    counts = jax.vmap(lambda _: _abft.zero_counts())(jnp.arange(bsz))
    fa = a
    for k in range(n // nb):
        k0, k1 = k * nb, (k + 1) * nb
        col = fa[:, k0:, k0:k1]
        left = fa[:, k0:, :k0]
        lead = jnp.swapaxes(fa[:, k0:k1, :k0], 1, 2)
        upd, fac = chol_panel_batched(col, left, lead, tiles, k=k, bw=bw,
                                      interpret=interpret)
        if abft:
            fac = faults.maybe_corrupt("post_panel", fac)
            lkk, det, cor = jax.vmap(
                lambda h, l: _abft.chol_tile_check(h, l, n_ctx=n))(
                    upd[:, :nb], fac[:, :nb])
            fac = fac.at[:, :nb].set(lkk)
            counts = _abft.add_counts(counts, jax.vmap(
                lambda d, c: _abft.count_event(d, c, k, k))(det, cor))
            if k1 < n:
                # panel X solves X L^T = R; transpose into the canonical
                # left product L X^T = R^T, verified via R's checksums
                xh, det, cor, _, pj = jax.vmap(
                    lambda l, x, rr, rc: _abft.left_product_check(
                        l, x, rr, rc, unit=False, n_ctx=n))(
                            lkk, jnp.swapaxes(fac[:, nb:], 1, 2),
                            jnp.sum(upd[:, nb:], axis=1),
                            jnp.sum(upd[:, nb:], axis=2))
                fac = fac.at[:, nb:].set(jnp.swapaxes(xh, 1, 2))
                counts = _abft.add_counts(counts, jax.vmap(
                    lambda d, c, p: _abft.count_event(
                        d, c, (k1 + p) // nb, k))(det, cor, pj))
        fa = fa.at[:, k0:, k0:k1].set(fac)
    return fa, counts


def batch_getrf(a, sizes, *, nb: int, bw: int = 8,
                interpret: bool = False):
    """Ragged batched no-pivot LU: packed L\\U of identity-augmented
    slots ``a`` [B, n, n] with live sizes ``sizes`` [B], n % nb == 0.
    Unit lower implied, same packing as getrf.panel_lu_nopiv."""
    bsz, n, _ = a.shape
    tiles = tile_counts(sizes, nb)
    fa = a
    for k in range(n // nb):
        k0, k1 = k * nb, (k + 1) * nb
        col = fa[:, k0:, k0:k1]
        left = fa[:, k0:, :k0]
        lead = fa[:, :k0, k0:k1]
        _, fac = lu_panel_batched(col, left, lead, tiles, k=k, bw=bw,
                                  interpret=interpret)
        fa = fa.at[:, k0:, k0:k1].set(fac)
        if k1 < n:
            # U12 row block: padding rows of r are exactly zero (zero A
            # rows, zero L10 rows) and the unit-lower solve against the
            # block-diagonal L11 never mixes padding and live rows, so
            # the padding region stays exactly 0.
            r = _f32(fa[:, k0:k1, k1:]) - jnp.matmul(
                _f32(fa[:, k0:k1, :k0]), _f32(fa[:, :k0, k1:]),
                precision=_HI)
            u12 = lax.linalg.triangular_solve(
                _f32(fac[:, :nb]), r, left_side=True, lower=True,
                unit_diagonal=True)
            fa = fa.at[:, k0:k1, k1:].set(u12.astype(a.dtype))
    return fa


def batch_getrs(fa, b):
    """Solve with a batched packed no-pivot L\\U: unit-lower forward
    substitution then upper back substitution.  fa [B, n, n], b
    [B, n, k].  A bf16 factor is promoted and solved in f32 (the result
    follows ``b``'s dtype, the refine-side precision)."""
    fh = _f32(fa)
    y = lax.linalg.triangular_solve(fh, _f32(b), left_side=True, lower=True,
                                    unit_diagonal=True)
    x = lax.linalg.triangular_solve(fh, y, left_side=True, lower=False)
    return x.astype(b.dtype)


def batch_geqrf(a, rows, *, nb: int, interpret: bool = False):
    """Ragged batched Householder QR of ``a`` [B, mb, n] with per-problem
    live row counts ``rows`` [B] (zero marks a filler slot), n % w == 0
    for w = min(nb, n), mb >= n.

    Returns ``(packed, ts)``: per-problem packed panels (R in/above the
    diagonal, Householder vectors below, unit diagonal implied) and the
    stacked compact-WY triangles ts [B, n//w, w, w].  Q = prod_j
    (I - V_j T_j V_j^T) over the panels in order."""
    bsz, mb, n = a.shape
    w = min(nb, n)
    packed = a
    ts = []
    for j in range(n // w):
        j0, j1 = j * w, (j + 1) * w
        m = mb - j0
        pk, t = qr_panel_batched(packed[:, j0:, j0:j1], rows,
                                 interpret=interpret)
        packed = packed.at[:, j0:, j0:j1].set(pk)
        ts.append(t)
        if j1 < n:
            v = _f32(jnp.tril(pk, -1)) + jnp.eye(m, w, dtype=jnp.float32)[None]
            c = _f32(packed[:, j0:, j1:])
            g = jnp.matmul(jnp.swapaxes(v, 1, 2), c, precision=_HI)
            g = jnp.matmul(jnp.swapaxes(_f32(t), 1, 2), g, precision=_HI)
            packed = packed.at[:, j0:, j1:].set(
                (c - jnp.matmul(v, g, precision=_HI)).astype(a.dtype))
    return packed, jnp.stack(ts, axis=1)


def batch_gels(a, b, rows, *, nb: int, interpret: bool = False):
    """Ragged batched least squares via batch_geqrf: minimize
    ||a_i x_i - b_i|| per problem.  a [B, mb, n], b [B, mb, k], returns
    ``(x [B, n, k], packed)`` with x = R^-1 (Q^T b)[:n].  A bf16 factor
    applies Q^T and solves against R in f32 (x follows ``b``'s dtype)."""
    bsz, mb, n = a.shape
    packed, ts = batch_geqrf(a, rows, nb=nb, interpret=interpret)
    w = ts.shape[2]
    y = _f32(b)
    for j in range(n // w):
        j0 = j * w
        m = mb - j0
        pk = packed[:, j0:, j0:j0 + w]
        v = _f32(jnp.tril(pk, -1)) + jnp.eye(m, w, dtype=jnp.float32)[None]
        t = _f32(ts[:, j])
        c = y[:, j0:]
        g = jnp.matmul(jnp.swapaxes(v, 1, 2), c, precision=_HI)
        g = jnp.matmul(jnp.swapaxes(t, 1, 2), g, precision=_HI)
        y = y.at[:, j0:].set(c - jnp.matmul(v, g, precision=_HI))
    x = lax.linalg.triangular_solve(_f32(packed[:, :n, :n]), y[:, :n],
                                    left_side=True, lower=False)
    return x.astype(b.dtype), packed


def batch_chol_health(fa):
    """Batched HealthInfo for batch_potrf factors, built with the same
    helper the single-shot driver uses (drivers/cholesky._chol_health):
    padding diagonal entries are exactly 1, so they never win the
    min-pivot argmin away from a genuine failure."""
    from ..drivers.cholesky import _chol_health

    def one(f):
        d = jnp.abs(jnp.diagonal(f))
        d = jnp.where(jnp.isnan(d), jnp.zeros_like(d), d)
        mi = jnp.argmin(d)
        # tril: the loop never writes the strict upper triangle, which
        # still holds input values (same as the single-shot driver)
        return _chol_health(jnp.tril(f), d[mi], mi)

    return jax.vmap(one)(fa)


def batch_lu_health(a, fa):
    """Batched HealthInfo for batch_getrf factors via
    drivers/lu._lu_health (zero/NaN pivot -> info, growth = max|L\\U| /
    max|A|; padding contributes 1s to both, never masking a blow-up)."""
    from ..drivers.lu import _lu_health

    def one(ai, fi):
        ud = jnp.abs(jnp.diagonal(fi))
        mi = jnp.argmin(ud)
        return _lu_health(fi, ud[mi], mi, jnp.max(jnp.abs(ai)))

    return jax.vmap(one)(a, fa)
