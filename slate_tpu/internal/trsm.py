"""internal::trsm — triangular solve against one diagonal tile, batched over
a tile column/row.

Analog of the reference's internal_trsm.cc:481 / internal_trsmA.cc (single
block row/col solve, batched on device via blas::batch::trsm).  Here the
batch is a vmapped XLA triangular_solve over the [batch, mb, nb] tile array.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..types import Op


def apply_op_tile(t, op: Op):
    if op is Op.Trans:
        return t.swapaxes(-1, -2)
    if op is Op.ConjTrans:
        return jnp.conj(t).swapaxes(-1, -2)
    return t


def trsm_tile_batch(tri, b_batch, *, left: bool, lower: bool,
                    unit_diag: bool = False, op_tri: Op = Op.NoTrans):
    """Solve op(T) X = B (left) or X op(T) = B (right) for each tile in
    b_batch [batch, mb, nb] against one triangular tile T."""
    t = apply_op_tile(tri, op_tri)
    low = lower if op_tri is Op.NoTrans else not lower
    return jax.vmap(lambda b: lax.linalg.triangular_solve(
        t, b, left_side=left, lower=low, unit_diagonal=unit_diag))(b_batch)


def tri_inv_lower(L, unit_diag: bool = False, base: int = 32):
    """Explicit inverse of a lower-triangular [n, n] block in LOG depth.

    The reference's trsm panels run forward substitution (one column of
    latency per step); on TPU a [15872, 512] triangular_solve measured
    675 GFLOP/s (a pre-benchmark measurement) because the per-column
    While loop cannot feed the MXU.  The MAGMA-style alternative is to invert the
    nb x nb diagonal block once and turn every panel solve into one MXU
    gemm.  This inversion is itself log-depth and fully batched:

      inv([[A, 0], [C, B]]) = [[inv(A), 0], [-inv(B) C inv(A), inv(B)]]

    All ``base``-sized diagonal blocks are inverted in ONE batched
    triangular_solve, then each doubling level merges all sibling pairs
    with two batched matmuls — ~log2(n/base) * 3 device ops total, vs n
    sequential column steps.  Pads to a power-of-two multiple of ``base``
    with an identity diagonal (exact: the inverse of blockdiag(L, I) is
    blockdiag(inv(L), I))."""
    n = L.shape[0]
    dt = L.dtype
    if n <= base:
        return lax.linalg.triangular_solve(
            L, jnp.eye(n, dtype=dt), left_side=True, lower=True,
            unit_diagonal=unit_diag)
    n2 = base
    while n2 < n:
        n2 *= 2
    if n2 > n:
        r = jnp.arange(n, n2)
        Lp = jnp.zeros((n2, n2), dt).at[:n, :n].set(L).at[r, r].set(1)
    else:
        Lp = L
    m = n2 // base
    i = jnp.arange(m)
    d = Lp.reshape(m, base, m, base)[i, :, i, :]       # [m, base, base]
    eye = jnp.eye(base, dtype=dt)
    X = jax.vmap(lambda t: lax.linalg.triangular_solve(
        t, eye, left_side=True, lower=True,
        unit_diagonal=unit_diag))(d)
    s = base
    while s < n2:
        m2 = X.shape[0] // 2
        A, B = X[0::2], X[1::2]
        Ls = Lp.reshape(n2 // s, s, n2 // s, s)
        j = jnp.arange(m2)
        C = Ls[2 * j + 1, :, 2 * j, :]                 # [m2, s, s]
        off = -jnp.einsum("bij,bjk,bkl->bil", B, C, A)
        top = jnp.concatenate([A, jnp.zeros_like(A)], axis=2)
        bot = jnp.concatenate([off, B], axis=2)
        X = jnp.concatenate([top, bot], axis=1)
        s *= 2
    return X[0][:n, :n]


def tri_inv_upper(U, unit_diag: bool = False, base: int = 32):
    """inv(U) for upper-triangular U via the lower-triangular engine:
    inv(U) = inv(U^T)^T."""
    return tri_inv_lower(U.T, unit_diag=unit_diag, base=base).T


def _diag_tiles(ad, K: int, nb: int):
    """[K, nb, nb] diagonal blocks of a [K nb, K nb] dense matrix."""
    i = jnp.arange(K)
    return ad.reshape(K, nb, K, nb)[i, :, i, :]


def _pad_tri(ad, nb: int):
    """Identity-augment a triangular [n, n] up to the next multiple of nb.

    blockdiag(A, I) is triangular whichever triangle A lives in (the pad's
    off-diagonal blocks are zero) and the identity diagonal is invariant
    under transpose/conjugate, so padding BEFORE the op is exact:
    solving against blockdiag(op(A), I) with zero-padded B rows/cols
    yields the unpadded solution in the leading n slice."""
    n = ad.shape[0]
    n2 = -(-n // nb) * nb
    if n2 == n:
        return ad, n
    r = jnp.arange(n, n2)
    return (jnp.zeros((n2, n2), ad.dtype).at[:n, :n].set(ad)
            .at[r, r].set(1)), n


def _checksum_repair(a_op, x, bd, *, eff_lower: bool, unit: bool):
    """Verify the finished solve ``a_op @ x == bd`` through bd's
    Huang-Abraham checksums and repair ONE corrupted element of x in
    place (robust/abft.py, lazy import — robust pulls in the driver
    layer at package init).  The upper-triangular case is index-reversed
    into the canonical lower-left product: ``P A P`` is lower for the
    reversal permutation P, column sums are P-invariant and row sums
    P-equivariant."""
    from ..robust.abft import left_product_check
    r_row = jnp.sum(bd, axis=1)
    r_col = jnp.sum(bd, axis=0)
    if eff_lower:
        x2, _, _, _, _ = left_product_check(a_op, x, r_row, r_col,
                                            unit=unit)
        return x2
    x2, _, _, _, _ = left_product_check(a_op[::-1, ::-1], x[::-1],
                                        r_row[::-1], r_col, unit=unit)
    return x2[::-1]


def trsm_left_blocked(ad, bd, *, lower: bool, trans: bool, conj: bool,
                      unit: bool, nb: int, check: bool = False):
    """Solve op(A) X = B, A triangular [n, n], by block substitution with
    ALL diagonal blocks inverted in one batched log-depth pass
    (tri_inv_lower) — each step is then two MXU gemms.  A ragged n (not a
    multiple of nb) is identity-augmented to the next block boundary
    (exact; see _pad_tri).

    XLA's monolithic triangular_solve runs a per-column While loop
    (4.1 TFLOP/s on [16384, 256], a pre-benchmark measurement); this is
    the reference's work_trsm block sweep (ref: work/work_trsm.cc)
    reshaped so every op is a matmul."""
    ad, n0 = _pad_tri(ad, nb)
    n = ad.shape[0]
    if n > n0:
        bd = jnp.zeros((n, bd.shape[1]), bd.dtype).at[:n0].set(bd)
    K = n // nb
    a_op = jnp.conj(ad) if conj else ad
    if trans:
        a_op = a_op.T
    eff_lower = lower != trans
    d = _diag_tiles(a_op, K, nb)
    if eff_lower:
        dinv = jax.vmap(lambda t: tri_inv_lower(t, unit_diag=unit))(d)
    else:
        dinv = jax.vmap(lambda t: tri_inv_upper(t, unit_diag=unit))(d)
    xs = [None] * K
    order = range(K) if eff_lower else range(K - 1, -1, -1)
    for k in order:
        k0, k1 = k * nb, (k + 1) * nb
        acc = bd[k0:k1]
        if eff_lower and k > 0:
            x_done = jnp.concatenate(xs[:k], axis=0)
            acc = acc - a_op[k0:k1, :k0] @ x_done
        elif not eff_lower and k < K - 1:
            x_done = jnp.concatenate(xs[k + 1:], axis=0)
            acc = acc - a_op[k0:k1, k1:] @ x_done
        xs[k] = dinv[k] @ acc
    x = jnp.concatenate(xs, axis=0)
    if check:
        x = _checksum_repair(a_op, x, bd, eff_lower=eff_lower, unit=unit)
    return x[:n0]


def trsm_right_blocked(ad, bd, *, lower: bool, trans: bool, conj: bool,
                       unit: bool, nb: int, check: bool = False):
    """Solve X op(A) = B by block substitution over block columns (right
    side twin of trsm_left_blocked; ragged n identity-augmented)."""
    ad, n0 = _pad_tri(ad, nb)
    n = ad.shape[0]
    if n > n0:
        bd = jnp.zeros((bd.shape[0], n), bd.dtype).at[:, :n0].set(bd)
    K = n // nb
    a_op = jnp.conj(ad) if conj else ad
    if trans:
        a_op = a_op.T
    eff_lower = lower != trans
    d = _diag_tiles(a_op, K, nb)
    if eff_lower:
        dinv = jax.vmap(lambda t: tri_inv_lower(t, unit_diag=unit))(d)
    else:
        dinv = jax.vmap(lambda t: tri_inv_upper(t, unit_diag=unit))(d)
    xs = [None] * K
    # X_k depends on later X_j for lower (B_k - sum_{j>k} X_j A[j,k]),
    # earlier for upper
    order = range(K - 1, -1, -1) if eff_lower else range(K)
    for k in order:
        k0, k1 = k * nb, (k + 1) * nb
        acc = bd[:, k0:k1]
        if eff_lower and k < K - 1:
            x_done = jnp.concatenate(xs[k + 1:], axis=1)
            acc = acc - x_done @ a_op[k1:, k0:k1]
        elif not eff_lower and k > 0:
            x_done = jnp.concatenate(xs[:k], axis=1)
            acc = acc - x_done @ a_op[:k0, k0:k1]
        xs[k] = acc @ dinv[k]
    x = jnp.concatenate(xs, axis=1)
    if check:
        # X op(A) = B  <=>  op(A)^T X^T = B^T: the left check transposed
        x = _checksum_repair(a_op.T, x.T, bd.T,
                             eff_lower=not eff_lower, unit=unit).T
    return x[:, :n0]
