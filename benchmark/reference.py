"""The plain reference: residuals on the host in f64, and plain solvers.

Nothing here imports the program.  A solve is judged by what it says:
its residual against the operands the benchmark made, in f64 on the
host, after the window.

The plain solvers (blocked right-looking LU with partial pivoting,
blocked Cholesky) exist for the control: the same answers computed with every matrix product at a given
precision, ``prec``:

- ``highest``: f32 products (XLA's HIGHEST);
- ``default``: one bf16 pass, operands rounded to bf16 (XLA's DEFAULT
  on the TPU);
- ``fp8``: operands rounded to float8 e4m3 (scaled by their largest
  magnitude), the step below one bf16 pass.

The panels and diagonal blocks are factored in f32 element by element;
everything else, the triangular solves included, goes through products.
"""

from __future__ import annotations

import numpy as np

EPS32 = float(np.finfo(np.float32).eps)
PRECISIONS = ("highest", "default", "fp8")


# ----------------------------------------------------- host f64 residuals


def residual_measures(anorm_inf, x, b, r) -> dict:
    """Each residual measure per column, by the name a configuration's
    ``limits`` give it (see :data:`RESIDUALS`)."""
    return {k: f(anorm_inf, x, b, r) for k, f in RESIDUALS.items()}


def residual_over_b(anorm_inf, x, b, r) -> np.ndarray:
    """``||r||_inf / ||b||_inf`` per column: how far A x lands from b,
    against b itself.  HPL's scaled residual divides by ``||A|| ||x||``
    too, and a solve whose x has grown along A's smallest singular
    direction grows that divisor with its error; this one does not."""
    with np.errstate(invalid="ignore", divide="ignore"):
        out = np.abs(r).max(axis=0) / np.abs(b).max(axis=0)
    return np.where(np.isfinite(out), out, np.inf)


def hpl_scaled(anorm_inf, x, b, r) -> np.ndarray:
    """HPL's scaled residual per column, ``||r||_inf / (eps (||A||_inf
    ||x||_inf + ||b||_inf) n)``, eps of f32; HPL passes a run under 16."""
    n = x.shape[0]
    rn = np.abs(r).max(axis=0)
    xn = np.abs(x).max(axis=0)
    bn = np.abs(b).max(axis=0)
    with np.errstate(invalid="ignore", divide="ignore"):
        out = rn / (EPS32 * (anorm_inf * xn + bn) * n)
    return np.where(np.isfinite(out), out, np.inf)


RESIDUALS = {"hpl_scaled_residual": hpl_scaled,
             "residual_over_b": residual_over_b}


def shifted_residuals(a, shifts, x, b, rows: int = 2048):
    """Residuals of ``(A + s_j I) x_j = b`` for every column j, in f64 on
    the host, ``rows`` rows of A at a time.  Returns ``(r, anorm_inf)``
    with ``anorm_inf[j] = ||A + s_j I||_inf``."""
    n = a.shape[0]
    s = np.asarray(shifts, np.float64)
    x64 = np.asarray(x, np.float64)
    r = np.empty_like(x64)
    rowsum = np.empty((n, len(s)))
    for r0 in range(0, n, rows):
        blk = a[r0:r0 + rows].astype(np.float64)
        r[r0:r0 + rows] = (np.asarray(b, np.float64)[r0:r0 + rows]
                           - blk @ x64 - s[None, :] * x64[r0:r0 + rows])
        d = np.diagonal(blk, offset=r0)
        absum = np.abs(blk).sum(axis=1)
        rowsum[r0:r0 + rows] = (absum[:, None] - np.abs(d)[:, None]
                                + np.abs(d[:, None] + s[None, :]))
    return r, rowsum.max(axis=0)


# ------------------------------------------------------- plain solvers


def _jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def product(a, b, prec: str):
    """``a @ b`` with the operands rounded as ``prec`` says, every
    product of the rounded parts exact and summed in f32: the same
    arithmetic on any backend, so a CPU test reads what the chip reads."""
    jax, jnp = _jnp()

    def mm(x, y):
        return jnp.matmul(x, y, precision=jax.lax.Precision.HIGHEST)

    if prec == "highest":
        return mm(a, b)
    if prec == "fp8":
        return mm(round_mantissa(a, 3), round_mantissa(b, 3))
    if prec == "default":
        return mm(round_mantissa(a, 7), round_mantissa(b, 7))
    raise ValueError(f"no precision {prec!r} (one of {PRECISIONS})")


def round_mantissa(x, bits: int):
    """f32 ``x`` rounded to nearest (ties to even) on ``bits`` stored
    mantissa bits: 7 is bfloat16's, 3 float8 e4m3's (its exponent range
    left out).  Done on the bit pattern, so that no compiler can fold the
    rounding away."""
    jax, jnp = _jnp()
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    drop = 23 - bits
    odd = (u >> drop) & jnp.uint32(1)
    u = (u + jnp.uint32((1 << (drop - 1)) - 1) + odd) & jnp.uint32(
        (0xFFFFFFFF << drop) & 0xFFFFFFFF)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _tri_inverse(t, lower: bool, unit: bool):
    jax, jnp = _jnp()
    eye = jnp.eye(t.shape[0], dtype=t.dtype)
    with jax.default_matmul_precision("highest"):
        return jax.scipy.linalg.solve_triangular(
            t, eye, lower=lower, unit_diagonal=unit)


def _substitute(t, y, nb: int, lower: bool, unit: bool, prec: str):
    """Blocked forward (lower) or back (upper) substitution t z = y:
    z_k = inv(t_kk) (y_k - t_k,done z_done), both products at ``prec``."""
    jax, jnp = _jnp()
    n = t.shape[0]
    blocks = n // nb
    cols = jnp.arange(n)

    def body(step, z):
        k = step if lower else blocks - 1 - step
        k0 = k * nb
        rowblk = jax.lax.dynamic_slice(t, (k0, 0), (nb, n))
        done = (cols < k0) if lower else (cols >= k0 + nb)
        rhs = (jax.lax.dynamic_slice(y, (k0, 0), (nb, y.shape[1]))
               - product(jnp.where(done[None, :], rowblk, 0.0), z, prec))
        tkk = jax.lax.dynamic_slice(t, (k0, k0), (nb, nb))
        zk = product(_tri_inverse(tkk, lower, unit), rhs, prec)
        return jax.lax.dynamic_update_slice(z, zk, (k0, 0))

    return jax.lax.fori_loop(0, blocks, body, jnp.zeros_like(y))


def lu_solve(a, b, nb: int, prec: str):
    """x of a x = b by blocked right-looking LU with partial pivoting.
    ``a``'s order must be a multiple of ``nb``."""
    jax, jnp = _jnp()
    n = a.shape[0]
    rows = jnp.arange(n)
    cols = jnp.arange(nb)

    def panel(k, carry):
        a, perm = carry
        k0 = k * nb

        def column(j, carry):
            """One column of the panel ``slab`` (rows x nb), unblocked:
            pivot search below the diagonal, row swap, multipliers,
            rank-1 update of the panel's later columns."""
            slab, p = carry
            c = k0 + j
            colv = jax.lax.dynamic_slice(slab, (0, j), (n, 1))[:, 0]
            piv = jnp.argmax(jnp.where(rows >= c, jnp.abs(colv), -1.0))
            rc, rp = slab[c], slab[piv]
            slab = slab.at[c].set(rp).at[piv].set(rc)
            pc, pp = p[c], p[piv]
            p = p.at[c].set(pp).at[piv].set(pc)
            colv = jax.lax.dynamic_slice(slab, (0, j), (n, 1))[:, 0]
            lcol = jnp.where(rows > c, colv / slab[c, j], 0.0)
            urow = jnp.where(cols > j, slab[c], 0.0)
            slab = slab - lcol[:, None] * urow[None, :]
            slab = jax.lax.dynamic_update_slice(
                slab, jnp.where(rows > c, lcol, colv)[:, None], (0, j))
            return slab, p

        slab = jax.lax.dynamic_slice(a, (0, k0), (n, nb))
        slab, p = jax.lax.fori_loop(0, nb, column, (slab, rows))
        # the panel's row interchanges, applied to every column at once
        a = jax.lax.dynamic_update_slice(a[p], slab, (0, k0))
        perm = perm[p]
        k1 = k0 + nb
        l11 = jax.lax.dynamic_slice(a, (k0, k0), (nb, nb))
        rowslab = jax.lax.dynamic_slice(a, (k0, 0), (nb, n))
        right = jnp.arange(n) >= k1
        u12 = product(_tri_inverse(l11, True, True),
                      jnp.where(right[None, :], rowslab, 0.0), prec)
        rowslab = jnp.where(right[None, :], u12, rowslab)
        a = jax.lax.dynamic_update_slice(a, rowslab, (k0, 0))
        colslab = jax.lax.dynamic_slice(a, (0, k0), (n, nb))
        l21 = jnp.where((rows >= k1)[:, None], colslab, 0.0)
        return a - product(l21, u12, prec), perm

    a, perm = jax.lax.fori_loop(0, n // nb, panel, (a, jnp.arange(n)))
    y = _substitute(a, b[perm], nb, True, True, prec)
    return _substitute(a, y, nb, False, False, prec)


def cholesky_solve(a, b, nb: int, prec: str):
    """x of a x = b for symmetric positive definite a, by blocked
    right-looking Cholesky (lower).  ``a``'s order must be a multiple of
    ``nb``."""
    jax, jnp = _jnp()
    n = a.shape[0]
    rows = jnp.arange(n)

    def panel(k, a):
        k0 = k * nb
        k1 = k0 + nb
        akk = jax.lax.dynamic_slice(a, (k0, k0), (nb, nb))
        lkk = jnp.linalg.cholesky(akk)
        colslab = jax.lax.dynamic_slice(a, (0, k0), (n, nb))
        below = (rows >= k1)[:, None]
        l21 = product(jnp.where(below, colslab, 0.0),
                      _tri_inverse(lkk, True, False).T, prec)
        colslab = jnp.where(below, l21, colslab)
        colslab = jax.lax.dynamic_update_slice(colslab, lkk, (k0, 0))
        a = jax.lax.dynamic_update_slice(a, colslab, (0, k0))
        return a - product(l21, l21.T, prec)

    a = jax.lax.fori_loop(0, n // nb, panel, a)
    lower = jnp.tril(a)
    y = _substitute(lower, b, nb, True, False, prec)
    return _substitute(lower.T, y, nb, False, False, prec)
