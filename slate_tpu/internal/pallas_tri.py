"""Shared in-kernel triangular inverse for the fused Pallas panels.

The fused panel kernels (pallas_chol / pallas_lu) turn their TRSM stage
into one MXU gemm per row tile by materializing U^-1 once on the
diagonal tile.  Inside a Mosaic kernel there is no triangular_solve, so
the inverse is built from the factorization U = D (I + N) with D the
diagonal and N strictly upper — N is nilpotent, hence

    (I + N)^-1 = (I - N)(I + N^2)(I + N^4) ...   (log2(n) MXU dots)

is EXACT in exact arithmetic (same trick as pallas_lu's deferred
trailing update, just at tile scale).  U^-1 = (I + N)^-1 D^-1.

Everything here is plain jnp on values (no refs), so the helper runs
unchanged inside a Pallas kernel, under interpret=True, or in a host
test.  Masks use iota comparisons rather than tril/triu so Mosaic never
sees a bool vector cross a loop boundary.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas import tpu as pltpu

_HI = lax.Precision.HIGHEST
# Scoped VMEM for the whole-tile panel kernels (fused/batched Cholesky
# and LU, the Householder panel).  Their in-kernel temporaries outgrow
# the compiler's 16 MiB default at the widths the seams admit (nb=512,
# QR panels past 2048 x 256; lane-padded [m, 1] columns count as
# [m, 128]).  Half of v5e's 128 MiB VMEM compiles every admitted shape
# (tests/test_tpu_compile.py).
PANEL_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 2 ** 20)


def upper_tri_inv(u):
    """Inverse of an upper-triangular [n, n] (nonzero diagonal; entries
    below the diagonal are ignored)."""
    n = u.shape[0]
    dt = u.dtype
    r = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye = (r == c).astype(dt)
    u = jnp.where(r <= c, u, 0.0)
    dcol = jnp.sum(jnp.where(r == c, u, 0), axis=1, keepdims=True)  # [n, 1]
    drow = jnp.sum(jnp.where(r == c, u, 0), axis=0, keepdims=True)  # [1, n]
    N = u * (1.0 / dcol) - eye                   # strictly upper, nilpotent
    inv = eye - N
    N2 = jnp.dot(N, N, preferred_element_type=dt, precision=_HI)
    steps = 1
    while 2 * steps < n:
        inv = jnp.dot(inv, eye + N2, preferred_element_type=dt,
                      precision=_HI)
        N2 = jnp.dot(N2, N2, preferred_element_type=dt, precision=_HI)
        steps *= 2
    return inv * (1.0 / drow)                    # (I + N)^-1 D^-1
