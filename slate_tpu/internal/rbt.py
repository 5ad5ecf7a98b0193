"""internal::rbt — recursive random butterfly transforms (RBT / PRBT).

Partial pivoting is a sequential, latency-bound row hunt per panel column;
on TPU it is the one part of LU that cannot feed the MXU (a
pre-benchmark measurement: the CALU tournament's ~400 ms pivoting wall
at n=16384 vs posv's 66 ms pivot-free Cholesky floor).  The classical
alternative (Parker '95; Baboulin et al., "Accelerating linear system
solutions using randomization techniques") is to precondition with random
butterflies so that NO pivoting is needed with probability ~1: factor

    A~ = U^T diag(A, I_pad) V,      x = V y,   A~ y = U^T [b; 0]

with U, V independent depth-``d`` recursive butterflies.  A butterfly of
size s is

    B = (1/sqrt(2)) [[R0,  R1],
                     [R0, -R1]]

with R0, R1 random diagonal — so applying B (or B^T, or B^-1) to a vector
is one add/sub of its halves plus a diagonal scale: O(s) elementwise work,
no matmul.  A depth-d recursive butterfly is W = L_0 L_1 ... L_{d-1} where
L_0 is one full-size butterfly and L_l is block-diagonal with 2^l
butterflies of size n/2^l; the two-sided transform costs O(d n^2) total
and every entry of A~ mixes 4^d entries of A, which destroys the
adversarial structure (zero leading pivots, growth drivers) that makes
NoPiv LU unsafe.

Exactness is what makes the transform certifiable: with entries
r = exp(u/10), u ~ U(-1/2, 1/2), each level is exactly invertible
elementwise (B^-1 is B^T with R -> R^-1 and the same 1/sqrt(2) scale), so
apply -> unapply round-trips to the identity at working precision — see
tests/test_rbt.py.

This module is pure mechanism: host-seeded constants + jnp elementwise
combines, no Options, no policy.  The driver seam lives in
drivers/lu.py:getrf_rbt and robust/recovery.py (speculate-then-certify).

Butterfly representation: a tuple of ``depth`` levels, level ``l`` being a
pair ``(r0, r1)`` of flat [n/2] real arrays — the concatenated top-half /
bottom-half diagonals of that level's 2^l butterflies.  The levels are
generated with HOST numpy from a static seed, so under jit they are trace
constants (the same discipline as robust/faults.py fault positions).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

#: element padding granularity for a depth-2 transform
DEFAULT_DEPTH = 2


def padded_size(n: int, depth: int = DEFAULT_DEPTH) -> int:
    """Smallest multiple of 2**depth that is >= n (and >= 2**depth)."""
    m = 1 << depth
    return max(-(-int(n) // m) * m, m)


def generate(n: int, depth: int = DEFAULT_DEPTH, seed: int = 0,
             dtype=jnp.float64):
    """A random depth-``depth`` butterfly of size ``n`` (n % 2**depth == 0)
    as a tuple of per-level ``(r0, r1)`` diagonal pairs.

    Entries are exp(u/10), u ~ U(-1/2, 1/2) (Baboulin et al.'s scaling):
    positive, O(1), and exactly invertible elementwise.  ``dtype`` may be
    complex; the diagonals are always its real counterpart."""
    if n <= 0 or n % (1 << depth):
        raise ValueError(
            f"rbt.generate: n={n} must be a positive multiple of "
            f"2**depth={1 << depth}")
    rdt = np.finfo(np.dtype(dtype)).dtype
    rng = np.random.default_rng(seed)
    levels = []
    for _ in range(depth):
        r = np.exp(rng.uniform(-0.5, 0.5, size=n) / 10.0).astype(rdt)
        levels.append((jnp.asarray(r[: n // 2]), jnp.asarray(r[n // 2:])))
    return tuple(levels)


def _combine(r0, r1, top, bot, mode, s):
    """One butterfly block: B = s[[R0, R1], [R0, -R1]], s = 1/sqrt(2)."""
    if mode == "n":                         # B x
        return s * (r0 * top + r1 * bot), s * (r0 * top - r1 * bot)
    if mode == "t":                         # B^T x
        return s * r0 * (top + bot), s * r1 * (top - bot)
    if mode == "inv":                       # B^-1 x  (B^T with R -> R^-1)
        return s * (top + bot) / r0, s * (top - bot) / r1
    # "invt": B^-T x  (B with R -> R^-1)
    return s * (top / r0 + bot / r1), s * (top / r0 - bot / r1)


def apply_axis(levels, x, mode: str, axis: int = 0):
    """Apply W = L_0 L_1 ... L_{d-1} (or its transpose/inverse) along one
    axis of ``x``.  ``mode``: "n" W, "t" W^T, "inv" W^-1, "invt" W^-T.
    Pure jnp — traces through jit/shard_map unchanged."""
    x = jnp.moveaxis(jnp.asarray(x), axis, 0)
    n = x.shape[0]
    d = len(levels)
    s = float(np.sqrt(0.5))
    # W x applies the innermost (smallest-block) level first; W^T / W^-1
    # reverse the product, so they apply the full-size level first.
    order = range(d) if mode in ("t", "inv") else range(d - 1, -1, -1)
    for lev in order:
        r0, r1 = levels[lev]
        nblk = 1 << lev
        half = n // nblk // 2
        shp = (nblk, half) + (1,) * (x.ndim - 1)
        r0b = jnp.asarray(r0).reshape(shp)
        r1b = jnp.asarray(r1).reshape(shp)
        xb = x.reshape(nblk, 2, half, *x.shape[1:])
        top, bot = _combine(r0b, r1b, xb[:, 0], xb[:, 1], mode, s)
        x = jnp.stack([top, bot], axis=1).reshape(n, *x.shape[1:])
    return jnp.moveaxis(x, 0, axis)


def apply_left(levels, x):
    """W @ x — the solution back-transform x = V y."""
    return apply_axis(levels, x, "n", 0)


def apply_left_t(levels, x):
    """W^T @ x — the RHS forward transform U^T b."""
    return apply_axis(levels, x, "t", 0)


def apply_left_inv(levels, x):
    """W^-1 @ x (exact elementwise inverse; round-trip tests)."""
    return apply_axis(levels, x, "inv", 0)


def apply_right(levels, a):
    """a @ W — the column side of the two-sided transform."""
    # a @ W == (W^T a^T)^T: the "t" combine along axis 1, same level order.
    return apply_axis(levels, a, "t", 1)


def transform(a, u_levels, v_levels):
    """A~ = U^T A V (two independent butterflies, O(d n^2) elementwise)."""
    return apply_right(v_levels, apply_left_t(u_levels, a))


def untransform(at, u_levels, v_levels):
    """A = U^-T A~ V^-1 — exact inverse of :func:`transform`."""
    left = apply_axis(u_levels, at, "invt", 0)      # U^-T A~
    return apply_axis(v_levels, left, "invt", 1)    # ... V^-1
