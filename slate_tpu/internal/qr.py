"""internal QR kernels: Householder panel, block reflector T, larfb apply.

Analog of the reference's QR internals (ref: src/internal/internal_geqrf.cc
+ Tile_geqrf.hh threaded panel; internal_unmqr.cc:581 larfb-style trailing
update; lapackpp larft/larfb used per tile).  TPU-first shape:

- the panel factorization is ONE fori_loop of masked rank-1 updates on the
  whole [mm, w] panel — static shapes, no per-tile objects, compiles once;
- the block-reflector triangle T is built from a single MXU gram product
  V^H V plus a w-step triangular recursion (larft Forward/Columnwise);
- trailing updates are three MXU gemms (larfb): C -= V T^(H) V^H C.

Conventions (LAPACK-compatible): A = Q R with Q = H_0 H_1 ... H_{r-1},
H_j = I - tau_j v_j v_j^H, v_j[j] = 1, v_j[:j] = 0.  The factorization
applies H_j^H (= H_j for real) to the trailing columns.  Q = I - V T V^H.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _larfg(alpha, x):
    """larfg scalar core shared by the panel loop and householder_vec: given
    the pivot ``alpha`` and the tail ``x`` (entries outside the tail MUST
    already be zeroed), return (tau, beta, scale, live).

    beta = -copysign(mu, Re(alpha)); ``live`` False (identity reflector,
    tau = 0) when mu == 0."""
    real_dt = jnp.real(x).dtype
    sigma2 = jnp.sum(jnp.real(x * jnp.conj(x)))
    mu = jnp.sqrt(jnp.real(alpha * jnp.conj(alpha)) + sigma2)
    beta = jnp.where(jnp.real(alpha) >= 0, -mu, mu).astype(real_dt)
    live = mu > 0
    safe_beta = jnp.where(live, beta, jnp.ones_like(beta))
    tau = jnp.where(live, (safe_beta - alpha) / safe_beta,
                    jnp.zeros_like(alpha))
    scale = jnp.where(live, 1 / jnp.where(live, alpha - safe_beta,
                                          jnp.ones_like(alpha)),
                      jnp.zeros_like(alpha))
    return tau, beta, scale, live


def phase_of(z):
    """z / |z| elementwise, with phase 1 where z == 0 (safe division)."""
    az = jnp.abs(z)
    return jnp.where(az > 0, z / jnp.where(az > 0, az, jnp.ones_like(az)),
                     jnp.ones_like(z))


def householder_panel(a):
    """Householder QR of a panel ``a`` [mm, w] (mm >= 1, any w).

    Returns (packed, taus): ``packed`` holds R in/above the diagonal and the
    Householder vectors below it (unit diagonal implied); ``taus`` [w].
    """
    mm, w = a.shape
    r = min(mm, w)
    rows = jnp.arange(mm)
    cols = jnp.arange(w)

    def body(j, carry):
        a, taus = carry
        colj = lax.dynamic_index_in_dim(a, j, axis=1, keepdims=False)
        alpha = lax.dynamic_index_in_dim(colj, j, axis=0, keepdims=False)
        x = jnp.where(rows > j, colj, jnp.zeros_like(colj))
        tau, beta, scale, live = _larfg(alpha, x)
        v = jnp.where(rows > j, x * scale, jnp.zeros_like(x))
        v = jnp.where(rows == j, jnp.ones_like(v), v)
        # trailing update: a[:, j+1:] -= conj(tau) v (v^H a[:, j+1:])
        wrow = jnp.conj(v) @ a                       # [w]
        wrow = jnp.where(cols > j, wrow, jnp.zeros_like(wrow))
        a = a - jnp.conj(tau) * v[:, None] * wrow[None, :]
        # write column j: R above+diag(beta), v strictly below
        newc = jnp.where(rows < j, colj, x * scale)
        newc = jnp.where(rows == j, beta.astype(a.dtype), newc)
        newc = jnp.where(live, newc, colj)           # mu==0: leave column
        a = jnp.where((cols == j)[None, :], newc[:, None], a)
        taus = taus.at[j].set(tau)
        return a, taus

    taus0 = jnp.zeros_like(a[0])         # inherits device-variance from a
    packed, taus = lax.fori_loop(0, r, body, (a, taus0))
    return packed, taus


def panel_qr_cholqr(a):
    """CholQR2 + Householder reconstruction of a tall panel [mm, w]:
    every op an MXU gemm / small batched inverse — no per-column loop.

    CholQR2: G = P^H P, R1 = chol(G)^H, Q = P R1^-1, repeated once (the
    second pass restores orthogonality to eps * kappa(Q1)^2 ~ eps).
    Reconstruction (the LAPACK dorhr_col idea): with S = diag(s),
    s_j = -phase(Q_jj), the matrix  M = E - Q S  (E = [I_w; 0]) has an
    unpivoted LU  M = V W  with V exactly the unit-lower Householder
    aggregate and W = T V1^H — so V comes from one small LU and
    T = W V1^-H.  Then A = (I - V T V^H) E (S R): the packed panel holds
    S R above the diagonal and V below, byte-compatible with
    householder_panel's output.

    Returns (packed, T, ok): ok is False when the gram Cholesky broke
    down (kappa(P)^2 beyond the dtype — callers fall back to the scan
    panel), detected as any non-finite output."""
    from .getrf import _lu_nopiv_square
    from .trsm import tri_inv_lower, tri_inv_upper
    mm, w = a.shape
    eye = jnp.eye(w, dtype=a.dtype)
    iw = jnp.arange(w)
    # only the GRAMS need 3-pass ("high", ~f32-mantissa) matmuls: the
    # cancellation Q^H Q - I is what the kappa^2 term amplifies; the tall
    # Q updates ride the default single-pass rate (their elementwise
    # error is the framework's f32-on-TPU story, and pass 2's
    # high-precision gram sees — and corrects — pass 1's products)
    hi = dict(precision=lax.Precision.HIGH)
    G = jnp.matmul(jnp.conj(a).T, a, **hi)
    L1 = jnp.linalg.cholesky(G)
    Q = a @ jnp.conj(tri_inv_lower(L1)).T
    G2 = jnp.matmul(jnp.conj(Q).T, Q, **hi)
    L2 = jnp.linalg.cholesky(G2)
    Q = Q @ jnp.conj(tri_inv_lower(L2)).T
    R = jnp.matmul(jnp.conj(L2).T, jnp.conj(L1).T, **hi)
    s = -phase_of(jnp.diagonal(Q[:w]))
    M = (-Q * s[None, :]).at[iw, iw].add(1)          # E - Q S
    lu_top = _lu_nopiv_square(M[:w])
    V1 = jnp.tril(lu_top, -1) + eye
    W = jnp.triu(lu_top)
    V2 = M[w:] @ tri_inv_upper(W)
    T = jnp.matmul(W, jnp.conj(tri_inv_lower(V1, unit_diag=True)).T, **hi)
    # A = (I - V T V^H) E (S^-1 R); S is unitary diagonal so S^-1 = conj(S)
    Rs = jnp.triu(R * jnp.conj(s)[:, None])
    packed = jnp.concatenate([Rs + jnp.tril(V1, -1), V2], axis=0)
    ok = jnp.all(jnp.isfinite(packed)) & jnp.all(jnp.isfinite(T))
    return packed, T, ok


def householder_panel_blocked(a, base_w: int = 32):
    """Blocked Householder QR of a panel [mm, w].

    Tall panels (mm >= 2 w) first try the one-shot CholQR2 +
    reconstruction route (:func:`panel_qr_cholqr`) — ~8 bandwidth passes
    over the panel, all MXU — and fall back under lax.cond to the
    recursive scan path only when the gram Cholesky breaks down
    (kappa(P) beyond ~1/sqrt(eps), or structurally rank-deficient
    panels such as the zero-padded tails of the scan-form reductions).

    The fallback splits the columns, factors left, larfbs the right
    half, factors right, and merges the T triangles —
    T = [[T1, -T1 (V1^H V2) T2], [0, T2]] (the compact WY merge,
    ref: lapack dlarft recursion / internal_geqrf's ib blocking) — with
    the sequential rank-1 loop confined to ``base_w``-wide base panels.

    Returns (packed, T) — the T triangle directly, unlike
    householder_panel's taus."""
    mm, w = a.shape
    if mm >= 2 * w and w >= 8:
        pc, Tc, ok = panel_qr_cholqr(a)
        return lax.cond(ok, lambda: (pc, Tc),
                        lambda: _householder_blocked_rec(a, base_w))
    return _householder_blocked_rec(a, base_w)


def _qr_panel_ok(dtype, mm: int, w: int) -> bool:
    """True when the tuned plan routes this panel through the Pallas
    Householder kernel (internal/pallas_qr.py): real f32, MXU-aligned
    width, and the whole [mm, w] panel + its T triangle resident in
    the kernel's VMEM budget (pallas_tri.PANEL_PARAMS), which the chip
    compiler meets up to mm * max(w, 256) = 2^21 (8192 x 256)."""
    if not (dtype == jnp.float32 and mm >= w and mm % 8 == 0
            and w % 128 == 0 and 128 <= w <= 512
            and mm * max(w, 256) <= 2 ** 21):
        return False
    from ..tune import resolve_plan
    return resolve_plan("geqrf_panel", mm, "float32").kernel == "pallas"


def geqrf_panel(a, base_w: int = 32):
    """Tuned panel seam for geqrf/gels and the mesh QR panel step.

    Routes through the plan for ("geqrf_panel", mm): the VMEM-resident
    Pallas Householder panel (qr_panel_pallas — panel + compact-WY T in
    one kernel) when the plan selects it, else
    :func:`householder_panel_blocked`.  Returns (packed, T)."""
    mm, w = a.shape
    # slate-lint: disable=TRC001 -- capability probe: reads only static shape/dtype/plan, never tracer data
    if _qr_panel_ok(a.dtype, mm, w):
        from .pallas_qr import qr_panel_pallas
        from ..util.runtime import pallas_interpret
        return qr_panel_pallas(a, interpret=pallas_interpret())
    return householder_panel_blocked(a, base_w)


def _householder_blocked_rec(a, base_w: int = 32):
    """The scan-based recursive panel (see householder_panel_blocked)."""
    mm, w = a.shape
    if w <= base_w or mm < w:
        packed, taus = householder_panel(a)
        return packed, build_t(packed, taus)
    h = w // 2
    p1, T1 = _householder_blocked_rec(a[:, :h], base_w)
    right = apply_q_left(p1, T1, a[:, h:], conj_trans=True)
    p2, T2 = _householder_blocked_rec(right[h:], base_w)
    packed = jnp.concatenate(
        [p1, jnp.concatenate([right[:h], p2], axis=0)], axis=1)
    # V2's top h rows are structurally zero: restrict the gram product to
    # V1's live rows instead of multiplying 131072-tall zero padding
    T12 = -T1 @ (jnp.conj(unit_lower(p1)[h:]).T @ unit_lower(p2)) @ T2
    T = jnp.zeros((w, w), a.dtype)
    T = T.at[:h, :h].set(T1).at[h:, h:].set(T2).at[:h, h:].set(T12)
    return packed, T


def unit_lower(packed, r: int | None = None):
    """Extract V (unit lower trapezoid) from a packed panel [mm, w]."""
    mm, w = packed.shape
    r = min(mm, w) if r is None else r
    rows = jnp.arange(mm)[:, None]
    cols = jnp.arange(w)[None, :]
    v = jnp.where(rows > cols, packed, jnp.zeros_like(packed))
    return jnp.where((rows == cols) & (cols < r),
                     jnp.ones_like(packed), v)


def build_t(packed, taus):
    """Block-reflector triangle T [w, w] (larft Forward/Columnwise):
    Q = I - V T V^H, T[j, j] = tau_j, T[:j, j] = -tau_j T V^H v_j."""
    mm, w = packed.shape
    V = unit_lower(packed)
    G = jnp.conj(V).T @ V                            # [w, w] one MXU gram
    idx = jnp.arange(w)

    def body(j, T):
        tj = lax.dynamic_index_in_dim(taus, j, axis=0, keepdims=False)
        gj = lax.dynamic_index_in_dim(G, j, axis=1, keepdims=False)
        gj = jnp.where(idx < j, gj, jnp.zeros_like(gj))
        tcol = -tj * (T @ gj)
        tcol = jnp.where(idx == j, tj, tcol)
        return jnp.where((idx == j)[None, :], tcol[:, None], T)

    T0 = jnp.zeros_like(G)               # inherits device-variance from V
    return lax.fori_loop(0, min(mm, w), body, T0)


def householder_vec(x):
    """One Householder reflector mapping x -> beta e_0 (ref: the larfg
    kernel used throughout src/internal/internal_gebr.cc / hebr.cc).

    Returns (v, tau, beta): H = I - tau v v^H, v[0] = 1, beta real.
    Zero (or already-reduced) x yields tau = 0 (identity).
    """
    alpha = x[0]
    rows = jnp.arange(x.shape[0])
    tail = jnp.where(rows > 0, x, jnp.zeros_like(x))
    tau, beta, scale, live = _larfg(alpha, tail)
    v = jnp.where(rows > 0, tail * scale, jnp.zeros_like(x))
    v = jnp.where(rows == 0, jnp.ones_like(v), v)
    return v, tau, jnp.where(live, beta, jnp.real(alpha))


# ---- larfb: apply the block reflector (ref: internal_unmqr.cc larfb path).
# Q = I - V T V^H;  Q^H = I - V T^H V^H.

def apply_q_left(packed, T, C, conj_trans: bool):
    """C := Q C (conj_trans=False) or Q^H C (True); rows of C match packed."""
    V = unit_lower(packed)
    W = jnp.conj(V).T @ C                            # [w, nc]
    Tm = jnp.conj(T).T if conj_trans else T
    return C - V @ (Tm @ W)


def apply_q_right(packed, T, C, conj_trans: bool):
    """C := C Q (conj_trans=False) or C Q^H (True); cols of C match packed."""
    V = unit_lower(packed)
    W = C @ V                                        # [nr, w]
    Tm = jnp.conj(T).T if conj_trans else T
    return C - (W @ Tm) @ jnp.conj(V).T


def rolled_apply(Vstack, Tstack, offsets, Z):
    """Z <- (prod_k Q_k) Z over a reverse lax.scan of stacked panels.

    Shared back-transform engine for the scan-form two-stage reductions
    (heev he2hb / svd ge2tb; ref: src/unmtr_he2hb.cc, unmbr_ge2tb).
    Panel k is stored from local row 0; its true position is
    ``offsets[k]`` rows down in Z, so each step zero-pads to Z's height
    and rolls — the panels' zero tails wrap to the top, landing exactly
    on the rows Q_k must not touch."""
    K = Tstack.shape[0]
    if K == 0:
        return Z
    pad_rows = Z.shape[0]

    def step(Z, xs):
        packed, T, off = xs
        V = unit_lower(packed)
        Vfull = jnp.zeros((pad_rows, V.shape[1]), V.dtype)
        Vfull = Vfull.at[: V.shape[0]].set(V)
        Vr = jnp.roll(Vfull, off, axis=0)
        Z = Z - Vr @ (T @ (jnp.conj(Vr).T @ Z))
        return Z, None

    Z, _ = lax.scan(step, Z, (Vstack[::-1], Tstack[::-1], offsets[::-1]))
    return Z
