"""Pallas TPU kernel tests, run in interpret mode on the CPU backend
(the real-TPU lowering is compiled for a described v5e in
test_tpu_compile.py and run on the chip by chip_smoke.py)."""

import numpy as np
import jax.numpy as jnp
import pytest

from slate_tpu.internal.pallas_chol import chol_tile_pallas


@pytest.mark.parametrize("n,bw", [(128, 128), (512, 128), (256, 8)])
def test_pallas_chol_interpret(rng, n, bw):
    a0 = rng.standard_normal((n, n)).astype(np.float32) * 0.01
    a = a0 @ a0.T + 4 * np.eye(n, dtype=np.float32)
    L = np.asarray(chol_tile_pallas(jnp.asarray(a), bw=bw, interpret=True))
    np.testing.assert_allclose(L, np.linalg.cholesky(a), atol=5e-6)
    assert np.all(np.triu(L, 1) == 0)      # exact-zero upper contract


@pytest.mark.parametrize("W,nb", [(256, 32), (1024, 128)])
def test_pallas_lu_select_interpret(rng, W, nb):
    # pivot order must match the XLA LU oracle exactly
    from jax import lax
    from slate_tpu.internal.pallas_lu import lu_select_pallas
    a = jnp.asarray(rng.standard_normal((W, nb)).astype(np.float32))
    piv = np.asarray(lu_select_pallas(a, interpret=True))
    ref = np.asarray(lax.linalg.lu(a)[2])[:nb]
    np.testing.assert_array_equal(piv, ref)


def test_pallas_lu_select_ragged_interpret(rng):
    from jax import lax
    from slate_tpu.internal.pallas_lu import lu_select_pallas
    a = jnp.asarray(rng.standard_normal((160, 32)).astype(np.float32))
    ap = jnp.zeros((256, 32), jnp.float32).at[:160].set(a)
    piv = np.asarray(lu_select_pallas(ap, nrows=160, interpret=True))
    ref = np.asarray(lax.linalg.lu(a)[2])[:32]
    np.testing.assert_array_equal(piv, ref)


# ---- fused panel kernels (PR 7) ------------------------------------------


def _spd_panel(rng, m, nb, k):
    """(col, left, lead) such that col - left @ lead has an SPD top block;
    returns the expected fused outputs from a NumPy oracle too."""
    base = rng.standard_normal((m, nb)).astype(np.float32)
    top = base[:nb] @ base[:nb].T / nb + nb * np.eye(nb, dtype=np.float32)
    target = np.concatenate([top, base[nb:]], axis=0)
    left = rng.standard_normal((m, k)).astype(np.float32) * 0.01
    lead = left[:nb].T.copy()
    col = target + left @ lead
    lkk = np.linalg.cholesky(target[:nb])
    l21 = target[nb:] @ np.linalg.inv(lkk).T
    fac = np.concatenate([lkk, l21], axis=0)
    return col, left, lead, target, fac


@pytest.mark.parametrize("nb,bw", [(128, 8), (128, 16), (256, 8), (256, 16)])
def test_chol_panel_fused_interpret(rng, nb, bw):
    from slate_tpu.internal.pallas_chol import chol_panel_fused
    m, k = 3 * nb, nb
    col, left, lead, target, fac_ref = _spd_panel(rng, m, nb, k)
    upd, fac = chol_panel_fused(jnp.asarray(col), jnp.asarray(left),
                                jnp.asarray(lead), bw=bw, interpret=True)
    np.testing.assert_allclose(np.asarray(upd), target, atol=1e-4)
    np.testing.assert_allclose(np.asarray(fac), fac_ref,
                               rtol=2e-5, atol=1e-4)


def test_chol_panel_fused_empty_history_interpret(rng):
    """k=0 (first panel): no history, fused output is just the factor."""
    from slate_tpu.internal.pallas_chol import chol_panel_fused
    nb, m = 128, 256
    col, _, _, target, fac_ref = _spd_panel(rng, m, nb, nb)
    left = jnp.zeros((m, 0), jnp.float32)
    lead = jnp.zeros((0, nb), jnp.float32)
    upd, fac = chol_panel_fused(jnp.asarray(target), left, lead,
                                bw=8, interpret=True)
    np.testing.assert_allclose(np.asarray(upd), target, atol=1e-4)
    np.testing.assert_allclose(np.asarray(fac), fac_ref,
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("nb,bw", [(128, 8), (128, 16), (256, 16)])
def test_lu_panel_fused_interpret(rng, nb, bw):
    """Fused no-pivot LU panel matches the XLA panel_lu_nopiv packing."""
    from slate_tpu.internal.getrf import panel_lu_nopiv
    from slate_tpu.internal.pallas_lu import lu_panel_fused
    w = 3 * nb
    a = rng.standard_normal((w, nb)).astype(np.float32)
    a[:nb] += nb * np.eye(nb, dtype=np.float32)       # diagonally dominant
    got = np.asarray(lu_panel_fused(jnp.asarray(a), bw=bw, interpret=True))
    from slate_tpu.tune import XLA_PLAN, plan_override
    with plan_override("getrf_panel", XLA_PLAN):
        ref, perm = panel_lu_nopiv(jnp.asarray(a))
    np.testing.assert_array_equal(np.asarray(perm), np.arange(w))
    np.testing.assert_allclose(got, np.asarray(ref), rtol=2e-5, atol=1e-4)
    # and L\\U actually reconstructs A
    L = np.tril(got[:nb], -1) + np.eye(nb, dtype=np.float32)
    L = np.concatenate([L, got[nb:]], axis=0)
    U = np.triu(got[:nb])
    np.testing.assert_allclose(L @ U, a, rtol=2e-4, atol=5e-4)


@pytest.mark.parametrize("m,w", [(256, 128), (512, 128), (512, 256)])
def test_qr_panel_pallas_interpret(rng, m, w):
    """Pallas Householder panel is bit-compatible with householder_panel
    and its compact-WY T reconstructs Q."""
    from slate_tpu.internal.qr import build_t, householder_panel, unit_lower
    from slate_tpu.internal.pallas_qr import qr_panel_pallas
    a = jnp.asarray(rng.standard_normal((m, w)).astype(np.float32))
    packed, T = qr_panel_pallas(a, interpret=True)
    ref_packed, taus = householder_panel(a)
    np.testing.assert_allclose(np.asarray(packed), np.asarray(ref_packed),
                               rtol=1e-5, atol=1e-5)
    ref_T = build_t(ref_packed, taus)
    np.testing.assert_allclose(np.asarray(T), np.asarray(ref_T),
                               rtol=1e-4, atol=1e-5)
    # Q R == A through the compact-WY form
    V = np.asarray(unit_lower(packed))
    R = np.triu(np.asarray(packed)[:w])
    Q = np.eye(m, dtype=np.float32) - V @ np.asarray(T) @ V.T
    np.testing.assert_allclose(Q @ np.concatenate(
        [R, np.zeros((m - w, w), np.float32)]), np.asarray(a),
        rtol=1e-4, atol=1e-4)


# ---- fused path through the drivers (plan_override) ----------------------


def _pallas_plan(nb, bw=8):
    from slate_tpu.tune import TilePlan
    return TilePlan(kernel="pallas", nb=nb, bw=bw)


@pytest.mark.parametrize("n,nb", [(384, 128), (448, 128), (640, 256)])
def test_driver_chol_fused_parity(rng, n, nb):
    """_potrf_dense_blocked through the fused panel (incl. ragged trailing
    edges) matches jnp.linalg.cholesky."""
    from slate_tpu.drivers.cholesky import _potrf_dense_blocked
    from slate_tpu.tune import plan_override
    a0 = rng.standard_normal((n, n)).astype(np.float32) * 0.1
    a = jnp.asarray(a0 @ a0.T + n * np.eye(n, dtype=np.float32))
    with plan_override("potrf_panel", _pallas_plan(nb)):
        L, _ = _potrf_dense_blocked(a, nb)
    ref = np.asarray(jnp.linalg.cholesky(a))
    np.testing.assert_allclose(np.tril(np.asarray(L)), ref,
                               rtol=2e-4, atol=2e-3)


def test_driver_chol_fused_abft_single_strike(rng):
    """ABFT repairs a single injected fault THROUGH the fused panel step:
    the factor matches the clean run and no residual corruption leaks."""
    from slate_tpu.drivers.cholesky import _potrf_dense_blocked
    from slate_tpu.robust import faults
    from slate_tpu.tune import plan_override
    n, nb = 384, 128
    a0 = rng.standard_normal((n, n)).astype(np.float32) * 0.1
    a = jnp.asarray(a0 @ a0.T + n * np.eye(n, dtype=np.float32))
    # seed chosen to land the strike in the tile's LOWER triangle: on the
    # exact-zero upper half a multiplicative bitflip is a no-op
    plan = faults.FaultPlan("post_panel", kind="bitflip", seed=2,
                            transient=True)
    with plan_override("potrf_panel", _pallas_plan(nb)):
        clean, _ = _potrf_dense_blocked(a, nb, abft=True)
        with faults.inject(plan):
            hit, counts = _potrf_dense_blocked(a, nb, abft=True)
    assert int(counts.detected) == 1 and int(counts.corrected) == 1
    np.testing.assert_allclose(np.tril(np.asarray(hit)),
                               np.tril(np.asarray(clean)),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,nb", [(384, 128), (512, 256)])
def test_driver_lu_nopiv_fused_parity(rng, n, nb):
    """panel_lu_nopiv through the fused kernel matches its XLA path."""
    from slate_tpu.internal.getrf import panel_lu_nopiv
    from slate_tpu.tune import plan_override
    a = rng.standard_normal((n, nb)).astype(np.float32)
    a[:nb] += nb * np.eye(nb, dtype=np.float32)
    from slate_tpu.tune import XLA_PLAN
    with plan_override("getrf_panel", XLA_PLAN):
        ref, ref_perm = panel_lu_nopiv(jnp.asarray(a))
    with plan_override("getrf_panel", _pallas_plan(nb)):
        got, perm = panel_lu_nopiv(jnp.asarray(a))
    np.testing.assert_array_equal(np.asarray(perm), np.asarray(ref_perm))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=5e-4)


def test_driver_qr_fused_parity(rng):
    """geqrf through the tuned Pallas panel matches the XLA R (up to
    column signs) and reconstructs A."""
    from slate_tpu.drivers.qr import _geqrf_dense_blocked
    from slate_tpu.tune import XLA_PLAN, plan_override
    m, n, nb = 384, 128, 128
    a = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
    with plan_override("geqrf_panel", XLA_PLAN):
        ref = _geqrf_dense_blocked(a, nb)
    with plan_override("geqrf_panel", _pallas_plan(nb)):
        got = _geqrf_dense_blocked(a, nb)
    np.testing.assert_allclose(np.abs(np.triu(np.asarray(got[0])[:n])),
                               np.abs(np.triu(np.asarray(ref[0])[:n])),
                               rtol=1e-4, atol=1e-4)
