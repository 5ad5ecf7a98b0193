"""LU tests: getrf/getrs/gesv across methods and targets, incl. an
adversarial row-scaled matrix that fails without pivoting (analog of ref
test/test_gesv.cc residual checks)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st


def adversarial(rng, n):
    """Row-scaled so no-pivot LU loses many digits: tiny leading pivot."""
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    a[0, 0] = 1e-14
    return a


@pytest.mark.parametrize("n,nb", [(24, 8), (30, 7)])
def test_getrf_single(rng, n, nb):
    a = rng.standard_normal((n, n))
    A = st.Matrix.from_numpy(a, nb)
    F = st.getrf(A)
    l = np.tril(F.LU.to_numpy(), -1) + np.eye(n)
    u = np.triu(F.LU.to_numpy())
    perm = np.asarray(F.perm)
    np.testing.assert_allclose(l @ u, a[perm], rtol=1e-11, atol=1e-11)


def test_getrf_rectangular(rng):
    m, n, nb = 20, 12, 4
    a = rng.standard_normal((m, n))
    F = st.getrf(st.Matrix.from_numpy(a, nb))
    lu = F.LU.to_numpy()
    l = np.tril(lu, -1)[:, :n] + np.eye(m, n)
    u = np.triu(lu)[:n]
    np.testing.assert_allclose(l @ u, a[np.asarray(F.perm)],
                               rtol=1e-11, atol=1e-11)


@pytest.mark.parametrize("method", ["partial", "tntpiv"])
def test_gesv_adversarial_single(rng, method):
    n, nb = 24, 8
    a = adversarial(rng, n)
    b = rng.standard_normal((n, 3))
    A = st.Matrix.from_numpy(a, nb)
    B = st.Matrix.from_numpy(b, nb)
    opts = {st.Option.MethodLU:
            st.MethodLU.CALU if method == "tntpiv" else st.MethodLU.PartialPiv}
    F, X = st.gesv(A, B, opts)
    x = X.to_numpy()
    resid = np.linalg.norm(a @ x - b) / (np.linalg.norm(a) *
                                         np.linalg.norm(x))
    assert resid < 1e-13
    # no-pivot on the same matrix must be catastrophically worse
    Fn, Xn = st.gesv_nopiv(A, B)
    xn = Xn.to_numpy()
    residn = np.linalg.norm(a @ xn - b) / (np.linalg.norm(a) *
                                           np.linalg.norm(xn))
    assert residn > 1e-8


@pytest.mark.parametrize("p,q", [(2, 2), (2, 4)])
@pytest.mark.parametrize("n,nb", [(24, 4), (22, 5)])
@pytest.mark.slow
def test_gesv_mesh(rng, p, q, n, nb):
    g = st.Grid(p, q, devices=jax.devices()[: p * q])
    a = adversarial(rng, n)
    b = rng.standard_normal((n, 4))
    A = st.Matrix.from_numpy(a, nb, nb, g)
    B = st.Matrix.from_numpy(b, nb, nb, g)
    F, X = st.gesv(A, B)
    x = X.to_numpy()
    resid = np.linalg.norm(a @ x - b) / (np.linalg.norm(a) *
                                         np.linalg.norm(x) * n)
    assert resid < 1e-14


@pytest.mark.slow
def test_getrf_mesh_factors(rng):
    """Mesh factors reproduce A[perm] = L U exactly, pads clean."""
    n, nb, p, q = 18, 4, 2, 2
    g = st.Grid(p, q, devices=jax.devices()[: p * q])
    a = rng.standard_normal((n, n))
    F = st.getrf(st.Matrix.from_numpy(a, nb, nb, g))
    lu = F.LU.to_numpy()
    l = np.tril(lu, -1) + np.eye(n)
    u = np.triu(lu)
    np.testing.assert_allclose(l @ u, a[np.asarray(F.perm)],
                               rtol=1e-11, atol=1e-11)
    canon = np.asarray(F.LU.storage.canonical())
    assert np.all(canon[-1, :, 2:, :] == 0)      # pad rows zero
    assert np.all(canon[:, -1, :, :, ][..., 2:] == 0)


@pytest.mark.slow
def test_gesv_nopiv_mesh(rng):
    n, nb = 16, 4
    g = st.Grid(2, 2, devices=jax.devices()[:4])
    a = rng.standard_normal((n, n)) + n * np.eye(n)   # diagonally dominant
    b = rng.standard_normal((n, 2))
    F, X = st.gesv_nopiv(st.Matrix.from_numpy(a, nb, nb, g),
                         st.Matrix.from_numpy(b, nb, nb, g))
    x = X.to_numpy()
    assert np.linalg.norm(a @ x - b) / np.linalg.norm(b) < 1e-12


@pytest.mark.slow
def test_gesv_tntpiv_mesh(rng):
    n, nb = 16, 4
    g = st.Grid(2, 2, devices=jax.devices()[:4])
    a = adversarial(rng, n)
    b = rng.standard_normal((n, 2))
    opts = {st.Option.MethodLU: st.MethodLU.CALU}
    F, X = st.gesv(st.Matrix.from_numpy(a, nb, nb, g),
                   st.Matrix.from_numpy(b, nb, nb, g), opts)
    x = X.to_numpy()
    assert np.linalg.norm(a @ x - b) / np.linalg.norm(b) < 1e-11


@pytest.mark.slow
def test_mesh_getrs_mismatched_b_tiling(rng):
    """Mesh getrs fast path with B.mb != LU.nb (B pads differently):
    dist_permute_rows builds perm_pad over B's own padded row space, so
    mismatched tilings must still solve exactly (ADVICE r3 invariant)."""
    n, nb, mbB = 22, 5, 4         # LU tiles 5x5, B tiles 4-wide rows
    g = st.Grid(2, 2, devices=jax.devices()[:4])
    a = adversarial(rng, n)
    b = rng.standard_normal((n, 3))
    F = st.getrf(st.Matrix.from_numpy(a, nb, nb, g))
    B = st.Matrix.from_numpy(b, mbB, 3, g)
    x = st.getrs(F, B).to_numpy()
    resid = np.linalg.norm(a @ x - b) / (np.linalg.norm(a) *
                                         np.linalg.norm(x) * n)
    assert resid < 1e-14


def test_getri(rng):
    n = 16
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    Ainv = st.getriOOP(st.Matrix.from_numpy(a, 4))
    np.testing.assert_allclose(Ainv.to_numpy() @ a, np.eye(n),
                               rtol=1e-11, atol=1e-10)


def test_gesv_under_jit(rng):
    n = 16
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    b = rng.standard_normal((n, 2))
    A = st.Matrix.from_numpy(a, 4)
    B = st.Matrix.from_numpy(b, 4)

    @jax.jit
    def solve(A, B):
        _, X = st.gesv(A, B)
        return X

    x = solve(A, B).to_numpy()
    assert np.linalg.norm(a @ x - b) / np.linalg.norm(b) < 1e-12


@pytest.mark.parametrize("W,w,dtype", [(300, 128, np.float64),
                                       (257, 40, np.float32),
                                       (96, 64, np.complex128)])
def test_recursive_panel_matches_partial_pivoting(rng, W, w, dtype):
    """The recursive panel (taken past XLA_LU_MAX_ROWS) factors P A = L U
    with LAPACK's pivots on real panels; complex panels pivot on |z|
    rather than |re| + |im|, so those are held to the factorization."""
    from jax import lax

    from slate_tpu.internal.getrf import panel_lu_recursive
    a = rng.standard_normal((W, w)).astype(dtype)
    if np.iscomplexobj(a):
        a = a + 1j * rng.standard_normal((W, w))
    lu, perm = panel_lu_recursive(jnp.asarray(a), base=16)
    lu, perm = np.asarray(lu), np.asarray(perm)
    L = np.tril(lu, -1)[:, :w] + np.eye(W, w)
    U = np.triu(lu[:w])
    tol = 1e-4 if dtype == np.float32 else 1e-11
    np.testing.assert_allclose(L @ U, a[perm], atol=tol * w)
    if not np.iscomplexobj(a):
        _, _, ref = lax.linalg.lu(jnp.asarray(a))
        assert (perm == np.asarray(ref)).all()
