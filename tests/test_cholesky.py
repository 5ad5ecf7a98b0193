"""Cholesky chain tests: potrf/potrs/posv/potri + trsm/trmm/herk residuals on
single device and 2x2 / 2x4 meshes (analog of ref test/test_posv.cc,
test_potrf.cc residual methodology: ||Ax-b|| / (||A|| ||x|| n)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st


def spd(rng, n, dtype=np.float64):
    a = rng.standard_normal((n, n)).astype(dtype)
    if np.issubdtype(dtype, np.complexfloating):
        a = a + 1j * rng.standard_normal((n, n))
    return a @ a.conj().T + n * np.eye(n)


@pytest.mark.parametrize("n,nb", [(16, 4), (23, 5), (32, 8)])
def test_potrf_single(rng, n, nb):
    a = spd(rng, n)
    A = st.HermitianMatrix.from_numpy(a, nb, st.Uplo.Lower)
    L = st.potrf(A)
    l = L.to_numpy()
    np.testing.assert_allclose(l @ l.T, a, rtol=1e-12, atol=1e-10)


def test_potrf_upper(rng):
    a = spd(rng, 12)
    A = st.HermitianMatrix.from_numpy(a, 4, st.Uplo.Upper)
    U = st.potrf(A)
    u = U.to_numpy()
    assert np.allclose(np.tril(u, -1), 0)
    np.testing.assert_allclose(u.T @ u, a, rtol=1e-12, atol=1e-10)


@pytest.mark.parametrize("p,q", [(2, 2), (2, 4)])
@pytest.mark.parametrize("n,nb", [(24, 4), (18, 5)])
def test_potrf_mesh(rng, p, q, n, nb):
    g = st.Grid(p, q, devices=jax.devices()[: p * q])
    a = spd(rng, n)
    A = st.HermitianMatrix.from_numpy(a, nb, st.Uplo.Lower, g)
    L = st.potrf(A)
    l = L.to_numpy()
    np.testing.assert_allclose(l @ l.T, a, rtol=1e-12, atol=1e-9)


def test_potrf_complex(rng):
    a = spd(rng, 12, np.complex128)
    A = st.HermitianMatrix.from_numpy(a, 4, st.Uplo.Lower)
    L = st.potrf(A)
    l = L.to_numpy()
    np.testing.assert_allclose(l @ l.conj().T, a, rtol=1e-12, atol=1e-10)


@pytest.mark.parametrize("uplo,op", [
    ("lower", "n"), ("lower", "t"), ("upper", "n"), ("upper", "t")])
@pytest.mark.parametrize("target", ["single", "mesh"])
def test_trsm_left(rng, uplo, op, target):
    n, nrhs, nb = 20, 12, 4
    lower = uplo == "lower"
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    tri = np.tril(a) if lower else np.triu(a)
    b = rng.standard_normal((n, nrhs))
    if target == "mesh":
        g = st.Grid(2, 2, devices=jax.devices()[:4])
    else:
        g = None
    A = st.TriangularMatrix.from_numpy(
        a, nb, st.Uplo.Lower if lower else st.Uplo.Upper, grid=g)
    if op == "t":
        A = A.transpose()
    B = st.Matrix.from_numpy(b, nb, nb, g)
    X = st.trsm("l", 2.0, A, B)
    eff = tri.T if op == "t" else tri
    np.testing.assert_allclose(eff @ X.to_numpy(), 2.0 * b,
                               rtol=1e-10, atol=1e-10)


@pytest.mark.parametrize("uplo,op,dtype", [
    ("lower", "n", np.float64), ("lower", "t", np.float64),
    ("upper", "n", np.float64), ("upper", "t", np.float64),
    ("lower", "c", np.complex128)])
@pytest.mark.parametrize("p,q", [(2, 2), (2, 4)])
@pytest.mark.parametrize("n,nb", [(24, 4), (18, 5)])
@pytest.mark.parametrize("wide", [False, True], ids=["nrhs1", "nrhs_nb"])
def test_trsm_left_stationary_a(rng, uplo, op, dtype, p, q, n, nb, wide):
    """B of one tile column (nrhs 1 or nb) runs the stationary-A kernel
    under Auto: op(A) X = alpha B against numpy, and X equal to the
    stationary-B kernel's, forced with MethodTrsm.trsmB."""
    nrhs = nb if wide else 1
    lower = uplo == "lower"
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    b = rng.standard_normal((n, nrhs))
    if np.issubdtype(dtype, np.complexfloating):
        a = a + 1j * rng.standard_normal((n, n))
        b = b + 1j * rng.standard_normal((n, nrhs))
    tri = np.tril(a) if lower else np.triu(a)
    g = st.Grid(p, q, devices=jax.devices()[: p * q])
    A = st.TriangularMatrix.from_numpy(
        a, nb, st.Uplo.Lower if lower else st.Uplo.Upper, grid=g)
    A = {"n": A, "t": A.transpose(), "c": A.conj_transpose()}[op]
    eff = {"n": tri, "t": tri.T, "c": tri.conj().T}[op]
    B = st.Matrix.from_numpy(b, nb, nb, g)
    x = st.trsm("l", 2.0, A, B).to_numpy()
    np.testing.assert_allclose(eff @ x, 2.0 * b, rtol=1e-10, atol=1e-10)
    xb = st.trsm("l", 2.0, A, B,
                 {st.Option.MethodTrsm: st.MethodTrsm.trsmB}).to_numpy()
    np.testing.assert_allclose(x, xb, rtol=1e-12, atol=1e-12 * abs(xb).max())


def _all_reduce_sizes(text):
    """Element count of every array each all-reduce of an HLO text moves
    (a combined all-reduce moves a tuple)."""
    sizes = []
    for line in text.splitlines():
        m = re.search(r"= (.*?) all-reduce(?:-start)?\(", line)
        if m:
            sizes.append([int(np.prod([int(d) for d in dims.split(",")
                                       if d] or [1]))
                          for dims in re.findall(r"\w+\[([\d,]*)\]",
                                                 m[1])])
    return sizes


@pytest.mark.parametrize("nrhs,method,stationary_a", [
    (1, st.MethodTrsm.Auto, True),
    (48, st.MethodTrsm.Auto, False),
    (48, st.MethodTrsm.trsmA, False)])
def test_trsm_stationary_a_moves_no_tile_of_a(rng, nrhs, method,
                                              stationary_a):
    """dist_trsm_left as the driver lowers it on Grid(2, 2), n=64,
    nb=16: with one tile column of B no all-reduce of the compiled
    program carries as many as nb*nb entries (no tile of A leaves its
    chip); a B of three tile columns keeps the stationary-B kernel,
    whose panel all-reduce does, under either method."""
    n, nb = 64, 16
    g = st.Grid(2, 2, devices=jax.devices()[:4])
    A = st.TriangularMatrix.from_numpy(
        rng.standard_normal((n, n)) + n * np.eye(n), nb, st.Uplo.Lower,
        grid=g)
    B = st.Matrix.from_numpy(rng.standard_normal((n, nrhs)), nb, nb, g)
    opts = {st.Option.MethodTrsm: method}
    text = jax.jit(lambda A, B: st.trsm("l", 1.0, A, B, opts)).lower(
        A, B).compile().as_text()
    sizes = _all_reduce_sizes(text)
    assert sizes
    largest = max(max(s) for s in sizes)
    if stationary_a:
        assert largest < nb * nb, sizes
    else:
        assert largest >= nb * nb, sizes


def test_posv_mesh_one_rhs(rng):
    """posv on Grid(2, 2) with one right-hand side: both sweeps take the
    stationary-A kernel, end to end."""
    n, nb = 24, 4
    g = st.Grid(2, 2, devices=jax.devices()[:4])
    a = spd(rng, n)
    b = rng.standard_normal((n, 1))
    A = st.HermitianMatrix.from_numpy(a, nb, st.Uplo.Lower, g)
    L, X = st.posv(A, st.Matrix.from_numpy(b, nb, nb, g))
    x = X.to_numpy()
    assert x.shape == (n, 1)
    resid = np.linalg.norm(a @ x - b) / (
        np.linalg.norm(a) * np.linalg.norm(x) * n)
    assert resid < 1e-15


@pytest.mark.parametrize("target", ["single", "mesh"])
def test_trsm_right(rng, target):
    n, m, nb = 16, 12, 4
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    tri = np.tril(a)
    b = rng.standard_normal((m, n))
    g = st.Grid(2, 2, devices=jax.devices()[:4]) if target == "mesh" else None
    A = st.TriangularMatrix.from_numpy(a, nb, st.Uplo.Lower, grid=g)
    B = st.Matrix.from_numpy(b, nb, nb, g)
    X = st.trsm("r", 1.0, A, B)
    np.testing.assert_allclose(X.to_numpy() @ tri, b, rtol=1e-10, atol=1e-10)


def test_trsm_unit_diag(rng):
    n = 12
    a = rng.standard_normal((n, n))
    b = rng.standard_normal((n, 4))
    A = st.TriangularMatrix.from_numpy(a, 4, st.Uplo.Lower, st.Diag.Unit)
    X = st.trsm("l", 1.0, A, st.Matrix.from_numpy(b, 4))
    tri = np.tril(a, -1) + np.eye(n)
    np.testing.assert_allclose(tri @ X.to_numpy(), b, rtol=1e-11, atol=1e-11)


def test_trmm(rng):
    n, m = 12, 8
    a = rng.standard_normal((n, n))
    b = rng.standard_normal((n, m))
    A = st.TriangularMatrix.from_numpy(a, 4, st.Uplo.Upper)
    B = st.Matrix.from_numpy(b, 4)
    out = st.trmm("l", 1.0, A, B)
    np.testing.assert_allclose(out.to_numpy(), np.triu(a) @ b, atol=1e-12)


def test_herk_syrk(rng):
    mkn = 12
    a = rng.standard_normal((mkn, 8))
    c = spd(rng, mkn)
    A = st.Matrix.from_numpy(a, 4)
    C = st.SymmetricMatrix.from_numpy(c, 4, st.Uplo.Lower)
    out = st.syrk(1.0, A, 0.5, C)
    np.testing.assert_allclose(out.to_numpy(), a @ a.T + 0.5 * c,
                               rtol=1e-12, atol=1e-10)
    Ch = st.HermitianMatrix.from_numpy(c, 4, st.Uplo.Lower)
    outh = st.herk(1.0, A, 0.5, Ch)
    np.testing.assert_allclose(outh.to_numpy(), a @ a.T + 0.5 * c,
                               rtol=1e-12, atol=1e-10)


def test_her2k_symm(rng):
    n, k = 10, 6
    a = rng.standard_normal((n, k))
    b = rng.standard_normal((n, k))
    c = spd(rng, n)
    A, B = st.Matrix.from_numpy(a, 4), st.Matrix.from_numpy(b, 4)
    C = st.HermitianMatrix.from_numpy(c, 4, st.Uplo.Lower)
    out = st.her2k(1.0, A, B, 1.0, C)
    np.testing.assert_allclose(out.to_numpy(), a @ b.T + b @ a.T + c,
                               rtol=1e-12, atol=1e-10)
    s = st.SymmetricMatrix.from_numpy(c, 4, st.Uplo.Lower)
    d = rng.standard_normal((n, 7))
    D = st.Matrix.from_numpy(d, 4)
    out2 = st.symm("l", 1.0, s, D)
    np.testing.assert_allclose(out2.to_numpy(), s.to_numpy() @ d,
                               rtol=1e-12, atol=1e-10)


@pytest.mark.parametrize("target,pq", [("single", None), ("mesh", (2, 2)),
                                       ("mesh", (2, 4))])
@pytest.mark.slow
def test_posv(rng, target, pq):
    n, nrhs, nb = 24, 8, 4
    g = st.Grid(*pq, devices=jax.devices()[: pq[0] * pq[1]]) if pq else None
    a = spd(rng, n)
    b = rng.standard_normal((n, nrhs))
    A = st.HermitianMatrix.from_numpy(a, nb, st.Uplo.Lower, g)
    B = st.Matrix.from_numpy(b, nb, nb, g)
    L, X = st.posv(A, B)
    x = X.to_numpy()
    resid = np.linalg.norm(a @ x - b) / (
        np.linalg.norm(a) * np.linalg.norm(x) * n)
    assert resid < 1e-15


def test_potri(rng):
    n = 12
    a = spd(rng, n)
    A = st.HermitianMatrix.from_numpy(a, 4, st.Uplo.Lower)
    L = st.potrf(A)
    Ainv = st.potri(L)
    np.testing.assert_allclose(Ainv.to_numpy() @ a, np.eye(n),
                               rtol=1e-10, atol=1e-9)


def test_posv_under_jit(rng):
    n = 16
    a = spd(rng, n)
    b = rng.standard_normal((n, 4))
    A = st.HermitianMatrix.from_numpy(a, 4, st.Uplo.Lower)
    B = st.Matrix.from_numpy(b, 4)

    @jax.jit
    def solve(A, B):
        _, X = st.posv(A, B)
        return X

    x = solve(A, B).to_numpy()
    assert np.linalg.norm(a @ x - b) / np.linalg.norm(b) < 1e-12


def test_trsm_right_conjtrans_mesh(rng):
    """Right-side solve against A^H on the mesh (regression: op composition
    rejected ConjTrans∘Trans)."""
    n, m, nb = 12, 8, 4
    g = st.Grid(2, 2, devices=jax.devices()[:4])
    a = rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n))
    a = a + n * np.eye(n)
    b = rng.standard_normal((m, n)) + 1j * rng.standard_normal((m, n))
    A = st.TriangularMatrix.from_numpy(a, nb, st.Uplo.Lower, grid=g)
    B = st.Matrix.from_numpy(b, nb, nb, g)
    X = st.trsm("r", 1.0, A.conj_transpose(), B)
    np.testing.assert_allclose(X.to_numpy() @ np.tril(a).conj().T, b,
                               rtol=1e-10, atol=1e-10)


def test_herk_rejects_general_C(rng):
    A = st.Matrix.from_numpy(rng.standard_normal((8, 4)), 4)
    C = st.Matrix.zeros(8, 8, 4)
    try:
        st.herk(1.0, A, 0.0, C)
        assert False, "expected SlateValueError"
    except st.SlateValueError:
        pass


@pytest.mark.slow
def test_potri_getri_mesh(rng):
    # inverses ride the distributed trsm/herk kernels on a mesh
    # (ref: src/trtri.cc, src/getri.cc distribute)
    import jax
    n, nb = 24, 4
    g = st.Grid(2, 2, devices=jax.devices()[:4])
    a0 = rng.standard_normal((n, n))
    s = a0 @ a0.T + n * np.eye(n)
    S = st.HermitianMatrix.from_numpy(s, nb, st.Uplo.Lower, g)
    L = st.potrf(S)
    Sinv = st.potri(L)
    np.testing.assert_allclose(s @ Sinv.general().to_numpy(), np.eye(n),
                               atol=1e-9)
    A = st.Matrix.from_numpy(a0 + n * np.eye(n), nb, nb, g)
    X = st.getriOOP(A)
    np.testing.assert_allclose((a0 + n * np.eye(n)) @ X.to_numpy(),
                               np.eye(n), atol=1e-9)
