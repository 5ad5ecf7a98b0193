"""Unit tests for the tile-storage / matrix core.

Analog of the reference's per-class unit tests (ref: unit_test/test_Matrix.cc,
test_Tile.cc, test_TrapezoidMatrix.cc, test_BandMatrix.cc).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu.core import layout


GRIDS = [(1, 1), (2, 2), (2, 4), (4, 2)]


def make_grid(p, q):
    return st.Grid(p, q, devices=jax.devices()[: p * q])


@pytest.mark.parametrize("m,n,mb,nb", [(8, 8, 4, 4), (10, 7, 4, 3),
                                       (5, 13, 4, 4), (64, 64, 16, 16),
                                       (1, 1, 4, 4)])
@pytest.mark.parametrize("p,q", GRIDS)
def test_storage_roundtrip(rng, m, n, mb, nb, p, q):
    a = rng.standard_normal((m, n))
    g = make_grid(p, q)
    s = st.TileStorage.from_dense(a, mb, nb, g)
    np.testing.assert_allclose(np.asarray(s.to_dense()), a)


@pytest.mark.parametrize("p,q", [(2, 4), (1, 1)])
def test_cyclic_tile_placement(rng, p, q):
    """tile(i, j) equals dense block; owner coordinate is (i%p, j%q)
    (ref: MatrixStorage.hh:555-568)."""
    m, n, mb, nb = 20, 12, 4, 4
    a = rng.standard_normal((m, n))
    g = make_grid(p, q)
    s = st.TileStorage.from_dense(a, mb, nb, g)
    for i in range(s.Mt):
        for j in range(s.Nt):
            blk = a[i * mb:(i + 1) * mb, j * nb:(j + 1) * nb]
            got = np.asarray(s.tile(i, j))[: blk.shape[0], : blk.shape[1]]
            np.testing.assert_allclose(got, blk)
            assert g.tile_coords(i, j) == (i % p, j % q)
    # storage is sharded over all p*q devices
    if g.mesh is not None:
        assert len({sh.device for sh in s.data.addressable_shards}) == p * q


def test_padding_is_zero(rng):
    a = rng.standard_normal((10, 7))
    s = st.TileStorage.from_dense(a, 4, 4, make_grid(2, 2))
    canon = np.asarray(s.canonical())
    # last tile row has 2 valid rows, last tile col 3 valid cols
    assert np.all(canon[-1, :, 2:, :] == 0)
    assert np.all(canon[:, -1, :, 3:] == 0)


def test_views_are_zero_copy(rng):
    a = rng.standard_normal((16, 16))
    A = st.Matrix.from_numpy(a, 4)
    v = A.sub(1, 2, 0, 3)
    assert v.storage is A.storage
    assert v.m == 8 and v.n == 16
    np.testing.assert_allclose(v.to_numpy(), a[4:12, :])
    t = A.T
    assert t.storage is A.storage
    np.testing.assert_allclose(t.to_numpy(), a.T)
    tt = t.T
    assert tt.op is st.Op.NoTrans
    sub_t = A.T.sub(0, 1, 1, 2)
    np.testing.assert_allclose(sub_t.to_numpy(), a.T[0:8, 4:12])


def test_uneven_view_dims(rng):
    a = rng.standard_normal((10, 7))
    A = st.Matrix.from_numpy(a, 4, 4)
    v = A.sub(1, 2, 1, 1)          # rows 4..9 (ragged), cols 4..6
    assert v.m == 6 and v.n == 3
    np.testing.assert_allclose(v.to_numpy(), a[4:10, 4:7])
    assert v.tile_mb(1) == 2 and v.tile_nb(0) == 3


def test_with_dense_writeback(rng):
    a = rng.standard_normal((12, 12))
    A = st.Matrix.from_numpy(a, 4)
    v = A.sub(1, 2, 1, 2)
    new = v.with_dense(jnp.zeros((8, 8)))
    # view region zeroed, parent region preserved, original untouched
    full = np.asarray(new.storage.to_dense())
    expect = a.copy()
    expect[4:12, 4:12] = 0
    np.testing.assert_allclose(full, expect)
    np.testing.assert_allclose(A.to_numpy(), a)


@pytest.mark.parametrize("uplo", [st.Uplo.Lower, st.Uplo.Upper])
def test_structured_expand(rng, uplo):
    a = rng.standard_normal((9, 9))
    tri = st.TriangularMatrix.from_numpy(a, 4, uplo)
    ref = np.tril(a) if uplo is st.Uplo.Lower else np.triu(a)
    np.testing.assert_allclose(tri.to_numpy(), ref)
    uni = st.TriangularMatrix.from_numpy(a, 4, uplo, st.Diag.Unit)
    ref_u = ref.copy()
    np.fill_diagonal(ref_u, 1.0)
    np.testing.assert_allclose(uni.to_numpy(), ref_u)

    sym = st.SymmetricMatrix.from_numpy(a, 4, uplo)
    t = np.tril(a) if uplo is st.Uplo.Lower else np.triu(a)
    ref_s = t + t.T - np.diag(np.diag(a))
    np.testing.assert_allclose(sym.to_numpy(), ref_s)


def test_hermitian_expand(rng):
    a = rng.standard_normal((8, 8)) + 1j * rng.standard_normal((8, 8))
    he = st.HermitianMatrix.from_numpy(a, 4, st.Uplo.Lower)
    t = np.tril(a)
    ref = t + t.conj().T
    np.fill_diagonal(ref, np.real(np.diag(a)))
    np.testing.assert_allclose(he.to_numpy(), ref)
    # conj_transpose of hermitian equals itself
    np.testing.assert_allclose(he.H.to_numpy(), ref)


def test_band_expand(rng):
    a = rng.standard_normal((12, 12))
    bd = st.BandMatrix.from_numpy(a, 2, 3, 4)
    i, j = np.indices(a.shape)
    ref = np.where((j - i <= 3) & (i - j <= 2), a, 0.0)
    np.testing.assert_allclose(bd.to_numpy(), ref)


def test_matrix_as_pytree(rng):
    a = rng.standard_normal((8, 8))
    A = st.Matrix.from_numpy(a, 4)

    @jax.jit
    def f(M):
        return M.with_dense(M.to_dense() * 2.0)

    out = f(A)
    np.testing.assert_allclose(out.to_numpy(), 2 * a)


def test_grid_rank_order():
    g = st.Grid(2, 3, devices=jax.devices()[:6], order=st.GridOrder.Col)
    assert g.tile_rank(0, 0) == 0 and g.tile_rank(1, 0) == 1
    assert g.tile_rank(0, 1) == 2
    g2 = st.Grid(2, 3, devices=jax.devices()[:6], order=st.GridOrder.Row)
    assert g2.tile_rank(0, 1) == 1 and g2.tile_rank(1, 0) == 3


def test_compile_cache_is_placed_from_outside_or_fixed(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache is
    the fixed .jax_cache/ at the checkout root, never a temporary name."""
    from slate_tpu.util import runtime
    set_to = []
    monkeypatch.setattr(runtime.jax.config, "update",
                        lambda k, v: set_to.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert runtime.use_compile_cache() == "/elsewhere/cache"
    assert set_to == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = runtime.use_compile_cache()
    assert path == str(runtime.CHECKOUT / ".jax_cache")
    assert set_to == [("jax_compilation_cache_dir", path)]
    assert (runtime.CHECKOUT / "chip_smoke.py").exists()


def test_pallas_interprets_off_tpu_only():
    from slate_tpu.util import runtime
    assert runtime.pallas_interpret() == (jax.default_backend() != "tpu")
