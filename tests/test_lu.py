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


def _gathering_panel_lu_recursive(panel, base: int = 128):
    """The recursive panel as it was when each split gathered every row
    (``panel[p1, h:]``): the oracle the row-bundle version must match
    bit for bit."""
    from slate_tpu.internal.getrf import panel_lu_threshold
    from slate_tpu.internal.trsm import tri_inv_lower
    w = panel.shape[1]
    if w <= base:
        return panel_lu_threshold(panel, 1.0)
    h = w // 2
    with jax.default_matmul_precision("highest"):
        lu1, p1 = _gathering_panel_lu_recursive(panel[:, :h], base)
        right = panel[p1, h:]
        l11 = jnp.tril(lu1[:h], -1) + jnp.eye(h, dtype=panel.dtype)
        u12 = tri_inv_lower(l11, unit_diag=True) @ right[:h]
        lu2, p2 = _gathering_panel_lu_recursive(right[h:] - lu1[h:] @ u12,
                                                base)
    top = jnp.concatenate([lu1[:h], u12], axis=1)
    bot = jnp.concatenate([lu1[h:][p2], lu2], axis=1)
    perm = jnp.concatenate([p1[:h], p1[h:][p2]])
    return jnp.concatenate([top, bot], axis=0), perm


@pytest.mark.parametrize("kind", ["random", "no_swaps", "swap_every_step",
                                  "pivot_in_top_block"])
@pytest.mark.parametrize("W,w,base", [(300, 128, 16), (257, 40, 16),
                                      (1000, 64, 16), (2048, 256, 64)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_recursive_panel_bit_identical_to_gathering(rng, dtype, W, w, base,
                                                    kind):
    """Moving only the displaced rows at each split gives the same factor
    and permutation, bit for bit, as gathering every row, whether no row
    moves, one moves at every step, or a pivot lands inside the top
    block (repeated indices in the bundle)."""
    import functools

    from slate_tpu.internal.getrf import panel_lu_recursive
    a = rng.standard_normal((W, w))
    if kind == "no_swaps":        # column-diagonally dominant
        a[:w] += 2 * W * np.eye(w)
    elif kind == "swap_every_step":   # rows in ascending magnitude
        a *= (1.0 + np.arange(W)[:, None]) ** 2
    elif kind == "pivot_in_top_block":
        a[:w] *= 10.0
    a = jnp.asarray(a.astype(dtype))
    lu, perm = jax.jit(functools.partial(panel_lu_recursive, base=base))(a)
    ref_lu, ref_perm = jax.jit(functools.partial(
        _gathering_panel_lu_recursive, base=base))(a)
    assert np.array_equal(np.asarray(lu), np.asarray(ref_lu))
    assert np.array_equal(np.asarray(perm), np.asarray(ref_perm))
    # the row swaps that built perm (LAPACK's ipiv): the case is the one
    # its name says
    cur, piv = np.arange(W), np.zeros(w, int)
    for j, row in enumerate(np.asarray(perm)[:w]):
        piv[j] = np.flatnonzero(cur == row)[0]
        cur[[j, piv[j]]] = cur[[piv[j], j]]
    steps = np.arange(w)
    if kind == "no_swaps":
        assert (piv == steps).all()
    elif kind == "swap_every_step":
        assert (piv > steps).all()
    elif kind == "pivot_in_top_block":
        assert ((piv > steps) & (piv < w)).any()


def test_recursive_panel_moves_only_the_pivot_rows():
    """At 16384 x 512 every gather and scatter of the recursive panel
    moves at most 512 rows (2 x the 256-wide split): the whole-panel
    gathers were 16384 rows, each a per-row loop on the chip."""
    import re

    from slate_tpu.internal.getrf import panel_lu_recursive
    txt = jax.jit(panel_lu_recursive).lower(
        jax.ShapeDtypeStruct((16384, 512), jnp.float32)).as_text()
    # the indices operand's type follows the attributes (gather) or the
    # update region (scatter)
    found = re.findall(
        r'"stablehlo\.(gather|scatter)"\(.*?(?:\}>|\}\)) : '
        r'\(tensor<[^>]*>, tensor<([0-9x]*)[a-z]', txt, flags=re.S)
    kinds = {k for k, _ in found}
    assert kinds == {"gather", "scatter"}
    rows = [int(d.split("x")[0]) if d else 1 for _, d in found]
    assert max(rows) <= 512, sorted(rows)[-5:]


def test_row_exchange_moves_only_the_displaced_rows():
    """At 2048 x 2048, nb=256, every gather and scatter of matrix rows
    under the swap moves at most 256 rows (nb: the new top rows in, the
    old ones out), and both act on the whole [m, n] matrix, not on a
    slice of the blocks left or right of the panel that is then written
    back.  (``perm_g``, the global permutation vector, is updated
    whole.)"""
    import functools
    import re

    from slate_tpu.drivers.lu import _getrf_dense_blocked
    m = n = 2048
    txt = jax.jit(functools.partial(
        _getrf_dense_blocked, nb=256, method="partial")).lower(
        jax.ShapeDtypeStruct((m, n), jnp.float32)).as_text(debug_info=True)
    where = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', txt, flags=re.M))
    found = re.findall(
        r'"stablehlo\.(gather|scatter)"\(.*?(?:\}>|\}\)) : '
        r'\(tensor<((?:\d+x)*)[a-z][^>]*>, tensor<(\d+)x[^)]*\)'
        r'[^\n]*? loc\((#loc\d+)\)', txt, flags=re.S)
    # rank-2 operands: rows of the matrix, not entries of perm_g
    moves = [(kind, operand, int(rows)) for kind, operand, rows, loc in found
             if "slate.getrf/swap/" in where[loc]
             and operand.count("x") == 2]
    assert [k for k, _, _ in moves] == ["gather", "scatter"] * (n // 256)
    for kind, operand, rows in moves:
        assert operand == f"{m}x{n}x", (kind, operand)
        assert rows <= 256, (kind, rows)


def _two_block_getrf_dense_blocked(a, nb, method, tau=1.0, mpt=4, depth=2):
    """The one-chip blocked LU as it was when each step sliced the blocks
    left and right of the panel, moved the displaced rows inside each
    copy and wrote both blocks back: the oracle the whole-row move must
    match bit for bit."""
    from jax import lax

    from slate_tpu.internal.getrf import (panel_lu, panel_lu_threshold,
                                          panel_lu_tournament)
    from slate_tpu.internal.trsm import tri_inv_lower

    def apply_row_perm(mat, perm, bound):
        W = perm.shape[0]
        if W == 0 or mat.shape[1] == 0:
            return mat
        moved = (perm != jnp.arange(W)).astype(jnp.int32)
        _, idx = lax.top_k(moved, min(W, bound))
        return mat.at[idx].set(mat[perm[idx]], unique_indices=True)

    m, n = a.shape
    perm_g = jnp.arange(m)
    for k0 in range(0, min(m, n), nb):
        k1 = min(k0 + nb, m, n)
        w = k1 - k0
        pan = a[k0:, k0:k1]
        if method == "tntpiv":
            bh = pan.shape[0]
            br = max(nb, (-(-bh // (mpt * nb))) * nb)
            lu, perm = panel_lu_tournament(pan, block_rows=br, arity=depth)
        elif tau < 1.0:
            lu, perm = panel_lu_threshold(pan, tau)
        else:
            lu, perm = panel_lu(pan)
        a = a.at[k0:, k0:k1].set(lu)
        a = a.at[k0:, :k0].set(apply_row_perm(a[k0:, :k0], perm, 2 * w))
        a = a.at[k0:, k1:].set(apply_row_perm(a[k0:, k1:], perm, 2 * w))
        perm_g = perm_g.at[k0:].set(perm_g[k0:][perm])
        if k1 < n:
            u12 = tri_inv_lower(lu[:w, :w], unit_diag=True) @ a[k0:k1, k1:]
            a = a.at[k0:k1, k1:].set(u12)
            if k1 < m:
                a = a.at[k1:, k1:].add(-(lu[w:, :w] @ u12))
    return a, perm_g


@pytest.mark.parametrize("rows", ["random", "ascending"])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("m,n,nb", [(96, 96, 32), (100, 100, 32),
                                    (150, 70, 32), (70, 150, 32)])
@pytest.mark.parametrize("method,tau", [("partial", 1.0),
                                        ("partial", 0.5),
                                        ("tntpiv", 1.0)])
def test_row_exchange_bit_identical_to_two_block(rng, method, tau, m, n, nb,
                                                 dtype, rows):
    """Moving the displaced rows of the whole matrix in place, then
    writing the panel, gives the same factor and permutation, bit for
    bit, as moving them inside slices of the left and right blocks and
    writing both back: square, ragged last panel, tall and wide, with
    rows in ascending magnitude so that every step swaps."""
    import functools

    from slate_tpu.drivers.lu import _getrf_dense_blocked
    a = rng.standard_normal((m, n))
    if rows == "ascending":
        a *= (1.0 + np.arange(m)[:, None]) ** 2
    a = jnp.asarray(a.astype(dtype))
    lu, perm, _ = jax.jit(functools.partial(
        _getrf_dense_blocked, nb=nb, method=method, tau=tau))(a)
    ref_lu, ref_perm = jax.jit(functools.partial(
        _two_block_getrf_dense_blocked, nb=nb, method=method, tau=tau))(a)
    assert np.array_equal(np.asarray(lu), np.asarray(ref_lu))
    assert np.array_equal(np.asarray(perm), np.asarray(ref_perm))
    if rows == "ascending":
        # the row swaps that built perm (LAPACK's ipiv): every panel
        # step swaps
        k = min(m, n)
        cur, piv = np.arange(m), np.zeros(k, int)
        for j, row in enumerate(np.asarray(perm)[:k]):
            piv[j] = np.flatnonzero(cur == row)[0]
            cur[[j, piv[j]]] = cur[[piv[j], j]]
        swapped = piv > np.arange(k)
        assert all(swapped[k0:k0 + nb].any() for k0 in range(0, k, nb))
