"""Buffer-pointer driver entry points backing the embedded C API.

Analog of the reference's generated driver C API (ref:
src/c_api/wrappers.cc:1-1307, include/slate/c_api/wrappers.h): C callers
hand raw buffers to driver-level routines.  The reference's C tier wraps
its C++ runtime directly; here the runtime is the JAX program layer, so
the C tier (native/slate_tpu_capi.cc) EMBEDS the interpreter and calls
these functions — pointers arrive as integers, are wrapped zero-copy
with numpy, and results are written back into the caller's output
buffer.  Double precision, row-major with a row stride ("ld" = elements
between consecutive rows), full matrices.

Every function returns 0 on success, 1 on failure (exceptions are caught
and reported on stderr — a C caller cannot unwind Python exceptions).
"""

from __future__ import annotations

import ctypes
import os
import traceback

import numpy as np

import jax

if os.environ.get("SLATE_CAPI_PLATFORM"):
    # embedding hosts cannot call jax.config themselves, so honor an
    # explicit knob here
    jax.config.update("jax_platforms", os.environ["SLATE_CAPI_PLATFORM"])
# the C API traffics in doubles: without x64 JAX silently computes the
# whole solve in f32 (this module is only imported by embedding hosts,
# so the global flag is theirs to own)
jax.config.update("jax_enable_x64", True)


def _in(ptr, rows, cols, ld):
    """Wrap a caller buffer [rows, ld] and copy out the [rows, cols]
    payload (drivers may run on accelerators; zero-copy aliasing of user
    memory across the device boundary is not meaningful)."""
    base = np.ctypeslib.as_array(
        ctypes.cast(int(ptr), ctypes.POINTER(ctypes.c_double)),
        shape=(int(rows), int(ld)))
    return np.array(base[:, :int(cols)], dtype=np.float64)


def _out(ptr, rows, cols, ld, value):
    base = np.ctypeslib.as_array(
        ctypes.cast(int(ptr), ctypes.POINTER(ctypes.c_double)),
        shape=(int(rows), int(ld)))
    base[:, :int(cols)] = np.asarray(value, dtype=np.float64)


def _vec_out(ptr, n, value):
    base = np.ctypeslib.as_array(
        ctypes.cast(int(ptr), ctypes.POINTER(ctypes.c_double)),
        shape=(int(n),))
    base[:] = np.asarray(value, dtype=np.float64)


def _guard(fn):
    try:
        fn()
        return 0
    except Exception:  # noqa: BLE001 — C boundary: report, return rc
        traceback.print_exc()
        return 1


def dgesv(n, nrhs, a_ptr, lda, b_ptr, ldb, x_ptr, ldx, nb):
    """Solve A X = B by LU (ref: c_api slate_dgesv wrapper)."""
    def run():
        import slate_tpu as st
        A = st.Matrix.from_numpy(_in(a_ptr, n, n, lda), nb, nb)
        B = st.Matrix.from_numpy(_in(b_ptr, n, nrhs, ldb), nb, nb)
        _, X = st.gesv(A, B)
        _out(x_ptr, n, nrhs, ldx, X.to_numpy())
    return _guard(run)


def dposv(n, nrhs, a_ptr, lda, b_ptr, ldb, x_ptr, ldx, nb):
    """Hermitian positive-definite solve (ref: c_api slate_dposv)."""
    def run():
        import slate_tpu as st
        H = st.HermitianMatrix.from_numpy(_in(a_ptr, n, n, lda), nb,
                                          st.Uplo.Lower)
        B = st.Matrix.from_numpy(_in(b_ptr, n, nrhs, ldb), nb, nb)
        _, X = st.posv(H, B)
        _out(x_ptr, n, nrhs, ldx, X.to_numpy())
    return _guard(run)


def dgels(m, n, nrhs, a_ptr, lda, b_ptr, ldb, x_ptr, ldx, nb):
    """Least squares min ||A X - B|| (ref: c_api slate_dgels)."""
    def run():
        import slate_tpu as st
        A = st.Matrix.from_numpy(_in(a_ptr, m, n, lda), nb, nb)
        B = st.Matrix.from_numpy(_in(b_ptr, m, nrhs, ldb), nb, nb)
        X = st.gels(A, B)
        _out(x_ptr, n, nrhs, ldx, X.to_numpy())
    return _guard(run)


def dsyev(n, a_ptr, lda, w_ptr, nb):
    """Symmetric eigenvalues (ref: c_api slate_dsyev, values mode)."""
    def run():
        import slate_tpu as st
        H = st.HermitianMatrix.from_numpy(_in(a_ptr, n, n, lda), nb,
                                          st.Uplo.Lower)
        w = st.heev_vals(H)
        _vec_out(w_ptr, n, np.sort(np.asarray(w)))
    return _guard(run)


def dgesvd(m, n, a_ptr, lda, s_ptr, nb):
    """Singular values (ref: c_api slate_dgesvd, values mode)."""
    def run():
        import slate_tpu as st
        A = st.Matrix.from_numpy(_in(a_ptr, m, n, lda), nb, nb)
        s = st.svd_vals(A)
        _vec_out(s_ptr, min(m, n), np.asarray(s))
    return _guard(run)
