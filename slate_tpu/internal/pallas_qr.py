"""Pallas TPU kernel: Householder QR panel + compact-WY T, VMEM-resident.

geqrf's panel step previously stitched XLA-level tile ops (qr.py
householder_panel: one dynamic-slice rank-1 update per column, each a
round trip through HBM for the whole [mm, w] panel).  This kernel keeps
the panel AND the growing T triangle in VMEM for all w columns: column
extraction is mask+reduce (no lane slicing), the trailing update and the
T recursion are MXU dots, and the output is byte-compatible with
(householder_panel, build_t) — R in/above the diagonal, Householder
vectors below (unit diagonal implied), T the larft Forward/Columnwise
triangle with tau on its diagonal.  Q = I - V T V^T.

The larfg scalar math mirrors qr.py _larfg exactly (beta =
-copysign(mu, alpha); dead columns with mu == 0 get tau = 0 and keep
their column), so parity tests compare against the XLA panel directly.

Ragged batched variant (qr_panel_batched): one panel per grid step over
a leading batch dimension, per-problem live row counts via scalar
prefetch.  Unlike Cholesky/LU, padding columns carry real reflectors
(the identity augmentation must be annihilated), so raggedness is
problem-granular: only zero-row filler slots skip the factorization.

Real f32, mm >= w; the batched variant additionally accepts bf16
storage — the panel is upcast once into VMEM, the whole column loop
(larfg scalars, trailing updates, T recursion) runs in f32, and only
the final packed/T writes demote back (see pallas_chol.py for the
accumulation contract).  Other panels use the XLA path (qr.geqrf_panel).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_tri import PANEL_PARAMS

_HI = lax.Precision.HIGHEST


def _qr_panel_steps(a):
    """Pure column loop shared by the single-panel kernel and the
    batched grid: packed Householder panel + T of ``a`` [mm, w], carried
    through the fori_loop as VALUES so it can run under pl.when."""
    mm, w = a.shape
    dt = a.dtype
    rows = lax.broadcasted_iota(jnp.int32, (mm, w), 0)
    cols = lax.broadcasted_iota(jnp.int32, (mm, w), 1)
    rc = lax.broadcasted_iota(jnp.int32, (mm, 1), 0)
    cn = lax.broadcasted_iota(jnp.int32, (1, w), 1)
    tc = lax.broadcasted_iota(jnp.int32, (w, w), 1)
    trc = lax.broadcasted_iota(jnp.int32, (w, 1), 0)

    def col_step(j, AT):
        A, T = AT
        colj = jnp.sum(jnp.where(cols == j, A, 0), axis=1, keepdims=True)
        alpha = jnp.sum(jnp.where(rc == j, colj, 0))
        x = jnp.where(rc > j, colj, 0.0)
        mu = jnp.sqrt(alpha * alpha + jnp.sum(x * x))
        live = mu > 0
        beta = jnp.where(alpha >= 0, -mu, mu)
        sb = jnp.where(live, beta, 1.0)
        tau = jnp.where(live, (sb - alpha) / sb, 0.0)
        scale = 1.0 / jnp.where(live, alpha - sb, 1.0)
        v = jnp.where(rc == j, 1.0, x * scale)       # [mm, 1], v[:j] = 0
        v = jnp.where(rc < j, 0.0, v)
        # trailing update: A[:, j+1:] -= tau v (v^T A)
        wrow = lax.dot_general(v, A, (((0,), (0,)), ((), ())),
                               preferred_element_type=dt, precision=_HI)
        wrow = jnp.where(cn > j, wrow, 0.0)          # [1, w]
        A = A - tau * v * wrow
        # write column j: R above+diag(beta), v strictly below
        newc = jnp.where(rc == j, beta, jnp.where(rc < j, colj, x * scale))
        newc = jnp.where(live, newc, colj)           # mu==0: leave column
        A = jnp.where(cols == j, newc, A)
        # T column j: -tau T (V^T v), diag tau (larft recursion)
        V = jnp.where((rows > cols) & (cols < j), A, 0.0)
        V = V + jnp.where((rows == cols) & (cols < j), 1.0, 0.0)
        g = lax.dot_general(V, v, (((0,), (0,)), ((), ())),
                            preferred_element_type=dt, precision=_HI)
        tcol = -tau * jnp.dot(T, g, preferred_element_type=dt,
                              precision=_HI)         # [w, 1]
        tcol = jnp.where(trc == j, tau, jnp.where(trc < j, tcol, 0.0))
        T = jnp.where(tc == j, tcol, T)
        return A, T

    return lax.fori_loop(0, w, col_step, (a, jnp.zeros((w, w), dt)))


def _qr_panel_kernel(a_ref, p_ref, t_ref):
    packed, t = _qr_panel_steps(a_ref[:])
    p_ref[:] = packed
    t_ref[:] = t


def _qr_panel_batched_kernel(rows_ref, a_ref, p_ref, t_ref):
    b = pl.program_id(0)
    w = t_ref.shape[1]
    dt = a_ref.dtype
    # QR raggedness is problem-granular: identity-augmented padding
    # COLUMNS carry nontrivial reflectors (the augmented unit diagonal
    # must be annihilated), so only problems with zero live rows —
    # filler slots — skip the panel entirely (packed = input, T = 0).
    live = rows_ref[b] > 0

    @pl.when(live)
    def _panel():
        # column loop in f32 (bf16 panels upcast once into registers);
        # the packed/T writes demote back to the storage dtype
        packed, t = _qr_panel_steps(a_ref[0].astype(jnp.float32))
        p_ref[0] = packed.astype(dt)
        t_ref[0] = t.astype(dt)

    @pl.when(jnp.logical_not(live))
    def _dead():
        p_ref[0] = a_ref[0]
        t_ref[0] = jnp.zeros((w, w), dt)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qr_panel_pallas(a, interpret: bool = False):
    """Packed Householder panel + T of ``a`` [mm, w], mm >= w.

    Returns (packed, T) with householder_panel's packing and build_t's
    T — drop-in for householder_panel_blocked on f32 panels."""
    mm, w = a.shape
    packed, t = pl.pallas_call(
        _qr_panel_kernel,
        out_shape=[jax.ShapeDtypeStruct((mm, w), a.dtype),
                   jax.ShapeDtypeStruct((w, w), a.dtype)],
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)],
        compiler_params=PANEL_PARAMS,
        interpret=interpret,
    )(a)
    return packed, t


@functools.partial(jax.jit, static_argnames=("interpret",))
def qr_panel_batched(a, rows, interpret: bool = False):
    """Ragged batched Householder panel: packed panels + Ts of ``a``
    [B, mm, w], mm >= w, with per-problem live row counts ``rows`` [B]
    int32 delivered via scalar prefetch.

    Raggedness is problem-granular only (unlike the Cholesky/LU tile
    grids): identity-augmented padding columns own real reflectors, so a
    live problem factors its whole bucket panel; a problem with
    rows[b] == 0 (a filler slot) passes its input through with T = 0.
    Accepts real f32 or bf16 storage (the column loop runs in f32 either
    way).  Returns (packed [B, mm, w], T [B, w, w])."""
    bsz, mm, w = a.shape
    packed, t = pl.pallas_call(
        _qr_panel_batched_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz,),
            in_specs=[pl.BlockSpec((1, mm, w), lambda b, rows: (b, 0, 0))],
            out_specs=[pl.BlockSpec((1, mm, w), lambda b, rows: (b, 0, 0)),
                       pl.BlockSpec((1, w, w), lambda b, rows: (b, 0, 0))],
        ),
        out_shape=[jax.ShapeDtypeStruct((bsz, mm, w), a.dtype),
                   jax.ShapeDtypeStruct((bsz, w, w), a.dtype)],
        interpret=interpret,
    )(rows, a)
    return packed, t
