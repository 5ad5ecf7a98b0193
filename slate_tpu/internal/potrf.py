"""internal::potrf — diagonal-tile Cholesky factor + fused panel seam.

Analog of the reference's internal_potrf.cc:132 (lapack::potrf on the
diagonal tile, host or device).  The reference delegates the tile factor
to vendor LAPACK; on TPU the vendor seam (XLA's Cholesky) runs a
per-column While loop — 2.07 ms per 512 f32 tile (a pre-benchmark v5e
measurement).

Kernel choice is now a TUNED decision: both the single-tile factor
(potrf_tile) and the fused panel step (potrf_panel_fused: rank-k update
+ tile factor + TRSM in one pallas_call — internal/pallas_chol.py)
consult slate_tpu.tune.resolve_plan at trace time, keyed by
(op, n, dtype, chip).  Shipped plans default to XLA everywhere; run
``python -m slate_tpu.tune`` on a new chip (docs/TUNING.md).

The old ``SLATE_PALLAS=1`` env gate this module used to read directly
is DEPRECATED: the tune resolver still honors it for one release as a
force-on/force-off override of the cached plan.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..tune import resolve_plan
from ..util.runtime import pallas_interpret


def _tile_plan_ok(dtype, n: int) -> bool:
    if not (dtype == jnp.float32 and n % 128 == 0 and 128 <= n <= 1024):
        return False
    return resolve_plan("potrf_tile", n, "float32").kernel == "pallas"


def potrf_tile(a):
    """Factor one Hermitian positive-definite tile: returns lower L.

    Routed through the tuned plan for ("potrf_tile", n): the Pallas
    VMEM-resident kernel when the plan says so (f32, 128 <= n <= 1024,
    n % 128 == 0), XLA's Cholesky otherwise."""
    n = a.shape[-1]
    if a.ndim == 2 and _tile_plan_ok(a.dtype, n):
        from .pallas_chol import chol_tile_pallas
        plan = resolve_plan("potrf_tile", n, "float32")
        return chol_tile_pallas(a, bw=plan.bw,
                                interpret=pallas_interpret())
    return jnp.linalg.cholesky(a)


def potrf_panel_ok(dtype, m: int, w: int, nb: int) -> bool:
    """True when the fused Pallas panel step serves this panel: tuned
    plan says pallas, f32, full-width panel, MXU-aligned nb that fits
    VMEM (the [nb, nb] accumulator + U^-1 scratches cap nb at 512)."""
    if not (dtype == jnp.float32 and w == nb and m >= nb
            and nb % 128 == 0 and 128 <= nb <= 512):
        return False
    return resolve_plan("potrf_panel", m, "float32").kernel == "pallas"


# ---- out-of-core panel-step kernels (drivers/cholesky.py potrf_ooc) ----
# Each step of the OOC left-looking loop is a pure jitted function of the
# device windows the TileMap streams in; jit's shape-keyed cache gives one
# executable per (panel width, remaining height), reused across steps AND
# across a checkpoint resume — a load-bearing property: bit-identical
# resume relies on the resumed run dispatching the exact same kernels on
# the exact same bytes as the uninterrupted one.

@jax.jit
def ooc_chol_update(acc, left, lead):
    """One streamed left-looking accumulation: subtract the contribution
    of a previous block column.  ``acc`` [m-k0, w] is the running panel,
    ``left`` = A[k0:, j0:j1], ``lead`` = A[k0:k1, j0:j1]."""
    return acc - left @ jnp.conj(lead).T


@jax.jit
def ooc_chol_panel(upd):
    """Factor the fully-accumulated [m-k0, w] panel: returns [L00; L21]
    with the diagonal tile routed through the tuned potrf_tile and the
    rows below one MXU gemm against the inverted L00 (same seam as the
    in-core blocked loop in drivers/cholesky.py)."""
    from .trsm import tri_inv_lower
    w = upd.shape[1]
    lkk = potrf_tile(upd[:w])
    tail = upd[w:] @ jnp.conj(tri_inv_lower(lkk)).T
    return jnp.concatenate([lkk, tail], axis=0)


def potrf_panel_fused(col, left, lead):
    """Fused left-looking panel step (see pallas_chol.chol_panel_fused):
    returns (upd, fac) = (pre-factor panel for the ABFT rungs,
    [L00; L21]).  Caller gates with potrf_panel_ok; ragged row counts
    are zero-padded to a tile multiple here and sliced back."""
    from .pallas_chol import chol_panel_fused
    m, nb = col.shape
    plan = resolve_plan("potrf_panel", m, "float32")
    mp = -(-m // nb) * nb
    if mp != m:                       # zero rows factor to zero L21 rows
        col = jnp.pad(col, ((0, mp - m), (0, 0)))
        left = jnp.pad(left, ((0, mp - m), (0, 0)))
    upd, fac = chol_panel_fused(col, left, lead, bw=plan.bw,
                                interpret=pallas_interpret())
    return upd[:m], fac[:m]
