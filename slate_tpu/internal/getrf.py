"""internal::getrf — LU panel factorizations.

Analog of the reference's threaded+MPI LU panels:

- partial pivoting panel (ref: src/internal/internal_getrf.cc:20-119 +
  Tile_getrf.hh:99-444): `MaxPanelThreads` host threads cooperate over the
  local tiles of one panel column, with an MPI_Allreduce(MAXLOC) per column
  across the panel ranks and a bcast of the pivot row.  On TPU the panel is
  skinny (W x nb) and per-chip compute is enormous, so the panel is gathered
  and factored REPLICATED on every rank with XLA's native partially-pivoted
  LU — trading a few redundant kilo-FLOPs for the elimination of nb
  latency-bound MAXLOC rounds per panel (the reference's known bottleneck).
- no-pivot panel (ref: internal_getrf_nopiv.cc + Tile_getrf_nopiv.hh).
- tournament pivoting / CALU (ref: internal_getrf_tntpiv.cc:837 +
  Tile_getrf_tntpiv.hh): blocks of rows are factored independently, each
  contributes its nb pivot-candidate rows, and a reduction tree selects the
  final pivot set before one clean factorization.  Here the tournament tree
  is computed on the (already gathered) panel — the pivot SELECTION is the
  CALU algorithm with identical numerics, while the communication shape it
  was invented for is already optimal under replication.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .trsm import tri_inv_lower, tri_inv_upper


# XLA's TPU LU custom call holds a [W, 128] column block in scoped VMEM;
# the chip compiler refuses it past this many rows (16384 x 128 is
# refused, 8192 x 512 compiles — tests/test_tpu_compile.py).
XLA_LU_MAX_ROWS = 8192


def panel_lu(panel):
    """Partially-pivoted LU of a gathered panel [W, nb].

    Returns (lu, perm) with panel[perm] = L @ U (L unit lower incl. rows
    below the square part; U upper nb x nb).  XLA's LU up to
    XLA_LU_MAX_ROWS rows, the recursive panel (same pivot rule) above:
    where both compile, XLA's ran 2.5x (128 x 128) to 6.6x (8192 x 512)
    faster on the chip (PERF.md; the benchmark's ``panel_ms.solve``
    reads the panel's time inside the solve).
    """
    if panel.shape[0] > XLA_LU_MAX_ROWS:
        return panel_lu_recursive(panel)
    lu, _, perm = lax.linalg.lu(panel)
    return lu, perm


def panel_lu_recursive(panel, base: int = 128):
    """Partially-pivoted LU of a panel [W, w], W >= w, by recursive
    column halving (Toledo; ref LAPACK getrf2): factor the left half,
    permute and solve the right half against it, factor the updated
    lower-right block.  Only the <= ``base``-wide leaves run the rank-1
    column loop (:func:`panel_lu_threshold` at tau = 1, which is exactly
    the max-magnitude pivot rule); everything between is MXU gemms.
    A 128-wide leaf moves the same lane-padded bytes per step as a
    narrower one and compiles ~5x faster at 16384 x 512.

    Its solves and updates multiply at HIGHEST: with the TPU's default
    f32 product (one bf16 pass) the factor's ||P A - L U|| / ||A|| was
    3e-2 to 8e-2 at 512 columns on the chip (PERF.md),
    since partial pivoting's unit-lower L11 can have a large inverse."""
    w = panel.shape[1]
    if w <= base:
        return panel_lu_threshold(panel, 1.0)
    h = w // 2
    with jax.default_matmul_precision("highest"):
        lu1, p1 = panel_lu_recursive(panel[:, :h], base)
        right = panel[p1, h:]
        l11 = jnp.tril(lu1[:h], -1) + jnp.eye(h, dtype=panel.dtype)
        u12 = tri_inv_lower(l11, unit_diag=True) @ right[:h]
        lu2, p2 = panel_lu_recursive(right[h:] - lu1[h:] @ u12, base)
    top = jnp.concatenate([lu1[:h], u12], axis=1)
    bot = jnp.concatenate([lu1[h:][p2], lu2], axis=1)
    perm = jnp.concatenate([p1[:h], p1[h:][p2]])
    return jnp.concatenate([top, bot], axis=0), perm


# ---- out-of-core step kernels (drivers/lu.py getrf_ooc) ----
# Pure jitted functions of the device windows the TileMap streams in;
# jit's shape-keyed cache reuses one executable per window shape across
# steps and across a checkpoint resume, which is what makes a resumed
# run bit-identical to the uninterrupted one.

@jax.jit
def ooc_lu_panel(panel):
    """Partially-pivoted LU of the gathered current panel [W, nb]."""
    return panel_lu(panel)


@jax.jit
def ooc_lu_trailing(colj, lu, perm):
    """One streamed right-looking trailing update: apply the panel's row
    permutation to trailing block column ``colj`` [W, wj], solve the U12
    strip against unit-L11 and subtract the L21 @ U12 contribution.
    Returns the updated [U12; trailing] column."""
    w = lu.shape[1]
    colj = colj[perm]
    u12 = tri_inv_lower(lu[:w, :w], unit_diag=True) @ colj[:w]
    tail = colj[w:] - lu[w:, :w] @ u12
    return jnp.concatenate([u12, tail], axis=0)


def _nopiv_fused_ok(dtype, w: int, nb: int) -> bool:
    """True when the tuned plan routes this no-pivot panel through the
    fused Pallas kernel (internal/pallas_lu.py lu_panel_fused): f32,
    MXU-aligned nb small enough for the [nb, nb] U^-1 scratch."""
    if not (dtype == jnp.float32 and w >= nb
            and nb % 128 == 0 and 128 <= nb <= 512):
        return False
    from ..tune import resolve_plan
    return resolve_plan("getrf_panel", w, "float32").kernel == "pallas"


def panel_lu_nopiv(panel):
    """No-pivot LU of a panel [W, nb] (ref: Tile_getrf_nopiv.hh).

    Routed through the tuned plan for ("getrf_panel", W): the fused
    Pallas panel (tile factor + per-row-tile TRSM in one pallas_call)
    when the plan says so, else the XLA composition — square top block
    factored unpivoted, rows below one MXU gemm against the inverted U
    (tri_inv_upper) instead of a per-column substitution loop.
    """
    nb = panel.shape[1]
    # slate-lint: disable=TRC001 -- capability probe: reads only static shape/dtype/plan, never tracer data
    if _nopiv_fused_ok(panel.dtype, panel.shape[0], nb):
        from ..tune import resolve_plan
        from .pallas_lu import lu_panel_fused
        from ..util.runtime import pallas_interpret
        w = panel.shape[0]
        plan = resolve_plan("getrf_panel", w, "float32")
        wp = -(-w // nb) * nb
        pp = jnp.pad(panel, ((0, wp - w), (0, 0))) if wp != w else panel
        lu = lu_panel_fused(pp, bw=plan.bw,
                            interpret=pallas_interpret())[:w]
        return lu, jnp.arange(w)
    top = panel[:nb]
    lu_top = _lu_nopiv_square(top)
    u = jnp.triu(lu_top)
    below = panel[nb:] @ tri_inv_upper(u)
    lu = jnp.concatenate([lu_top, below], axis=0)
    perm = jnp.arange(panel.shape[0])
    return lu, perm


def _lu_nopiv_base(a):
    """Unpivoted LU of a small square block via fori_loop elimination."""
    n = a.shape[0]

    def body(j, a):
        col = a[:, j]
        pivot = col[j]
        idx = jnp.arange(n)
        l = jnp.where(idx > j, col / pivot, jnp.zeros_like(col))
        a = a - jnp.outer(l, jnp.where(idx > j, a[j], 0.0))
        a = a.at[:, j].set(jnp.where(idx > j, l, col))
        return a

    return lax.fori_loop(0, n, body, a)


def _lu_nopiv_square(a, base: int = 64):
    """Unpivoted LU of a square block, recursively blocked: the rank-1
    elimination loop only ever runs on <= base-wide blocks; everything
    between is tri_inv-powered MXU gemms (same discipline as the blocked
    Householder panel, internal/qr.py)."""
    n = a.shape[0]
    if n <= base:
        return _lu_nopiv_base(a)
    h = n // 2
    a11 = _lu_nopiv_square(a[:h, :h], base)
    l11 = jnp.tril(a11, -1) + jnp.eye(h, dtype=a.dtype)
    u11 = jnp.triu(a11)
    u12 = tri_inv_lower(l11, unit_diag=True) @ a[:h, h:]
    l21 = a[h:, :h] @ tri_inv_upper(u11)
    a22 = _lu_nopiv_square(a[h:, h:] - l21 @ u12, base)
    top = jnp.concatenate([a11, u12], axis=1)
    bot = jnp.concatenate([l21, a22], axis=1)
    return jnp.concatenate([top, bot], axis=0)


def panel_lu_threshold(panel, tau):
    """Threshold-pivoted LU of a panel [W, nb] (ref: Option::PivotThreshold,
    enums.hh:91 'threshold for pivoting, >= 0, <= 1'; used by the reference
    getrf panel to prefer the diagonal when it is within ``tau`` of the
    column max, trading a bounded growth factor for fewer row swaps).

    One fori_loop of masked rank-1 steps; returns (lu, perm) like
    :func:`panel_lu`.
    """
    W, nb = panel.shape
    rows = jnp.arange(W)
    tau = jnp.asarray(tau, jnp.real(panel).dtype)

    def body(j, carry):
        a, perm = carry
        col = lax.dynamic_index_in_dim(a, j, axis=1, keepdims=False)
        mag = jnp.where(rows >= j, jnp.abs(col), -jnp.ones_like(
            jnp.abs(col)))
        cmax = jnp.max(mag)
        diag = jnp.abs(col[j])
        pos = jnp.where(diag >= tau * cmax, j, jnp.argmax(mag))
        # swap rows j <-> pos
        rj, rp = a[j], a[pos]
        a = a.at[j].set(rp).at[pos].set(rj)
        pj, pp = perm[j], perm[pos]
        perm = perm.at[j].set(pp).at[pos].set(pj)
        # eliminate below the diagonal
        colj = lax.dynamic_index_in_dim(a, j, axis=1, keepdims=False)
        piv = colj[j]
        safe = jnp.where(piv == 0, jnp.ones_like(piv), piv)
        l = jnp.where((rows > j) & (piv != 0), colj / safe,
                      jnp.zeros_like(colj))
        cols = jnp.arange(nb)
        rowj = jnp.where(cols > j, a[j], jnp.zeros_like(a[j]))
        a = a - jnp.outer(l, rowj)
        a = a.at[:, j].set(jnp.where(rows > j, l, colj))
        return a, perm

    lu, perm = lax.fori_loop(0, min(W, nb), body,
                             (panel, jnp.arange(W)))
    return lu, perm


def _lu_select_ok(blocks, nb: int) -> bool:
    """Route tournament pivot selection through the Pallas kernel
    (internal/pallas_lu.py) when the tuned plan for ("lu_select", W)
    says so.  The old direct SLATE_PALLAS=1 gate is deprecated — the
    tune resolver honors the env var for one release as a force
    override (docs/TUNING.md)."""
    from ..tune import resolve_plan
    W = blocks.shape[1]
    return (resolve_plan("lu_select", W, "float32").kernel == "pallas"
            and blocks.dtype == jnp.float32
            and nb % 128 == 0 and W % 128 == 0 and W <= 4096)


def panel_lu_tournament(panel, block_rows: int, arity: int = 2):
    """CALU tournament pivot selection + clean factorization
    (ref: internal_getrf_tntpiv.cc, Tile_getrf_tntpiv.hh).

    Round 1: factor every block of ``block_rows`` rows in ONE batched
    (vmapped) pivoted LU and keep each block's nb pivot rows.  Reduction
    rounds: merge ``arity`` candidate sets at a time (Option.Depth — the
    fan-in), again one batched LU per LEVEL — the tree is latency-bound,
    and XLA's batched LU amortizes its per-column While latency across
    the whole batch (measured 5.4x, docs/ceiling.jsonl xla_lu batch32).
    Finally the chosen rows move to the top via a VECTORIZED permutation
    that displaces at most 2 nb rows (the bound the distributed bundle
    exchange relies on), and the permuted panel is factored with no
    further pivoting across blocks — CALU's defining step.
    Returns (lu, perm) like :func:`panel_lu`.
    """
    arity = max(2, int(arity))
    W, nb = panel.shape
    iota = jnp.arange(W)
    if W <= nb:
        lu, _, perm = lax.linalg.lu(panel)
        return lu, perm
    block_rows = max(block_rows, nb)
    nch = -(-W // block_rows)
    Wp = nch * block_rows
    pp = jnp.pad(panel, ((0, Wp - W), (0, 0)))
    # pad rows carry sentinel index W; all-zero, they lose every pivot
    # contest against any nonzero row
    gidx = jnp.concatenate([iota, jnp.full((Wp - W,), W, iota.dtype)])
    cand = pp.reshape(nch, block_rows, nb)
    cidx = gidx.reshape(nch, block_rows)

    def keep_best(blocks, idx):
        # slate-lint: disable=TRC001 -- capability probe: reads only static shape/dtype/env, never tracer data
        if _lu_select_ok(blocks, nb):
            from ..tune import resolve_plan
            from .pallas_lu import lu_select_pallas
            from ..util.runtime import pallas_interpret
            bw = resolve_plan("lu_select", blocks.shape[1], "float32").bw
            take = jax.vmap(lambda b: lu_select_pallas(
                b, bw=bw, interpret=pallas_interpret()))(blocks)
        else:
            _, _, pb = jax.vmap(lax.linalg.lu)(blocks)
            take = pb[:, :nb]
        return (jnp.take_along_axis(blocks, take[:, :, None], axis=1),
                jnp.take_along_axis(idx, take, axis=1))

    if block_rows > nb:
        cand, cidx = keep_best(cand, cidx)
    while cand.shape[0] > 1:
        g = cand.shape[0]
        gp = -(-g // arity) * arity
        if gp > g:
            cand = jnp.concatenate(
                [cand, jnp.zeros((gp - g,) + cand.shape[1:], cand.dtype)])
            cidx = jnp.concatenate(
                [cidx, jnp.full((gp - g, cidx.shape[1]), W, cidx.dtype)])
        rows_per = cand.shape[1]
        cand = cand.reshape(gp // arity, arity * rows_per, nb)
        cidx = cidx.reshape(gp // arity, arity * rows_per)
        cand, cidx = keep_best(cand, cidx)
    chosen = cidx[0, :nb]
    # sentinel guard (only reachable for a singular panel): fill sentinel
    # slots with the smallest NOT-chosen rows so `chosen` stays a set of
    # nb DISTINCT in-range rows (a naive slot-index fallback can collide
    # with a genuinely chosen row and silently drop a matrix row)
    valid = chosen < W
    # scatter sentinels OUT of range (mode="drop") — aliasing them to a
    # real index races a True and a False onto that slot
    in_ch0 = jnp.zeros((W,), jnp.bool_).at[
        jnp.where(valid, chosen, W)].set(True, mode="drop")
    free = jnp.sort(jnp.where(in_ch0, W + iota, iota))
    kfree = jnp.cumsum(~valid) - 1
    chosen = jnp.where(valid, chosen,
                       free[jnp.clip(kfree, 0, W - 1)].astype(chosen.dtype))

    # Vectorized pivot placement: perm[j] = chosen[j] for j < nb, and the
    # displaced top rows fill the holes the chosen rows left (both in
    # ascending order) — a permutation displacing <= 2 nb rows, with no
    # nb-step transposition loop.
    in_ch = jnp.zeros((W,), jnp.bool_).at[chosen].set(True)
    s1 = (~in_ch) & (iota < nb)              # top rows pushed out
    s2 = in_ch & (iota >= nb)                # holes left below
    idx1 = jnp.sort(jnp.where(s1, iota, W + iota))[:nb]
    r2 = jnp.cumsum(s2.astype(jnp.int32)) - 1
    fill = idx1[jnp.clip(r2, 0, nb - 1)]
    perm = iota.at[:nb].set(chosen)
    perm = jnp.where(s2, jnp.where(fill < W, fill, iota), perm)
    lu, _ = panel_lu_nopiv(panel[perm])
    return lu, perm
