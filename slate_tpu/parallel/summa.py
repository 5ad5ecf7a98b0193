"""Distributed gemm: stationary-C SUMMA over the 2D block-cyclic mesh.

Analog of the reference's gemmC driver + internal::gemm<Devices>
(ref: src/gemmC.cc:29-192, src/internal/internal_gemm.cc:383-688):

reference                             | here
------------------------------------- | ----------------------------------
omp task DAG over k, lookahead la     | software pipeline in the fori_loop
  (gemmC.cc:99-115)                   |   carry: step k+la's ring broadcast
                                      |   is issued before step k's MXU
                                      |   accumulate, so ICI rides under
                                      |   compute (depth from tune/
                                      |   ``dist_lookahead``; 0 = the
                                      |   bulk-synchronous oracle)
A.listBcastMT(A(i,k) -> row owners)   | ring_bcast_from_col(a_col, k % q)
B.listBcastMT(B(k,j) -> col owners)   | ring_bcast_from_row(b_row, k % p)
blas::batch::gemm 4-region            | one einsum over local tile batch
tileTick workspace release            | SSA temporary, freed by XLA

The loop body is identical on every rank (SPMD); the data-dependent owner
(k % q) is handled by a masked-psum broadcast at depth 0 and by a
ppermute ring at depth >= 1 — both deliver the owner's exact bytes, so
every depth produces bit-identical results and depth 0 stays the parity
oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..comm.collectives import (bcast_from_col, bcast_from_row,
                                ring_bcast_from_col, ring_bcast_from_row)
from ..core.grid import AXIS_P, AXIS_Q, TILE_SPEC, Grid
from ..internal.gemm import tile_outer_product
from ..robust import abft as _abft
from ..robust import faults
from ..util.compat_jax import pvary
from ..util.trace import span


def summa_local(a_loc, b_loc, c_loc, alpha, beta, Kt: int, p: int, q: int,
                abft: bool = False, la: int = 0):
    """Per-shard SUMMA body (runs inside shard_map).

    a_loc [mtl, ktl_a, mb, kb], b_loc [ktl_b, ntl, kb, nb],
    c_loc [mtl, ntl, mb, nb] — this shard's block-cyclic tiles.

    ``abft`` carries Huang-Abraham checksums of the accumulator through
    the k loop: the broadcast panels already ride the existing
    collectives, so the expected row/column sums of ``sum_k A(:,k)
    B(k,:)`` are accumulated locally at O(nb^2) per step — zero extra
    communication.  After the loop the accumulator is verified tile by
    tile and a single corrupted element is repaired in place
    (robust/abft.py); returns ``(result, detected, corrected, site)``
    with the counters psum-combined over the whole mesh.

    ``la`` (0/1/2, static) is the lookahead depth: at depth d >= 1 the
    fori_loop carry holds the next d steps' panels already in flight —
    the prologue issues the first d ring broadcasts, and each body issues
    step k+d's before accumulating the carried step k, so the broadcast
    rides ICI underneath the MXU accumulate (ref gemmC.cc:99-115).  The
    final body iterations re-issue the clamped last panel; the result is
    dropped with the carry, and since gemm writes no panel state back
    there is nothing to mask.  Checksums are maintained from the consumed
    buffer, so ABFT counters match depth 0 exactly.
    """

    def fetch(k):
        a_col = lax.dynamic_index_in_dim(a_loc, k // q, axis=1,
                                         keepdims=False)
        b_row = lax.dynamic_index_in_dim(b_loc, k // p, axis=0,
                                         keepdims=False)
        return a_col, b_row

    def step(k):
        with span("slate.gemm/bcast"):
            a_col, b_row = fetch(k)
            a_col = bcast_from_col(a_col, k % q)
            b_row = bcast_from_row(b_row, k % p)
        return a_col, b_row

    def issue(k):
        with span("slate.gemm/bcast_ahead"):
            a_col, b_row = fetch(k)
            a_col = ring_bcast_from_col(a_col, k % q, q)
            b_row = ring_bcast_from_row(b_row, k % p, p)
        return a_col, b_row

    if not abft:
        if la == 0:
            def body(k, acc):
                a_col, b_row = step(k)
                with span("slate.gemm/accumulate"):
                    return acc + tile_outer_product(a_col, b_row)

            acc = lax.fori_loop(0, Kt, body, jnp.zeros_like(c_loc))
        else:
            def body(k, carry):
                acc, bufs = carry
                nxt = issue(jnp.minimum(k + la, Kt - 1))
                a_col, b_row = bufs[0]
                with span("slate.gemm/accumulate"):
                    acc = acc + tile_outer_product(a_col, b_row)
                return acc, bufs[1:] + (nxt,)

            bufs = tuple(issue(min(d, Kt - 1)) for d in range(la))
            acc, _ = lax.fori_loop(0, Kt, body,
                                   (jnp.zeros_like(c_loc), bufs))
        acc = faults.maybe_corrupt("post_collective", acc)
        return alpha * acc + beta * c_loc

    mtl, ntl, mb, nb = c_loc.shape
    kb = a_loc.shape[3]
    dt = c_loc.dtype

    def consume(k, acc, rexp, cexp, a_col, b_row):
        with span("slate.gemm/accumulate"):
            acc = acc + tile_outer_product(a_col, b_row)
            # checksum maintenance without forming the product:
            # A (B e) and (e^T A) B per tile pair, O(tiles * nb^2)
            rexp = rexp + _abft.tile_product_row_sums(a_col[:, None],
                                                      b_row[None])
            cexp = cexp + _abft.tile_product_col_sums(a_col[:, None],
                                                      b_row[None])
        return acc, rexp, cexp

    zero = (jnp.zeros_like(c_loc),
            pvary(jnp.zeros((mtl, ntl, mb), dt), (AXIS_P, AXIS_Q)),
            pvary(jnp.zeros((mtl, ntl, nb), dt), (AXIS_P, AXIS_Q)))
    if la == 0:
        def body(k, carry):
            a_col, b_row = step(k)
            return consume(k, *carry, a_col, b_row)

        acc, rexp, cexp = lax.fori_loop(0, Kt, body, zero)
    else:
        def body(k, carry):
            acc, rexp, cexp, bufs = carry
            nxt = issue(jnp.minimum(k + la, Kt - 1))
            a_col, b_row = bufs[0]
            acc, rexp, cexp = consume(k, acc, rexp, cexp, a_col, b_row)
            return acc, rexp, cexp, bufs[1:] + (nxt,)

        bufs = tuple(issue(min(d, Kt - 1)) for d in range(la))
        acc, rexp, cexp, _ = lax.fori_loop(0, Kt, body, zero + (bufs,))
    acc = faults.maybe_corrupt("post_collective", acc)
    acc, ev, ti_l, tj_l = _abft.tile_sum_check(acc, rexp, cexp,
                                               n_ctx=Kt * kb)
    r = lax.axis_index(AXIS_P)
    c = lax.axis_index(AXIS_Q)
    site_l = jnp.where(ev.detected > 0,
                       _abft.site_code(r + p * ti_l, c + q * tj_l),
                       jnp.asarray(-1, jnp.int32))
    det = lax.psum(lax.psum(ev.detected, AXIS_P), AXIS_Q)
    cor = lax.psum(lax.psum(ev.corrected, AXIS_P), AXIS_Q)
    site = lax.pmax(lax.pmax(site_l, AXIS_P), AXIS_Q)
    return alpha * acc + beta * c_loc, det, cor, site


def summa_gemm_data(a_data, b_data, c_data, alpha, beta, Kt, grid: Grid,
                    abft: bool = False, la: int | None = None):
    """shard_map wrapper over the cyclic storage arrays.  With ``abft``
    returns ``(data, detected, corrected, site)`` — the extra outputs
    are fully replicated scalars.  ``la`` is the lookahead depth; None
    resolves the tuned depth through the ``dist_lookahead`` plan
    (SEAM011 — untuned chips stay on the depth-0 oracle)."""
    if la is None:
        from ..tune import lookahead_depth
        la = lookahead_depth(Kt * a_data.shape[3], a_data.dtype.name)
    spec = TILE_SPEC
    out_specs = (spec, P(), P(), P()) if abft else spec
    fn = jax.shard_map(
        lambda a, b, c: summa_local(a, b, c, alpha, beta, Kt,
                                    grid.p, grid.q, abft=abft, la=la),
        mesh=grid.mesh, in_specs=(spec, spec, spec), out_specs=out_specs)
    return fn(a_data, b_data, c_data)
