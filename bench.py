"""Headline benchmarks for the driver: prints one JSON line PER metric.

Covers BASELINE.md configs 1-4 (single-chip, single-target — the per-chip
building block of the 2D-grid configs) plus raw-MXU context:

  gemm   n=4096  f32  (config #1, kept for cross-round continuity)
  gemm   n=8192  f32  (larger-tile point where the chip leaves dispatch
                       overhead behind; closer to the chip's real ceiling)
  gemm   n=16384 f32  (near-peak point: raw dot measures ~0.6 MFU here)
  posv   n=16384 f32  (config #2 family: potrf + potrs, nrhs=256)
  gesv   n=16384 f32  (config #3 family: getrf partial pivot + getrs)
  geqrf  131072x1024  (config #4: tall-skinny Householder QR)
  gels   131072x1024  (config #4: least squares, auto method = CholQR)

Each line reports GFLOP/s/chip, ``mfu`` — the fraction of the chip's
dense-matmul peak — ``device_ms`` (best-rep seconds per chained solve) and
``flops`` (the per-iteration analytic count).  Both the flop formulas and
the chip-peak table come from slate_tpu.obs.flops — the SAME registry that
prices driver events under ``obs.timing()`` — so a bench line and a
production event can never disagree about an op's MFU (on TPU the MXU
computes bf16 x bf16 -> f32, and XLA's default f32 matmul runs single-pass
at that same rate, so one peak number applies to both precisions).  The
registered counts follow the reference tester: gemm 2mnk (ref:
src/gemm.cc:24), potrf n^3/3 + solve 2n^2*nrhs (ref: src/potrf.cc:334),
getrf 2n^3/3 + solve, geqrf 2mn^2 - 2n^3/3 (testsweeper gflop helpers);
gels reports the same nominal flops as the QR path regardless of method,
as the reference tester does.

Timing: each benchmark chains ``iters`` DEPENDENT solves inside one
jitted lax.scan (a scalar distilled from each result perturbs the next
input, so nothing is dead code and steps cannot overlap) and fetches one
element once, so per-dispatch host overhead is amortized over the chain.

``vs_baseline`` is value / 702 GFLOP/s — the only absolute number the
reference repo publishes (dgemm n=10000, 4 ranks x 1 GPU, 0.712 s =
702 GFLOP/s per GPU, ref docs/usage.md:41-42).  Set SLATE_BENCH_QUICK=1 for
a seconds-scale smoke run of the same harness at toy sizes.

``--sweep-nb`` switches to the autotuner's search space instead of the
headline metrics: one JSON line per candidate (kernel, nb, bw) plan per
op (slate_tpu.tune.autotune), so BENCH rounds record what the tuner saw.
"""

import argparse
import json
import os
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import slate_tpu as st
from slate_tpu.core.storage import TileStorage
from slate_tpu.obs import flops as _flops
from slate_tpu.obs.metrics import BENCH_SCHEMA
from slate_tpu.util.runtime import pallas_interpret, use_compile_cache

BASELINE_GFLOPS_PER_CHIP = 702.0  # ref docs/usage.md:41-42, per-GPU dgemm
QUICK = bool(int(os.environ.get("SLATE_BENCH_QUICK", "0")))
# per-metric time budget in seconds (0 = unlimited).  The run gets a total
# pool of BUDGET_S * n_metrics; a metric that would start with the pool
# exhausted, or that overruns it mid-flight (SIGALRM preemption), emits an
# explicit "skipped" JSON line instead of eating the remaining metrics'
# time — every invocation emits one line per metric and exits 0.
BUDGET_S = float(os.environ.get("SLATE_BENCH_BUDGET_S", "0") or 0)


def _chip_peak():
    """(dense matmul peak FLOP/s, device_kind) for MFU; None if unknown.
    Delegates to obs.flops.chip_peak — ONE peak table for bench lines and
    timed driver events alike."""
    return _flops.chip_peak()


PEAK, CHIP = None, "cpu"

# Live progress shared with the watchdog thread: which step index is in
# flight, whether it is compiling or running timed reps, and when it
# started — so a budget skip line can say WHERE the time went (a stall in
# a 400 s compile reads very differently from a slow run phase).
_PROGRESS = {"idx": None, "phase": None, "t0": None}


def _mat(dense, mb, nb):
    return st.Matrix(TileStorage.from_dense(dense, mb, nb))


def _time_chain(body, init, args, iters, flops_per_iter, reps=3):
    """Best-of-reps (GFLOP/s, seconds-per-iteration) for ``iters``
    dependent body applications.

    ``args`` (the big operands) are jit ARGUMENTS, not closure constants,
    so the data is never baked into the compiled program."""

    def chained(c0, *ops):
        c, _ = lax.scan(lambda c, _: (body(c, *ops), None), c0, None,
                        length=iters)
        # distil to ONE scalar: the fetch must not time a large transfer
        while getattr(c, "ndim", 0) > 0:
            c = c[(0,) * c.ndim]
        return c

    run = jax.jit(chained)
    _PROGRESS["phase"] = "compile"
    np.asarray(jax.device_get(run(init, *args)))   # compile + warmup
    _PROGRESS["phase"] = "run"
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(jax.device_get(run(init, *args)))
        times.append(time.perf_counter() - t0)
    sec = min(times) / iters
    return flops_per_iter / sec / 1e9, sec


def _emit(metric, timed, flops=None, extra=None):
    """One bench line.  ``timed`` is a _time_chain result — (GFLOP/s,
    sec-per-iter) — or a bare GFLOP/s number; ``flops`` the analytic
    per-iteration count (from the obs.flops registry) recorded so any
    consumer can re-derive mfu = value*1e9 / PEAK without re-implementing
    the model."""
    gflops, sec = timed if isinstance(timed, tuple) else (timed, None)
    line = {
        "schema": BENCH_SCHEMA,
        "metric": metric,
        "value": round(float(gflops), 1),
        "unit": "GFLOP/s",
        "vs_baseline": round(float(gflops) / BASELINE_GFLOPS_PER_CHIP, 2),
        "mfu": (round(gflops * 1e9 / PEAK, 3) if PEAK else None),
        "chip": CHIP,
        "device_ms": (round(sec * 1e3, 3) if sec is not None else None),
        "flops": flops,
    }
    if extra:
        line.update(extra)
    print(json.dumps(line), flush=True)


def bench_gemm(n, nb, iters):
    rng = np.random.default_rng(0)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    A = st.Matrix.from_numpy(a, nb, nb)
    B = st.Matrix.from_numpy(b, nb, nb)

    def body(carry, adata):
        # carry IS the tile storage of the running product (no re-tiling)
        C = st.gemm(1.0 / n, st.Matrix(TileStorage(
            adata, A.storage.m, A.storage.n, nb, nb, A.storage.grid)),
            st.Matrix(TileStorage(carry, B.storage.m, B.storage.n, nb, nb,
                                  B.storage.grid)))
        return C.storage.data

    flops = _flops.op_flops("gemm", [(n, n), (n, n)])
    timed = _time_chain(body, B.storage.data, (A.storage.data,), iters,
                        flops)
    _emit(f"gemm_n{n}_gflops_per_chip", timed, flops, {"nb": nb})


def bench_posv(n, nb, nrhs, iters):
    rng = np.random.default_rng(1)
    # SPD without an O(n^3) host product: symmetrize + diagonal dominance
    a0 = rng.standard_normal((n, n)).astype(np.float32)
    a = jnp.asarray(a0 + a0.T) * 0.001 + jnp.eye(n, dtype=jnp.float32) * 4.0
    b = jnp.asarray(rng.standard_normal((n, nrhs)).astype(np.float32))

    def body(carry, a, b):
        H = st.HermitianMatrix._from_view(
            _mat(a * (1.0 + carry), nb, nb), st.Uplo.Lower)
        _, X = st.posv(H, _mat(b, nb, nb))
        return X.to_dense()[0, 0] * 1e-24      # data dependence, ~0

    flops = _flops.op_flops("posv", [(n, n), (n, nrhs)])
    timed = _time_chain(body, jnp.float32(0.0), (a, b), iters, flops)
    _emit(f"posv_n{n}_gflops_per_chip", timed, flops,
          {"nb": nb, "nrhs": nrhs})


def bench_gesv(n, nb, nrhs, iters):
    rng = np.random.default_rng(2)
    a = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((n, nrhs)).astype(np.float32))
    # CALU tournament pivoting — BASELINE config #3 specifies the tntpiv
    # variant (and its bounded-height chunk LUs fit TPU scoped VMEM, which
    # XLA's monolithic tall-panel LU custom call does not at this size).
    # Depth=4 flattens the reduction tree to ONE batched merge level —
    # each level is a latency-bound batched LU, so fewer levels win.
    opts = {st.Option.MethodLU: st.MethodLU.CALU, st.Option.Depth: 4}

    def body(carry, a, b):
        A = _mat(a * (1.0 + carry), nb, nb)
        _, X = st.gesv(A, _mat(b, nb, nb), opts)
        return X.to_dense()[0, 0] * 1e-24

    flops = _flops.op_flops("gesv", [(n, n), (n, nrhs)])
    timed = _time_chain(body, jnp.float32(0.0), (a, b), iters, flops)
    _emit(f"gesv_n{n}_gflops_per_chip", timed, flops,
          {"nb": nb, "nrhs": nrhs, "method": "tntpiv"})


def bench_geqrf(m, n, nb, iters):
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))

    def body(carry, a):
        F = st.geqrf(_mat(a * (1.0 + carry), nb, nb))
        return F.QR.to_dense()[0, 0] * 1e-24

    flops = _flops.op_flops("geqrf", [(m, n)])
    timed = _time_chain(body, jnp.float32(0.0), (a,), iters, flops)
    _emit(f"geqrf_tall_{m}x{n}_gflops_per_chip", timed, flops, {"nb": nb})


def bench_gels(m, n, nb, nrhs, iters):
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((m, nrhs)).astype(np.float32))

    def body(carry, a, b):
        X = st.gels(_mat(a * (1.0 + carry), nb, nb), _mat(b, nb, nb))
        return X.to_dense()[0, 0] * 1e-24

    # nominal QR-path flops, as the reference tester reports for any method
    flops = _flops.op_flops("gels", [(m, n), (m, nrhs)])
    timed = _time_chain(body, jnp.float32(0.0), (a, b), iters, flops)
    _emit(f"gels_tall_{m}x{n}_gflops_per_chip", timed, flops,
          {"nb": nb, "nrhs": nrhs, "method": "cholqr"})


def bench_gesv_rbt(n, nb, nrhs, iters):
    """gesv under Option.Speculate: RBT-preconditioned NoPiv LU + 2 IR
    steps + residual certificate (robust/recovery.py) — the pivot-free
    fast path that targets posv's regime instead of the CALU pivoting
    wall (docs/PERF.md round 6).  Under jit the whole speculative attempt
    traces into one program (certification rides along as data; the
    escalation branch is eager-only), so this measures the honest
    fast-path cost including its certificate."""
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((n, nrhs)).astype(np.float32))
    opts = {st.Option.Speculate: "on", st.Option.ErrorPolicy: "info"}

    def body(carry, a, b):
        A = _mat(a * (1.0 + carry), nb, nb)
        _, X, h = st.gesv(A, _mat(b, nb, nb), opts)
        return X.to_dense()[0, 0] * 1e-24

    flops = _flops.op_flops("gesv", [(n, n), (n, nrhs)])
    timed = _time_chain(body, jnp.float32(0.0), (a, b), iters, flops)
    _emit(f"gesv_rbt_n{n}_gflops_per_chip", timed, flops,
          {"nb": nb, "nrhs": nrhs, "method": "rbt+nopiv"})


def bench_gesv_abft(n, nb, nrhs, iters):
    """gesv under Option.Abft (Huang-Abraham checksum verification of the
    panel, the U12 solve, and the trailing update — robust/abft.py) timed
    against the identical plain run: the emitted value is the protected
    GFLOP/s, ``abft_overhead_pct`` the wall-clock cost of the O(n^2)
    checksum shadow over the O(n^3) it guards."""
    rng = np.random.default_rng(8)
    a = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((n, nrhs)).astype(np.float32))

    def body_for(opts):
        def body(carry, a, b):
            A = _mat(a * (1.0 + carry), nb, nb)
            out = st.gesv(A, _mat(b, nb, nb), opts)
            return out[1].to_dense()[0, 0] * 1e-24
        return body

    flops = _flops.op_flops("gesv", [(n, n), (n, nrhs)])
    plain, _ = _time_chain(body_for(None), jnp.float32(0.0), (a, b), iters,
                           flops)
    prot = _time_chain(
        body_for({st.Option.Abft: "on", st.Option.ErrorPolicy: "info"}),
        jnp.float32(0.0), (a, b), iters, flops)
    _emit(f"gesv_abft_n{n}_gflops_per_chip", prot, flops,
          {"nb": nb, "nrhs": nrhs, "plain_gflops": round(float(plain), 1),
           "abft_overhead_pct": round((plain / prot[0] - 1.0) * 100.0, 1)})


def bench_posv_abft(n, nb, nrhs, iters):
    """posv under Option.Abft vs plain (see bench_gesv_abft)."""
    rng = np.random.default_rng(9)
    a0 = rng.standard_normal((n, n)).astype(np.float32)
    a = jnp.asarray(a0 + a0.T) * 0.001 + jnp.eye(n, dtype=jnp.float32) * 4.0
    b = jnp.asarray(rng.standard_normal((n, nrhs)).astype(np.float32))

    def body_for(opts):
        def body(carry, a, b):
            H = st.HermitianMatrix._from_view(
                _mat(a * (1.0 + carry), nb, nb), st.Uplo.Lower)
            out = st.posv(H, _mat(b, nb, nb), opts)
            return out[1].to_dense()[0, 0] * 1e-24
        return body

    flops = _flops.op_flops("posv", [(n, n), (n, nrhs)])
    plain, _ = _time_chain(body_for(None), jnp.float32(0.0), (a, b), iters,
                           flops)
    prot = _time_chain(
        body_for({st.Option.Abft: "on", st.Option.ErrorPolicy: "info"}),
        jnp.float32(0.0), (a, b), iters, flops)
    _emit(f"posv_abft_n{n}_gflops_per_chip", prot, flops,
          {"nb": nb, "nrhs": nrhs, "plain_gflops": round(float(plain), 1),
           "abft_overhead_pct": round((plain / prot[0] - 1.0) * 100.0, 1)})


def bench_heev(n, nb, iters):
    """Two-stage eigensolver, values only (BASELINE config #5 family).

    Stage 2 is the MethodEig.Auto band seam: jitted end-to-end this runs
    ~62x faster than routing through the bulge-chase scan (39.8 s -> 0.64 s
    at n=4096 on one v5e chip; the chase's sequential rank-1 scan steps are
    pure dispatch latency when the tridiagonal kernel is dense eigh anyway).
    """
    rng = np.random.default_rng(5)
    a0 = rng.standard_normal((n, n)).astype(np.float32)
    a = jnp.asarray((a0 + a0.T) / 2)

    def body(carry, a):
        H = st.HermitianMatrix._from_view(
            _mat(a * (1.0 + carry), nb, nb), st.Uplo.Lower)
        w = st.heev_vals(H)
        return w[0] * 1e-24

    flops = _flops.op_flops("heev_vals", [(n, n)])
    timed = _time_chain(body, jnp.float32(0.0), (a,), iters, flops)
    _emit(f"heev_vals_n{n}_gflops_per_chip", timed, flops, {"nb": nb})


def bench_svd(n, nb, iters):
    """Two-stage SVD, values only (BASELINE config #5 family): ge2tb band
    reduction + the MethodSvd.Auto band seam."""
    rng = np.random.default_rng(6)
    a = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32))

    def body(carry, a):
        s = st.svd_vals(_mat(a * (1.0 + carry), nb, nb))
        return s[0] * 1e-24

    flops = _flops.op_flops("svd_vals", [(n, n)])
    timed = _time_chain(body, jnp.float32(0.0), (a,), iters, flops)
    _emit(f"svd_vals_n{n}_gflops_per_chip", timed, flops, {"nb": nb})


def bench_potrf_fused(n, nb, bw, iters):
    """Fused Cholesky panel step (PERF r7): one pallas_call doing the
    trailing update (col - left @ lead), the nb x nb tile factor, and the
    L21 panel solve, MXU-resident.  Measures the panel seam in isolation
    so sweeps can compare (nb, bw) plans without full-driver noise."""
    from slate_tpu.internal.pallas_chol import chol_panel_fused

    rng = np.random.default_rng(7)
    k = nb                                  # one prior panel of history
    base = rng.standard_normal((n, nb)).astype(np.float32)
    top = base[:nb] @ base[:nb].T / nb + nb * np.eye(nb, dtype=np.float32)
    target = np.concatenate([top, base[nb:]], axis=0)
    left = (rng.standard_normal((n, k)).astype(np.float32) * 0.01)
    lead = left[:nb].T.copy()
    col = jnp.asarray(target + left @ lead)
    left, lead = jnp.asarray(left), jnp.asarray(lead)
    interp = pallas_interpret()

    def body(carry, col, left, lead):
        upd, fac = chol_panel_fused(col * (1.0 + carry), left, lead,
                                    bw=bw, interpret=interp)
        return fac[0, 0] * 1e-24

    # update 2*n*nb*k + tile factor nb^3/3 + panel solve (n-nb)*nb^2 —
    # a kernel-seam cost, not a public op, so no registry entry applies
    flops = 2.0 * n * nb * k + nb**3 / 3.0 + (n - nb) * nb**2
    timed = _time_chain(body, jnp.float32(0.0), (col, left, lead),
                        iters, flops)
    _emit(f"potrf_fused_n{n}_gflops_per_chip", timed, flops,
          {"nb": nb, "bw": bw})


def bench_geqrf_panel(m, n, iters):
    """Pallas Householder QR panel (PERF r7): panel factor + compact-WY T
    in one kernel.  The panel is the latency-bound piece of tall-skinny
    geqrf, so its throughput bounds the gels MFU target."""
    from slate_tpu.internal.pallas_qr import qr_panel_pallas

    rng = np.random.default_rng(8)
    a = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
    interp = pallas_interpret()

    def body(carry, a):
        packed, t = qr_panel_pallas(a * (1.0 + carry), interpret=interp)
        return packed[0, 0] * 1e-24

    flops = 2.0 * m * n**2            # dominant term of 2mn^2 - 2n^3/3
    timed = _time_chain(body, jnp.float32(0.0), (a,), iters, flops)
    _emit(f"geqrf_panel_m{m}_n{n}_gflops_per_chip", timed, flops)


def _lookahead_grid():
    """Largest supported process grid on this host: (2,2) with >=4
    devices, a 1-D ring with 2, degenerate (1,1) otherwise (rings of
    size 1 have zero hops — the bench still runs and reports)."""
    devs = jax.devices()
    if len(devs) >= 4:
        p, q = 2, 2
    elif len(devs) >= 2:
        p, q = 1, 2
    else:
        p, q = 1, 1
    return st.Grid(p, q, devices=devs[: p * q])


def _overlap_probe(g, mtl, ntl, nb, op, both_axes=True, reps=5):
    """overlap_pct for the PERF r15 pipeline: the share of one step's
    panel ring-broadcast wall time that the same step's local
    accumulate can hide — sum(min(bcast_i, acc_i)) / sum(bcast_i) over
    ``reps`` eagerly timed phase pairs.  100% means depth-1 lookahead
    fully hides the broadcast; the phases are timed under the SAME span
    names the jitted pipeline emits (slate.<op>/bcast_ahead vs
    /accumulate) so the flight recorder and this probe agree on
    vocabulary.  ``both_axes`` times the SUMMA pair of rings (A panel
    along q, B panel along p); off, the factorization single col-ring."""
    from slate_tpu import obs
    from slate_tpu.comm.collectives import (ring_bcast_from_col,
                                            ring_bcast_from_row)
    from slate_tpu.core.grid import TILE_SPEC
    from slate_tpu.util.trace import span

    spec = TILE_SPEC
    p, q = g.p, g.q

    def _bcast(apan, bpan):
        out = ring_bcast_from_col(apan, 0, q)
        if both_axes:
            return out, ring_bcast_from_row(bpan, 0, p)
        return out, bpan

    def _acc(apan, bpan, c):
        return c + jnp.einsum("mkab,knbc->mnac", apan, bpan)

    bc = jax.jit(jax.shard_map(_bcast, mesh=g.mesh, in_specs=(spec, spec),
                               out_specs=(spec, spec)))
    ac = jax.jit(jax.shard_map(_acc, mesh=g.mesh,
                               in_specs=(spec, spec, spec),
                               out_specs=spec))
    rng = np.random.default_rng(15)
    apan = jnp.asarray(rng.standard_normal(
        (p * mtl, q, nb, nb)).astype(np.float32))
    bpan = jnp.asarray(rng.standard_normal(
        (p, q * ntl, nb, nb)).astype(np.float32))
    c = jnp.zeros((p * mtl, q * ntl, nb, nb), jnp.float32)
    jax.block_until_ready(bc(apan, bpan))          # compile outside timing
    jax.block_until_ready(ac(apan, bpan, c))
    with obs.record_spans() as rec:
        for _ in range(reps):
            with span(f"slate.{op}/bcast_ahead"):
                jax.block_until_ready(bc(apan, bpan))
            with span(f"slate.{op}/accumulate"):
                jax.block_until_ready(ac(apan, bpan, c))
    bts = [s["dur_ms"] for s in rec.spans
           if s["name"].endswith("/bcast_ahead")]
    ats = [s["dur_ms"] for s in rec.spans
           if s["name"].endswith("/accumulate")]
    hidden = sum(min(b, a) for b, a in zip(bts, ats))
    return 100.0 * hidden / max(sum(bts), 1e-12)


def bench_summa_lookahead(n, nb, iters):
    """Lookahead-pipelined SUMMA (PERF r15): GFLOP/s at depth 0 (the
    bulk-synchronous oracle) vs the tuned ring-pipeline depth, their
    ratio, and overlap_pct — how much of the per-step panel broadcast
    the trailing accumulate can hide.  Depths produce bit-identical
    output (tests/test_lookahead.py), so the speedup line is pure
    schedule, no numerics."""
    from slate_tpu.core.layout import num_tiles
    from slate_tpu.parallel.summa import summa_gemm_data
    from slate_tpu.tune import lookahead_depth

    g = _lookahead_grid()
    p, q = g.p, g.q
    rng = np.random.default_rng(15)
    a = rng.standard_normal((n, n)).astype(np.float32)
    b = rng.standard_normal((n, n)).astype(np.float32)
    A = st.Matrix.from_numpy(a, nb, nb, g)
    B = st.Matrix.from_numpy(b, nb, nb, g)
    C = st.Matrix.from_numpy(np.zeros((n, n), np.float32), nb, nb, g)
    Kt = num_tiles(n, nb)
    la = max(1, lookahead_depth(n, "float32"))

    flops = _flops.op_flops("gemm", [(n, n), (n, n)])
    gf = {}
    for depth in (0, la):
        def body(carry, ad, bd, depth=depth):
            return summa_gemm_data(ad, bd, carry, 1.0 / n, 0.0, Kt, g,
                                   la=depth)
        timed = _time_chain(body, C.storage.data,
                            (A.storage.data, B.storage.data), iters,
                            flops)
        gf[depth] = timed[0]
        _emit(f"summa_lookahead_d{depth}_n{n}_gflops", timed, flops,
              {"nb": nb, "grid": f"{p}x{q}", "la": depth})
    base = {"schema": BENCH_SCHEMA, "chip": CHIP}
    print(json.dumps({**base, "metric": f"summa_lookahead_speedup_n{n}",
                      "value": round(gf[la] / max(gf[0], 1e-9), 3),
                      "unit": "x", "la": la, "grid": f"{p}x{q}"}),
          flush=True)
    mtl = A.storage.data.shape[0] // p
    ntl = C.storage.data.shape[1] // q
    ov = _overlap_probe(g, mtl, ntl, nb, "gemm", both_axes=True)
    print(json.dumps({**base,
                      "metric": f"summa_lookahead_overlap_pct_n{n}",
                      "value": round(float(ov), 1), "unit": "%",
                      "grid": f"{p}x{q}", "nb": nb}), flush=True)


def bench_dist_chol_lookahead(n, nb, iters):
    """Lookahead-pipelined distributed Cholesky (PERF r15): same
    depth-0-vs-tuned pair as bench_summa_lookahead for dist_potrf —
    here the lookahead additionally pulls the NEXT panel's column
    factor forward, so the critical path drops by the panel latency,
    not just the broadcast."""
    from slate_tpu.parallel.dist_chol import dist_potrf
    from slate_tpu.tune import lookahead_depth

    g = _lookahead_grid()
    p, q = g.p, g.q
    rng = np.random.default_rng(16)
    # SPD without an O(n^3) host product (bench_posv idiom)
    a0 = rng.standard_normal((n, n)).astype(np.float32)
    a = (a0 + a0.T) * 0.001 + np.eye(n, dtype=np.float32) * 4.0
    H = st.HermitianMatrix.from_numpy(a, nb, st.Uplo.Lower, g)
    stg = H.storage
    la = max(1, lookahead_depth(n, "float32"))

    flops = _flops.op_flops("potrf", [(n, n)])
    gf = {}
    for depth in (0, la):
        def body(carry, data, depth=depth):
            out = dist_potrf(data * (1.0 + carry), stg.Nt, g, stg.n,
                             abft=False, la=depth)
            return out[0][0, 0, 0, 0] * 1e-24
        timed = _time_chain(body, jnp.float32(0.0), (stg.data,), iters,
                            flops)
        gf[depth] = timed[0]
        _emit(f"dist_chol_lookahead_d{depth}_n{n}_gflops", timed, flops,
              {"nb": nb, "grid": f"{p}x{q}", "la": depth})
    base = {"schema": BENCH_SCHEMA, "chip": CHIP}
    print(json.dumps({**base,
                      "metric": f"dist_chol_lookahead_speedup_n{n}",
                      "value": round(gf[la] / max(gf[0], 1e-9), 3),
                      "unit": "x", "la": la, "grid": f"{p}x{q}"}),
          flush=True)
    mtl = stg.data.shape[0] // p
    ntl = stg.data.shape[1] // q
    ov = _overlap_probe(g, mtl, ntl, nb, "potrf", both_axes=False)
    print(json.dumps({**base,
                      "metric": f"dist_chol_lookahead_overlap_pct_n{n}",
                      "value": round(float(ov), 1), "unit": "%",
                      "grid": f"{p}x{q}", "nb": nb}), flush=True)


def bench_serve_mixed(problems, nrhs, reps, sizes):
    """Serving throughput (PR 10): a fixed seeded mixed workload — three
    ops round-robin over ``sizes`` — through serve.Server.  The first
    pass compiles every bucket executable (the "compile" phase the
    watchdog may preempt); the timed passes are pure cache hits, so the
    problems/s number is steady-state serving throughput.  Padding
    waste is the workload-weighted mean of the per-batch obs events.
    Emits its own lines: _emit hardcodes the GFLOP/s unit and these
    metrics are problems/s and %."""
    from slate_tpu import obs, serve

    rng = np.random.default_rng(10)
    ops = ("solve", "chol_solve", "least_squares_solve")
    reqs = []
    for i in range(problems):
        n = int(sizes[i % len(sizes)])
        op = ops[i % len(ops)]
        dt = np.float32
        if op == "least_squares_solve":
            a = rng.standard_normal((n + 8, n)).astype(dt)
            b = rng.standard_normal((n + 8, nrhs)).astype(dt)
        else:
            a = rng.standard_normal((n, n)).astype(dt)
            if op == "chol_solve":
                a = (a @ a.T / n + np.eye(n, dtype=dt)).astype(dt)
            else:
                a = a + np.eye(n, dtype=dt) * 4.0
            b = rng.standard_normal((n, nrhs)).astype(dt)
        reqs.append((op, a, b))

    srv = serve.Server(cache=serve.ExecutableCache())
    _PROGRESS["phase"] = "compile"
    with obs.recording() as warm_events:
        srv.serve_batch(reqs)              # compiles every bucket
    _PROGRESS["phase"] = "run"
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        srv.serve_batch(reqs)
        times.append(time.perf_counter() - t0)
    pps = problems / min(times)
    ev = [e for e in warm_events if e.get("kind") == "serve_batch"]
    waste = (sum(e["padding_waste"] * e["problems"] for e in ev)
             / max(sum(e["problems"] for e in ev), 1))
    base = {"schema": BENCH_SCHEMA, "chip": CHIP}
    print(json.dumps({**base, "metric": "serve_mixed_problems_per_s",
                      "value": round(float(pps), 2), "unit": "problems/s",
                      "n": problems}), flush=True)
    print(json.dumps({**base, "metric": "serve_mixed_padding_waste_pct",
                      "value": round(100.0 * float(waste), 2),
                      "unit": "%", "n": problems}), flush=True)


def bench_serve_ragged(problems, nrhs, reps, bucket):
    """Ragged vs vmapped-XLA serving cores (PERF r11): one seeded
    mixed-size workload (sizes spanning 1 .. the full bucket) through
    two Servers on a single-rung ladder — one with Pallas plans
    overridden onto the batch_* ops so `tune.resolve_plan` routes the
    fast rung through the ragged batched kernels, one resolving the
    default XLA plans (vmapped full-bucket route).  Reports raw and
    padding-waste-adjusted problems/s per route — adjusted = raw /
    (1 - waste), throughput per unit of LIVE work, the number the
    ragged grids improve — plus the raw ragged/xla speedup.  Emits its
    own lines: these metrics are problems/s, % and x, not GFLOP/s."""
    import contextlib

    from slate_tpu import obs, serve, tune
    from slate_tpu.serve import bucket as _bucket
    from slate_tpu.tune import TilePlan

    rng = np.random.default_rng(11)
    ops = ("solve", "chol_solve", "least_squares_solve")
    szs = (1, max(bucket // 3, 1), max(bucket - 17, 1), bucket)
    reqs = []
    for i in range(problems):
        n = int(szs[i % len(szs)])
        op = ops[i % len(ops)]
        dt = np.float32
        a = rng.standard_normal((n, n)).astype(dt)
        if op == "chol_solve":
            a = (a @ a.T / n + np.eye(n, dtype=dt)).astype(dt)
        elif op == "solve":
            a = a + np.eye(n, dtype=dt) * 4.0
        # least squares keeps m = n so all three ops share the single
        # bucket (mb = bucket_for(m + nb - n) = the one rung)
        b = rng.standard_normal((n, nrhs)).astype(dt)
        reqs.append((op, a, b))

    ladder = _bucket.BucketLadder((int(bucket),), "tuned")
    plan = TilePlan("pallas", min(128, int(bucket)), 8)
    stats = {}
    for route in ("ragged", "xla"):
        srv = serve.Server(ladder=ladder, cache=serve.ExecutableCache())
        _PROGRESS["phase"] = f"compile:{route}"
        with contextlib.ExitStack() as stack:
            if route == "ragged":
                for bop in ("batch_potrf", "batch_getrf", "batch_geqrf"):
                    stack.enter_context(tune.plan_override(bop, plan))
            with obs.recording() as warm_events:
                srv.serve_batch(reqs)      # compiles every bucket
        _PROGRESS["phase"] = f"run:{route}"
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            srv.serve_batch(reqs)
            times.append(time.perf_counter() - t0)
        ev = [e for e in warm_events if e.get("kind") == "serve_batch"]
        waste = (sum(e["padding_waste"] * e["problems"] for e in ev)
                 / max(sum(e["problems"] for e in ev), 1))
        stats[route] = (problems / min(times), float(waste))

    base = {"schema": BENCH_SCHEMA, "chip": CHIP}
    print(json.dumps({**base, "metric": "serve_ragged_padding_waste_pct",
                      "value": round(100.0 * stats["ragged"][1], 2),
                      "unit": "%", "n": problems}), flush=True)
    for route, (raw, waste) in stats.items():
        print(json.dumps({
            **base, "metric": f"serve_ragged_{route}_problems_per_s",
            "value": round(float(raw), 2), "unit": "problems/s",
            "n": problems}), flush=True)
        print(json.dumps({
            **base,
            "metric": f"serve_ragged_{route}_adjusted_problems_per_s",
            "value": round(float(raw / max(1.0 - waste, 1e-9)), 2),
            "unit": "problems/s", "n": problems}), flush=True)
    print(json.dumps({**base, "metric": "serve_ragged_speedup",
                      "value": round(stats["ragged"][0]
                                     / max(stats["xla"][0], 1e-9), 3),
                      "unit": "x", "n": problems}), flush=True)


def bench_serve_bf16(problems, nrhs, reps, bucket):
    """Certified bf16 serving rung vs the f32-only route (PERF r18): one
    seeded mixed workload (three ops, sizes spanning the bucket, all
    f32 requests) through two Servers on a single-rung ladder — one with
    ``Option.Precision = bf16`` (the certified low-precision rung below
    the f32 ladder, serve/batched.py) and one f32-only.  Reports raw and
    padding-waste-adjusted problems/s for BOTH routes, the certificate
    accept-rate over live slots (accepted = not escalated; escalations
    land on results bit-identical to the f32 route), and the bf16/f32
    speedup.  On CPU the rung computes both the bf16 attempt and its f32
    escalation target, so the speedup reads BELOW 1 there — the honest
    number; the >= 1.6x target is a TPU goal (docs/PERF.md round 18).
    Emits its own lines: problems/s, % and x, not GFLOP/s."""
    from slate_tpu import Option, Precision, obs, serve
    from slate_tpu.serve import bucket as _bucket

    rng = np.random.default_rng(18)
    ops = ("solve", "chol_solve", "least_squares_solve")
    szs = (max(bucket // 4, 1), max(bucket // 2, 1), max(bucket - 9, 1),
           bucket)
    reqs = []
    for i in range(problems):
        n = int(szs[i % len(szs)])
        op = ops[i % len(ops)]
        dt = np.float32
        a = rng.standard_normal((n, n)).astype(dt)
        if op == "chol_solve":
            a = (a @ a.T / n + np.eye(n, dtype=dt)).astype(dt)
        elif op == "solve":
            a = a + np.eye(n, dtype=dt) * 4.0
        # least squares keeps m = n so all three ops share the one rung
        b = rng.standard_normal((n, nrhs)).astype(dt)
        reqs.append((op, a, b))

    ladder = _bucket.BucketLadder((int(bucket),), "tuned")
    opts_by_route = {"bf16": {Option.Precision: Precision.Bf16},
                     "f32": None}
    stats, accept = {}, None
    for route, opts in opts_by_route.items():
        srv = serve.Server(opts=opts, ladder=ladder,
                           cache=serve.ExecutableCache())
        _PROGRESS["phase"] = f"compile:{route}"
        with obs.recording() as warm_events:
            srv.serve_batch(reqs)          # compiles every bucket
        _PROGRESS["phase"] = f"run:{route}"
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            srv.serve_batch(reqs)
            times.append(time.perf_counter() - t0)
        ev = [e for e in warm_events if e.get("kind") == "serve_batch"]
        waste = (sum(e["padding_waste"] * e["problems"] for e in ev)
                 / max(sum(e["problems"] for e in ev), 1))
        stats[route] = (problems / min(times), float(waste))
        if route == "bf16":
            live = max(sum(e["problems"] for e in ev), 1)
            esc = sum(e["escalated"] for e in ev)
            accept = 1.0 - esc / live

    base = {"schema": BENCH_SCHEMA, "chip": CHIP}
    for route, (raw, waste) in stats.items():
        print(json.dumps({
            **base, "metric": f"serve_precision_{route}_problems_per_s",
            "value": round(float(raw), 2), "unit": "problems/s",
            "n": problems}), flush=True)
        print(json.dumps({
            **base,
            "metric": f"serve_precision_{route}_adjusted_problems_per_s",
            "value": round(float(raw / max(1.0 - waste, 1e-9)), 2),
            "unit": "problems/s", "n": problems}), flush=True)
    print(json.dumps({**base, "metric": "serve_precision_accept_rate_pct",
                      "value": round(100.0 * float(accept), 2),
                      "unit": "%", "n": problems}), flush=True)
    print(json.dumps({**base, "metric": "serve_precision_bf16_speedup",
                      "value": round(stats["bf16"][0]
                                     / max(stats["f32"][0], 1e-9), 3),
                      "unit": "x", "n": problems}), flush=True)


def bench_serve_survival(problems, rate_hz, nrhs, sizes, budget_ms):
    """Survival-layer throughput (robustness PR): a seeded Poisson
    arrival stream (robust.faults.poisson_workload) replayed against a
    LIVE Server — background flush loop, deadline-aware admission,
    shed_oldest overflow, SLO governor — instead of the offline
    serve_batch path the other serve benches time.  Reports admitted
    problems/s over the replay wall time, delivered p99 latency, the
    shed and quarantine rates per 1k, and an ``slo_pass`` verdict from
    slo.evaluate over the recorded event stream (p99 must hold the
    declared budget for what the server chose to ADMIT — shedding is
    how it keeps that promise under overload).  Emits its own lines:
    these metrics are problems/s, ms and per-1k rates, not GFLOP/s."""
    from slate_tpu import obs, serve
    from slate_tpu.obs import slo as _slo
    from slate_tpu.robust import faults as _faults

    work = _faults.poisson_workload(16, problems, rate_hz, sizes,
                                    nrhs=nrhs)
    cfg = serve.AdmissionConfig(
        max_queue=max(problems // 4, 8), overflow="shed_oldest",
        flush_occupancy=max(problems // 8, 4), max_batch_delay_ms=10.0,
        slo_budget_ms=float(budget_ms), watchdog_timeout_s=120.0)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    _PROGRESS["phase"] = "compile"
    srv.serve_batch([(op, a, b) for _, op, a, b in work])  # warm buckets
    _PROGRESS["phase"] = "run"
    srv.start()
    tickets, shed = [], 0
    t0 = time.perf_counter()
    with obs.recording() as events:
        for t_arr, op, a, b in work:
            lag = t_arr - (time.perf_counter() - t0)
            if lag > 0:
                time.sleep(lag)
            try:
                tickets.append(srv.submit(op, a, b))
            except Exception:          # typed shed/overflow: counted
                shed += 1
        for tk in tickets:
            try:
                tk.result(timeout=60.0)
            except Exception:
                shed += 1
        wall = time.perf_counter() - t0
        srv.shutdown()
    stats = _slo.aggregate(list(events))
    union = stats.get("*", {})
    verdicts = _slo.evaluate(stats, {"*": {"latency_p99_ms": budget_ms}})
    served = union.get("problems", 0)
    base = {"schema": BENCH_SCHEMA, "chip": CHIP}
    print(json.dumps({**base, "metric": "serve_survival_problems_per_s",
                      "value": round(served / max(wall, 1e-9), 2),
                      "unit": "problems/s", "n": problems}), flush=True)
    print(json.dumps({**base, "metric": "serve_survival_latency_p99_ms",
                      "value": union.get("latency_p99_ms"),
                      "unit": "ms", "n": problems}), flush=True)
    print(json.dumps({**base, "metric": "serve_survival_shed_per_1k",
                      "value": round(1000.0 * shed
                                     / max(problems, 1), 2),
                      "unit": "per_1k", "n": problems}), flush=True)
    print(json.dumps({**base, "metric": "serve_survival_quar_per_1k",
                      "value": union.get("quar_per_1k", 0.0),
                      "unit": "per_1k", "n": problems}), flush=True)
    print(json.dumps({**base, "metric": "serve_survival_slo_pass",
                      "value": int(all(v["ok"] for v in verdicts)),
                      "unit": "bool", "n": problems}), flush=True)


def bench_serve_pool(problems, rate_hz, nrhs, sizes, members):
    """Elastic device pool (robustness PR): the same seeded Poisson
    mixed-size stream replayed against a 1-member server and a
    ``members``-wide DevicePool server, with a transient device kill and
    online retuning live on the pool run.  Reports the pool's admitted
    problems/s and its scaling over one device (on a single-chip host
    the members share the device, so ~1.0x is the honest answer — the
    line exists to price the pool machinery, not to fake speedup), the
    failover recovery wall (failover record -> the survivor's completed
    redispatch), and the retune hot-swap count.  Emits its own lines:
    problems/s, x, ms and a count, not GFLOP/s."""
    from slate_tpu import obs, serve
    from slate_tpu.robust import faults as _faults

    def replay(srv, plans=()):
        work = _faults.poisson_workload(16, problems, rate_hz, sizes,
                                        nrhs=nrhs)
        srv.serve_batch([(op, a, b) for _, op, a, b in work])  # warm
        srv.start()
        t0 = time.perf_counter()
        with obs.recording() as events:
            with _faults.inject(*plans):
                tickets = []
                for t_arr, op, a, b in work:
                    lag = t_arr - (time.perf_counter() - t0)
                    if lag > 0:
                        time.sleep(lag)
                    tickets.append(srv.submit(op, a, b))
                done = sum(tk.result(timeout=120.0) is not None
                           for tk in tickets)
            wall = time.perf_counter() - t0
            srv.shutdown()
        return done / max(wall, 1e-9), list(events)

    cfg = dict(max_queue=max(problems, 8),
               flush_occupancy=max(problems // 8, 4),
               max_batch_delay_ms=10.0, watchdog_timeout_s=120.0)
    _PROGRESS["phase"] = "compile"
    one, _ = replay(serve.Server(
        cache=serve.ExecutableCache(),
        admission=serve.AdmissionConfig(**cfg)))
    _PROGRESS["phase"] = "run"
    devs = jax.local_devices()
    devs = (devs * members)[:members] if len(devs) < members \
        else devs[:members]
    pool = serve.DevicePool(devs, serve.PoolConfig(strike_limit=1))
    srv = serve.Server(
        cache=serve.ExecutableCache(), pool=pool,
        admission=serve.AdmissionConfig(
            **cfg, retune_interval_s=0.25, retune_min_samples=32))
    kill = _faults.FaultPlan("serve_device_fail", transient=True,
                             device=0)
    rate, events = replay(srv, plans=(kill,))
    fo = [e for e in events if e.get("kind") == "serve_device"
          and e.get("event") == "failover"]
    recovery = None
    if fo:
        after = [e["ts"] for e in events if e.get("kind") == "serve_batch"
                 and e["ts"] >= fo[0]["ts"]]
        if after:
            recovery = round(1e3 * (after[0] - fo[0]["ts"]), 2)
    swaps = sum(1 for e in events if e.get("kind") == "serve_retune")
    base = {"schema": BENCH_SCHEMA, "chip": CHIP}
    print(json.dumps({**base, "metric": "serve_pool_problems_per_s",
                      "value": round(rate, 2),
                      "unit": "problems/s", "n": problems}), flush=True)
    print(json.dumps({**base, "metric": "serve_pool_scaling",
                      "value": round(rate / max(one, 1e-9), 3),
                      "unit": "x", "n": members}), flush=True)
    print(json.dumps({**base, "metric": "serve_pool_failover_recovery_ms",
                      "value": recovery, "unit": "ms",
                      "n": problems}), flush=True)
    print(json.dumps({**base, "metric": "serve_pool_retune_swaps",
                      "value": swaps, "unit": "count",
                      "n": problems}), flush=True)


def bench_potrf_ooc(n, nb, iters):
    """Out-of-core Cholesky throughput (durability PR): the host-resident
    TileMap streaming path — every panel round-trips host<->device with
    the next left panel prefetched behind the trailing update — against
    the in-core potrf at the same size, so the line prices what the
    host-offload axis costs.  Emits its own lines: the absolute GFLOP/s
    of the streaming factorization and its slowdown vs in-core."""
    rng = np.random.default_rng(0)
    a = rng.standard_normal((n, n)).astype(np.float32)
    spd = a @ a.T + n * np.eye(n, dtype=np.float32)
    flops = n ** 3 / 3.0
    _PROGRESS["phase"] = "compile"
    st.potrf_ooc(spd, nb=nb)                    # compile + warmup
    A = st.SymmetricMatrix(TileStorage.from_dense(spd, nb, nb),
                           uplo=st.Uplo.Lower)
    st.potrf(A)
    _PROGRESS["phase"] = "run"
    t_ooc = min(_walltime(lambda: st.potrf_ooc(spd, nb=nb))
                for _ in range(iters))
    t_inc = min(_walltime(lambda: np.asarray(st.potrf(A).to_dense()))
                for _ in range(iters))
    base = {"schema": BENCH_SCHEMA, "chip": CHIP}
    print(json.dumps({**base, "metric": "durability_potrf_ooc_gflops",
                      "value": round(flops / t_ooc / 1e9, 2),
                      "unit": "GFLOP/s", "n": n}), flush=True)
    print(json.dumps({**base, "metric": "durability_potrf_ooc_slowdown",
                      "value": round(t_ooc / max(t_inc, 1e-9), 3),
                      "unit": "x", "n": n}), flush=True)


def bench_checkpoint_overhead(n, nb, iters):
    """Panel-boundary checkpoint cost (durability PR): the same
    out-of-core Cholesky with a CheckpointManager snapshotting at EVERY
    panel step (the worst-case cadence) vs checkpointing off.  Reports
    the relative overhead and the per-snapshot wall cost — the number a
    user trades against their preemption rate when picking ``every``."""
    import shutil
    import tempfile
    from slate_tpu.robust import CheckpointManager

    rng = np.random.default_rng(0)
    a = rng.standard_normal((n, n)).astype(np.float32)
    spd = a @ a.T + n * np.eye(n, dtype=np.float32)
    nsteps = -(-n // nb)
    _PROGRESS["phase"] = "compile"
    st.potrf_ooc(spd, nb=nb)                    # compile + warmup
    _PROGRESS["phase"] = "run"
    t_off = min(_walltime(lambda: st.potrf_ooc(spd, nb=nb))
                for _ in range(iters))
    t_on = []
    for _ in range(iters):
        d = tempfile.mkdtemp(prefix="slate_bench_ckpt_")
        try:
            cm = CheckpointManager(d, every=1)
            t_on.append(_walltime(
                lambda: st.potrf_ooc(spd, nb=nb, checkpoint=cm)))
        finally:
            shutil.rmtree(d, ignore_errors=True)
    t_on = min(t_on)
    base = {"schema": BENCH_SCHEMA, "chip": CHIP}
    print(json.dumps({**base, "metric": "durability_ckpt_overhead_pct",
                      "value": round(100.0 * (t_on - t_off)
                                     / max(t_off, 1e-9), 2),
                      "unit": "%", "n": n}), flush=True)
    print(json.dumps({**base, "metric": "durability_ckpt_save_ms",
                      "value": round(1e3 * (t_on - t_off)
                                     / max(nsteps, 1), 3),
                      "unit": "ms", "n": n}), flush=True)


def _walltime(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


QUICK_STEPS = [
    (bench_gemm, dict(n=512, nb=128, iters=4)),
    (bench_posv, dict(n=768, nb=128, nrhs=64, iters=2)),
    (bench_gesv, dict(n=768, nb=128, nrhs=64, iters=2)),
    (bench_gesv_rbt, dict(n=768, nb=128, nrhs=64, iters=2)),
    (bench_gesv_abft, dict(n=768, nb=128, nrhs=64, iters=2)),
    (bench_posv_abft, dict(n=768, nb=128, nrhs=64, iters=2)),
    (bench_geqrf, dict(m=4096, n=256, nb=128, iters=2)),
    (bench_gels, dict(m=4096, n=256, nb=128, nrhs=16, iters=2)),
    (bench_heev, dict(n=512, nb=128, iters=2)),
    (bench_svd, dict(n=512, nb=128, iters=2)),
    (bench_potrf_fused, dict(n=256, nb=128, bw=8, iters=2)),
    (bench_geqrf_panel, dict(m=512, n=128, iters=2)),
    (bench_summa_lookahead, dict(n=512, nb=128, iters=2)),
    (bench_dist_chol_lookahead, dict(n=768, nb=128, iters=2)),
    (bench_serve_mixed, dict(problems=24, nrhs=4, reps=2,
                             sizes=(24, 48, 96))),
    (bench_serve_ragged, dict(problems=12, nrhs=4, reps=2, bucket=32)),
    (bench_serve_bf16, dict(problems=12, nrhs=4, reps=2, bucket=32)),
    (bench_serve_survival, dict(problems=24, rate_hz=400.0, nrhs=4,
                                sizes=(24, 48), budget_ms=5000.0)),
    (bench_serve_pool, dict(problems=24, rate_hz=400.0, nrhs=4,
                            sizes=(40, 96), members=2)),
    (bench_potrf_ooc, dict(n=192, nb=64, iters=2)),
    (bench_checkpoint_overhead, dict(n=192, nb=64, iters=2)),
]

FULL_STEPS = [
    (bench_gemm, dict(n=4096, nb=256, iters=50)),
    (bench_gemm, dict(n=8192, nb=512, iters=20)),
    (bench_gemm, dict(n=16384, nb=1024, iters=8)),
    (bench_posv, dict(n=16384, nb=512, nrhs=256, iters=5)),
    (bench_gesv, dict(n=16384, nb=512, nrhs=256, iters=4)),
    (bench_gesv_rbt, dict(n=16384, nb=512, nrhs=256, iters=4)),
    (bench_gesv_abft, dict(n=16384, nb=512, nrhs=256, iters=3)),
    (bench_posv_abft, dict(n=16384, nb=512, nrhs=256, iters=3)),
    (bench_geqrf, dict(m=131072, n=1024, nb=256, iters=4)),
    (bench_gels, dict(m=131072, n=1024, nb=256, nrhs=64, iters=4)),
    (bench_heev, dict(n=4096, nb=256, iters=3)),
    (bench_svd, dict(n=2048, nb=256, iters=3)),
    (bench_potrf_fused, dict(n=4096, nb=256, bw=8, iters=10)),
    (bench_geqrf_panel, dict(m=8192, n=256, iters=10)),
    (bench_summa_lookahead, dict(n=8192, nb=256, iters=8)),
    (bench_dist_chol_lookahead, dict(n=16384, nb=512, iters=3)),
    (bench_serve_mixed, dict(problems=96, nrhs=16, reps=3,
                             sizes=(48, 96, 160, 320))),
    (bench_serve_ragged, dict(problems=48, nrhs=16, reps=3, bucket=256)),
    (bench_serve_bf16, dict(problems=48, nrhs=16, reps=3, bucket=256)),
    (bench_serve_survival, dict(problems=192, rate_hz=800.0, nrhs=16,
                                sizes=(48, 96, 160), budget_ms=2000.0)),
    (bench_serve_pool, dict(problems=192, rate_hz=800.0, nrhs=16,
                            sizes=(96, 160, 320), members=4)),
    (bench_potrf_ooc, dict(n=4096, nb=512, iters=3)),
    (bench_checkpoint_overhead, dict(n=4096, nb=512, iters=3)),
]


class _BudgetExceeded(Exception):
    """Raised by the SIGALRM handler when a metric overruns the pool."""


def _skip_line(fn, reason, phase=None, elapsed_s=None):
    line = {
        "schema": BENCH_SCHEMA,
        "metric": f"{fn.__name__}_skipped", "value": None,
        "unit": "GFLOP/s", "vs_baseline": None,
        "skipped": True, "reason": reason, "chip": CHIP,
    }
    if phase is not None:
        line["phase"] = phase
    if elapsed_s is not None:
        line["elapsed_s"] = round(float(elapsed_s), 1)
    print(json.dumps(line), flush=True)


# Test seam: the watchdog's hard exit.  os._exit (not sys.exit) because the
# whole point is escaping a thread blocked inside a C++ compile that Python
# exceptions and SIGALRM cannot reach (the BENCH r05 rc=124 stall).
_EXIT = os._exit
_WATCHDOG_GRACE_S = 10.0


def _install_watchdog(steps, deadline, done, exit_fn=None):
    """Arm a daemon thread that hard-exits 1 just past ``deadline``.

    SIGALRM preemption (below) only works when the main thread is running
    Python bytecode; the r05 rc=124 came from a metric stuck inside a
    blocking C++ compile, where the alarm is queued but never delivered.
    The watchdog runs on its own thread, so it fires regardless: it emits
    a "skipped" line for every step index not yet in ``done`` (index, not
    fn — FULL_STEPS repeats bench_gemm) and then exits 1 — a cut run is
    a failed run — before the external ``timeout`` can return 124.

    Returns a threading.Event; set() it to stand the watchdog down.
    """
    stop = threading.Event()
    grace_deadline = deadline + _WATCHDOG_GRACE_S

    def _watch():
        while not stop.is_set():
            remaining = grace_deadline - time.monotonic()
            if remaining <= 0:
                break
            stop.wait(min(remaining, 1.0))
        if stop.is_set():
            return
        for idx, (fn, _) in enumerate(steps):
            if idx not in done:
                if idx == _PROGRESS["idx"] and _PROGRESS["t0"] is not None:
                    _skip_line(fn, "time budget exceeded (watchdog)",
                               phase=_PROGRESS["phase"],
                               elapsed_s=time.monotonic() - _PROGRESS["t0"])
                else:
                    _skip_line(fn, "time budget exceeded (watchdog)")
        (exit_fn or _EXIT)(1)

    threading.Thread(target=_watch, name="bench-watchdog",
                     daemon=True).start()
    return stop


def _run_isolated(steps, budget_s=None, done=None, deadline=None):
    """Run each benchmark in isolation: one failing step must still let
    every other metric emit.

    ``budget_s`` (SLATE_BENCH_BUDGET_S) grants the run a pool of
    budget_s * len(steps) seconds.  A metric facing an exhausted pool is
    skipped up front; one that overruns the pool mid-flight is preempted
    by SIGALRM (main thread only — signals cannot interrupt other
    threads).  Either way the metric emits an explicit "skipped" JSON
    line, so the output always has one line per step and the r05 timeout
    (rc=124, zero lines after the stall) cannot recur.

    ``done``/``deadline`` let main() share progress with the watchdog
    thread (_install_watchdog): completed step INDICES are added to
    ``done`` so a watchdog firing mid-run only skip-reports the metrics
    that have not emitted yet.

    Returns how many steps errored or were cut (skipped)."""
    failures = 0
    can_alarm = (budget_s and hasattr(signal, "setitimer")
                 and threading.current_thread() is threading.main_thread())
    if deadline is None:
        deadline = (time.monotonic() + budget_s * len(steps)
                    if budget_s else None)
    for idx, (fn, kwargs) in enumerate(steps):
        if deadline is not None:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                _skip_line(fn, "time budget exhausted")
                failures += 1
                if done is not None:
                    done.add(idx)
                continue
        if can_alarm:
            def _on_alarm(signum, frame):
                raise _BudgetExceeded
            prev = signal.signal(signal.SIGALRM, _on_alarm)
            signal.setitimer(signal.ITIMER_REAL, remaining)
        _PROGRESS.update(idx=idx, phase="compile", t0=time.monotonic())
        try:
            fn(**kwargs)
        except _BudgetExceeded:
            _skip_line(fn, "time budget exceeded (preempted)",
                       phase=_PROGRESS["phase"],
                       elapsed_s=time.monotonic() - _PROGRESS["t0"])
            failures += 1
        except Exception as exc:  # noqa: BLE001 — isolate, report, continue
            failures += 1
            print(json.dumps({
                "schema": BENCH_SCHEMA,
                "metric": f"{fn.__name__}_error", "value": None,
                "unit": "GFLOP/s", "vs_baseline": None, "chip": CHIP,
                "error": f"{type(exc).__name__}: {exc}"[:300],
            }), flush=True)
        finally:
            if done is not None:
                done.add(idx)
            if can_alarm:
                signal.setitimer(signal.ITIMER_REAL, 0)
                signal.signal(signal.SIGALRM, prev)
    return failures


def sweep_nb():
    """Emit one JSON line per candidate (kernel, nb, bw) plan per op —
    the autotuner's raw search space (slate_tpu.tune.autotune.sweep), so
    BENCH rounds record what the tuner saw, not just the winner.
    Returns how many ops errored."""
    from slate_tpu.tune import autotune, chip_kind

    chip = chip_kind()
    sizes = {
        "potrf_tile": 256 if QUICK else 512,
        "potrf_panel": 512 if QUICK else 2048,
        "getrf_panel": 512 if QUICK else 2048,
        "lu_select": 512 if QUICK else 2048,
        "geqrf_panel": 512 if QUICK else 8192,
        "batch_potrf": 128 if QUICK else 256,
        "batch_getrf": 128 if QUICK else 256,
        "batch_geqrf": 128 if QUICK else 256,
    }
    iters = 1 if QUICK else 3
    failures = 0
    from slate_tpu.tune import OPS
    for op in OPS:
        n = sizes[op]
        try:
            for plan, gflops in autotune.sweep(op, n, "float32",
                                               iters=iters):
                print(json.dumps({
                    "schema": BENCH_SCHEMA,
                    "metric": f"sweep_{op}_n{n}", "op": op, "n": n,
                    "kernel": plan.kernel, "nb": plan.nb, "bw": plan.bw,
                    "value": round(float(gflops), 1), "unit": "GFLOP/s",
                    "chip": chip,
                }), flush=True)
        except Exception as exc:  # noqa: BLE001 — isolate, report, continue
            failures += 1
            print(json.dumps({
                "schema": BENCH_SCHEMA,
                "metric": f"sweep_{op}_n{n}_error", "value": None,
                "unit": "GFLOP/s", "vs_baseline": None, "chip": chip,
                "error": f"{type(exc).__name__}: {exc}"[:300],
            }), flush=True)
    return failures


def main(argv=()):
    """Run every step in isolation and return 1 if any step errored or
    was cut, else 0.  A failing step still lets every later metric emit
    its line (their JSON lines carry "error"/"skipped"); the exit code
    then says the run is incomplete.

    The watchdog is armed BEFORE the first device contact (_chip_peak,
    i.e. before any compile can block), so even a stall inside the very
    first compilation self-terminates with rc=1 and explicit skip lines
    instead of tripping the external timeout's rc=124."""
    global PEAK, CHIP
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--sweep-nb", action="store_true",
                        help="emit one line per candidate autotuner plan "
                             "instead of the headline metrics")
    args = parser.parse_args(list(argv))

    steps = [] if args.sweep_nb else (QUICK_STEPS if QUICK else FULL_STEPS)
    done, stop = set(), None
    if BUDGET_S:
        deadline = time.monotonic() + BUDGET_S * max(len(steps), 1)
        stop = _install_watchdog(steps, deadline, done)
    else:
        deadline = None

    try:
        PEAK, CHIP = _chip_peak()
        if args.sweep_nb:
            failures = sweep_nb()
        else:
            failures = _run_isolated(steps, budget_s=BUDGET_S or None,
                                     done=done, deadline=deadline)
    finally:
        if stop is not None:
            stop.set()
    return 1 if failures else 0


if __name__ == "__main__":
    import sys
    use_compile_cache()
    sys.exit(main(sys.argv[1:]))
