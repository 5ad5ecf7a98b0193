#!/usr/bin/env python3
"""Chip smoke: drive slate_tpu's public solver path once on a TPU.

``python chip_smoke.py`` runs, in ONE process on one chip, the f32 phases
below at bench.py's FULL sizes (BASELINE configs 1-5 cut to what one
16 GB v5e chip holds) and holds every answer to the library's own
acceptance test (robust/certify.py):

  gemm    n=16384, nb=1024: three 1 GiB operands
  posv    n=16384, nb=512, nrhs=256
  gesv    n=16384, nb=512, nrhs=256, partial pivoting
  gels    131072 x 1024, nb=256, nrhs=64
  heev    n=2048, values only — NOT bench.py's 4096, an open bring-up
          failure: XLA's eigh compile at 4096 filled the chip host's
          40 GiB (PERF.md "Findings" and "Open questions", PR 21)
  serve   96 mixed solve / chol_solve / least_squares_solve problems,
          sizes 48-320, through serve.Server: cold, then warm; every
          answer certified on the host
  pallas  posv, gesv (CALU) and gels (Householder) at sizes the Pallas
          seams admit, plus one ragged f32 and one ragged bf16 serve
          batch (well conditioned by construction), each with the
          Pallas plans forced (tune.plan_override) and compared with
          the XLA-plan answers

``python chip_smoke.py --chips 4`` runs only BASELINE config 2's 2D
block-cyclic path: gemm (SUMMA), posv (dist_chol) and gesv (dist_lu) at
n=20480, nb=512 on Grid(2, 2) over the four local chips, each compared
with the same call on Grid(1, 1) and with the host residual, and
partial-pivot gesv on a general matrix (row swaps across chips) held to
the host certificate; ``--only`` picks a subset of these.

Driver programs are traced first, compiled concurrently (each program's
own compile seconds are reported), then run one phase at a time.  Each
phase prints one JSON line; the last line is ``{"ok": true, "device":
...}``.  Nothing is caught: any failure exits non-zero before that line.
A host whose first device is not a TPU is an error, not a CPU run.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
EPS = float(np.finfo(np.float32).eps)
# XLA's default-precision f32 product rounds its inputs to bf16 on the
# TPU: an XLA-plan answer is good to ~kappa(A) * EPS_BF16 (PERF.md)
EPS_BF16 = 2.0 ** -7
HPL_BOUND = 16.0
SEED = 0

SIZES = {
    "gemm": dict(n=16384, nb=1024),
    "posv": dict(n=16384, nb=512, nrhs=256),
    "gesv": dict(n=16384, nb=512, nrhs=256),
    "gels": dict(m=131072, n=1024, nb=256, nrhs=64),
    "heev": dict(n=2048, nb=256),
    "serve": dict(problems=96, nrhs=16, sizes=(48, 96, 160, 320)),
    "pallas_posv": dict(n=4096, nb=256, nrhs=64),
    "pallas_gesv": dict(n=4096, nb=256, nrhs=64),
    "pallas_gels": dict(m=4096, n=1024, nb=256, nrhs=64),
    "pallas_serve": dict(problems=48, nrhs=16, bucket=256, nb=128),
    "mesh": dict(n=20480, nb=512, nrhs=256),
}
PALLAS_OPS = ("potrf_tile", "potrf_panel", "getrf_panel", "lu_select",
              "geqrf_panel")
BATCH_OPS = ("batch_potrf", "batch_getrf", "batch_geqrf")
MESH_PHASES = ("gemm", "posv", "gesv", "gesv_general")


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def progress(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


# --------------------------------------------------------------- set-up


def build_native() -> bool:
    """Build slate_tpu/_native.so from native/ before slate_tpu is
    imported; False where the machine has no compiler (numpy packing)."""
    if not (shutil.which("make") and shutil.which(os.environ.get("CXX",
                                                                 "g++"))):
        return False
    subprocess.run(["make", "-s", "-C", str(ROOT / "native")], check=True,
                   stdout=sys.stderr)
    return True


def device_info(jax) -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


# --------------------------------------------------------------- checks


def hpl_scaled(a, x, b) -> float:
    """Max over right-hand sides of ||b - A x||_inf / (||A||_inf
    ||x||_inf n eps) in f64 on the host (HPL's test, without its ||b||
    term); HPL passes a run below 16."""
    a64 = a.astype(np.float64)
    r = b.astype(np.float64) - a64 @ x.astype(np.float64)
    anorm = np.abs(a64).sum(axis=1).max()
    xcol = np.abs(x.astype(np.float64)).max(axis=0)
    return float((np.abs(r).max(axis=0) / (anorm * xcol * a.shape[1]
                                           * EPS)).max())


def certify(a, x, b) -> tuple[float, float, bool]:
    """certify_solve on the f64 host residual: (ratio, tolerance,
    accepted)."""
    from slate_tpu.robust import certify as cert
    a64 = a.astype(np.float64)
    r = b.astype(np.float64) - a64 @ x.astype(np.float64)
    tol = cert.tolerance(np.float32, x.shape[0])
    h = cert.certify_solve(float(np.linalg.norm(a64)), x, b, r, tol=tol)
    return float(h.growth), tol, bool(h.ok)


def certify_ls(a, x, b) -> tuple[float, float, bool]:
    """certify_lstsq on the f64 host normal-equations residual."""
    from slate_tpu.robust import certify as cert
    a64 = a.astype(np.float64)
    rn = a64.T @ (b.astype(np.float64) - a64 @ x.astype(np.float64))
    tol = cert.tolerance(np.float32, max(a.shape))
    h = cert.certify_lstsq(float(np.linalg.norm(a64)), x, b, rn, tol=tol)
    return float(h.growth), tol, bool(h.ok)


def solution(out):
    """The X of a driver result: gemm's C, gels' (X, h), gesv/posv's
    (factor, X, h)."""
    return out if not isinstance(out, tuple) else out[len(out) - 2]


def rel_diff(x, ref) -> float:
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(np.asarray(x, np.float64) - ref)
                 / np.linalg.norm(ref))


def peak_bytes(devices) -> list:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def pallas_kernels(compiled) -> int:
    """Mosaic kernels in a compiled program (0 means no Pallas ran)."""
    return compiled.as_text().count("tpu_custom_call")


# ----------------------------------------------------------------- jobs


class Job:
    """One jitted public-driver call: traced in ``lower``, compiled on a
    worker thread, run and checked in ``run``."""

    def __init__(self, name, fn, specs, make, check, shapes,
                 overrides=(), pallas=False):
        self.name, self.fn, self.specs = name, fn, specs
        self.make, self.check, self.shapes = make, check, shapes
        self.overrides, self.pallas = overrides, pallas
        self.lowered = self.compiled = self.event = None
        self.compile_s = None

    def lower(self, st, jax):
        import contextlib

        from slate_tpu import obs, tune
        with contextlib.ExitStack() as stack:
            for op, plan in self.overrides:
                stack.enter_context(tune.plan_override(op, plan))
            with obs.recording() as events:
                self.lowered = jax.jit(self.fn).lower(*self.specs)
        drv = [e for e in events if e["kind"] == "event"]
        require(len(drv) == 1, f"{self.name}: one driver event, got "
                f"{[e['op'] for e in drv]}")
        self.event = drv[0]

    def compile(self):
        t0 = time.perf_counter()
        self.compiled = self.lowered.compile()
        self.compile_s = time.perf_counter() - t0
        self.lowered = None
        progress(f"compiled {self.name} in {self.compile_s:.1f} s")

    def run(self, jax, rng):
        args, host = self.make(rng)
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            out = jax.block_until_ready(self.compiled(*args))
            times.append(time.perf_counter() - t0)
        del args
        ev = self.event
        path = ev["path"]
        require(ev["status"] == "ok" and path.split(":")[0] == "direct"
                and ev["escalations"] == 0,
                f"{self.name}: obs path {path!r}, "
                f"{ev['escalations']} escalations")
        fields = self.check(out, host)
        if self.pallas:
            k = pallas_kernels(self.compiled)
            require(k > 0, f"{self.name}: no Pallas kernel in the program")
            fields["pallas_kernels"] = k
        emit(self.name, shapes=self.shapes, compile_s=self.compile_s,
             run_s=min(times), path=path,
             escalations=ev["escalations"], **fields)
        self.compiled = None
        return out, host


def _spec(st, jax, m, n, nb, grid):
    """Abstract Matrix with the tile layout Matrix.from_numpy builds."""
    from slate_tpu.core.storage import TileStorage
    mt, nt = -(-m // nb), -(-n // nb)
    rows, cols = -(-mt // grid.p) * grid.p, -(-nt // grid.q) * grid.q
    data = jax.ShapeDtypeStruct((rows, cols, nb, nb),
                                np.float32, sharding=grid.tile_sharding())
    return st.Matrix(TileStorage(data, m, n, nb, nb, grid),
                     kind=st.TileKind.UserOwned)


def _health_ok(h, what):
    require(bool(np.asarray(h.ok)), f"{what}: driver health not ok "
            f"({h.describe()})")


def solve_fields(a, x, b, h, name):
    _health_ok(h, name)
    ratio, tol, ok = certify(a, x, b)
    require(ok, f"{name}: certify_solve ratio {ratio} > tol {tol}")
    hpl = hpl_scaled(a, x, b)
    return dict(resid=hpl, hpl_bound=HPL_BOUND, cert=ratio, cert_tol=tol)


def spd(rng, n):
    a0 = rng.standard_normal((n, n), dtype=np.float32)
    a = (a0 + a0.T) * np.float32(0.001)
    a[np.diag_indices(n)] += np.float32(4.0)
    return a


def general(rng, n, shift=0.0):
    a = rng.standard_normal((n, n), dtype=np.float32)
    if shift:
        a[np.diag_indices(n)] += np.float32(shift)
    return a


def gemm_job(st, jax, grid, tag, n, nb):
    def make(rng):
        a = general(rng, n)
        b = general(rng, n)
        return ((st.Matrix.from_numpy(a, nb, grid=grid),
                 st.Matrix.from_numpy(b, nb, grid=grid)), (a, b))

    def check(out, host):
        a, b = host
        cols = np.linspace(0, n - 1, 64).astype(int)
        c = np.asarray(out.to_dense()[:, cols])
        x = b[:, cols]
        ratio, tol, ok = certify(a, x, c)
        require(ok, f"{tag}: certify_solve ratio {ratio} > tol {tol}")
        return dict(resid=hpl_scaled(a, x, c), hpl_bound=HPL_BOUND,
                    cert=ratio, cert_tol=tol, checked_cols=len(cols))

    spec = _spec(st, jax, n, n, nb, grid)
    return Job(tag, lambda A, B: st.gemm(1.0, A, B), (spec, spec), make,
               check, {"A": [n, n], "B": [n, n], "C": [n, n], "nb": nb})


def posv_job(st, jax, grid, tag, n, nb, nrhs, overrides=()):
    info = {st.Option.ErrorPolicy: st.ErrorPolicy.Info}

    def make(rng):
        a, b = spd(rng, n), rng.standard_normal((n, nrhs), np.float32)
        A = st.Matrix.from_numpy(a, nb, grid=grid).hermitian(st.Uplo.Lower)
        return (A, st.Matrix.from_numpy(b, nb, grid=grid)), (a, b)

    def check(out, host):
        _, X, h = out
        return solve_fields(host[0], np.asarray(X.to_dense()), host[1], h,
                            tag)

    A = _spec(st, jax, n, n, nb, grid).hermitian(st.Uplo.Lower)
    return Job(tag, lambda A, B: st.posv(A, B, info),
               (A, _spec(st, jax, n, nrhs, nb, grid)), make, check,
               {"A": [n, n], "B": [n, nrhs], "nb": nb},
               overrides, bool(overrides))


def gesv_job(st, jax, grid, tag, n, nb, nrhs, method, dominant=False,
             overrides=()):
    opts = {st.Option.ErrorPolicy: st.ErrorPolicy.Info,
            st.Option.MethodLU: method}

    def make(rng):
        # a dominant diagonal keeps the pivot order unambiguous where two
        # implementations are compared on it
        a = general(rng, n, 4.0 * np.sqrt(n) if dominant else 0.0)
        b = rng.standard_normal((n, nrhs), np.float32)
        return ((st.Matrix.from_numpy(a, nb, grid=grid),
                 st.Matrix.from_numpy(b, nb, grid=grid)), (a, b))

    def check(out, host):
        F, X, h = out
        fields = solve_fields(host[0], np.asarray(X.to_dense()), host[1],
                              h, tag)
        if grid.p > 1:
            # rows the pivoting moved between the grid's process rows
            # (tile row i lives on process row i mod p)
            perm = np.asarray(F.perm)[:n]
            owner = lambda r: (r // nb) % grid.p  # noqa: E731
            across = int(np.sum(owner(perm) != owner(np.arange(n))))
            require(dominant or across > 0,
                    f"{tag}: no pivot row crossed process rows")
            fields["rows_across"] = across
        return fields

    return Job(tag, lambda A, B: st.gesv(A, B, opts),
               (_spec(st, jax, n, n, nb, grid),
                _spec(st, jax, n, nrhs, nb, grid)), make, check,
               {"A": [n, n], "B": [n, nrhs], "nb": nb,
                "method": method.value}, overrides, bool(overrides))


def gels_job(st, jax, grid, tag, m, n, nb, nrhs, method, consistent=False,
             overrides=()):
    opts = {st.Option.ErrorPolicy: st.ErrorPolicy.Info,
            st.Option.MethodGels: method}

    def make(rng):
        a = rng.standard_normal((m, n), np.float32)
        b = rng.standard_normal((m, nrhs), np.float32)
        if consistent:
            # b in range(A): x's forward error is then ~kappa(A) times
            # the backward error, so two routes' answers can be compared
            # (a large residual would amplify it by kappa^2 ||r||)
            b = (a.astype(np.float64) @ b[:n].astype(np.float64)).astype(
                np.float32)
        return ((st.Matrix.from_numpy(a, nb, grid=grid),
                 st.Matrix.from_numpy(b, nb, grid=grid)), (a, b))

    def check(out, host):
        X, h = out
        _health_ok(h, tag)
        a, b = host
        x = np.asarray(X.to_dense())
        ratio, tol, ok = certify_ls(a, x, b)
        require(ok, f"{tag}: certify_lstsq ratio {ratio} > tol {tol}")
        a64 = a.astype(np.float64)
        rn = a64.T @ (b - a64 @ x)
        resid = float(np.linalg.norm(rn) / (np.linalg.norm(a64) ** 2
                                            * np.linalg.norm(x) * m * EPS))
        return dict(resid=resid, cert=ratio, cert_tol=tol)

    return Job(tag, lambda A, B: st.gels(A, B, opts),
               (_spec(st, jax, m, n, nb, grid),
                _spec(st, jax, m, nrhs, nb, grid)), make, check,
               {"A": [m, n], "B": [m, nrhs], "nb": nb,
                "method": method.value}, overrides, bool(overrides))


def heev_job(st, jax, grid, n, nb):
    info = {st.Option.ErrorPolicy: st.ErrorPolicy.Info}

    def make(rng):
        a0 = rng.standard_normal((n, n), np.float32)
        a = (a0 + a0.T) * np.float32(0.5)
        A = st.Matrix.from_numpy(a, nb, grid=grid).hermitian(st.Uplo.Lower)
        return (A,), a

    def check(out, a):
        from slate_tpu.robust import certify as cert
        w, h = out
        _health_ok(h, "heev")
        ref = np.linalg.eigvalsh(a.astype(np.float64))
        err = float(np.abs(np.sort(np.asarray(w, np.float64)) - ref).max())
        anorm = float(np.abs(ref).max())
        tol = cert.tolerance(np.float32, n)
        require(err / anorm <= tol, f"heev: max eigenvalue error "
                f"{err / anorm} x ||A||_2 > tol {tol}")
        return dict(resid=err / (anorm * n * EPS), cert=err / anorm,
                    cert_tol=tol)

    A = _spec(st, jax, n, n, nb, grid).hermitian(st.Uplo.Lower)
    return Job("heev", lambda A: st.heev_vals(A, info), (A,), make, check,
               {"A": [n, n], "nb": nb, "values_only": True})


# ---------------------------------------------------------------- serve


def serve_requests(rng, problems, nrhs, sizes, square_ls=False,
                   well_conditioned=False):
    """Seeded mixed workload: three ops round-robin over ``sizes``
    (bench.py bench_serve_mixed's construction).  ``well_conditioned``
    makes every solve and least-squares matrix G / sqrt(n) + 3 I, whose
    spectrum lies in the unit disc around 3, so kappa(A) stays near 2 —
    as chol_solve's A A^T / n + I (kappa <= 5) already does."""
    ops = ("solve", "chol_solve", "least_squares_solve")
    reqs = []
    for i in range(problems):
        n = int(sizes[i % len(sizes)])
        op = ops[i % len(ops)]
        m = n if (square_ls or op != "least_squares_solve") else n + 8
        a = rng.standard_normal((m, n), np.float32)
        if op == "chol_solve":
            a = (a @ a.T / n + np.eye(n, dtype=np.float32)).astype(
                np.float32)
        elif well_conditioned:
            a /= np.float32(np.sqrt(n))
            a[np.diag_indices(n)] += np.float32(3.0)
        elif op == "solve":
            a = a + np.eye(n, dtype=np.float32) * 4.0
        reqs.append((op, a, rng.standard_normal((m, nrhs), np.float32)))
    return reqs


def serve_fields(reqs, results, name) -> dict:
    """Hold every served answer to certify_solve / certify_lstsq on its
    f64 host residual (tolerance at the problem's own n); report the
    largest ratio / tolerance and the largest scaled residual
    ||r|| / (||A|| ||x|| n eps) (normal equations for least squares)."""
    worst, resid = 0.0, 0.0
    for i, ((op, a, b), res) in enumerate(zip(reqs, results)):
        x = np.asarray(res.x)
        ls = op == "least_squares_solve"
        ratio, tol, ok = (certify_ls if ls else certify)(a, x, b)
        require(ok, f"{name}: problem {i} ({op}, {a.shape}) certify "
                f"ratio {ratio} > tol {tol}")
        worst = max(worst, ratio / tol)
        a64, x64 = a.astype(np.float64), x.astype(np.float64)
        r = b - a64 @ x64
        scale = np.linalg.norm(a64) * np.linalg.norm(x64)
        if ls:
            r, scale = a64.T @ r, scale * np.linalg.norm(a64)
        resid = max(resid, float(np.linalg.norm(r)
                                 / (scale * max(a.shape) * EPS)))
    return dict(resid=resid, cert_over_tol=worst)


def serve_pass(srv, reqs, overrides=()):
    import contextlib

    from slate_tpu import obs, tune
    with contextlib.ExitStack() as stack:
        for op, plan in overrides:
            stack.enter_context(tune.plan_override(op, plan))
        with obs.recording() as events:
            t0 = time.perf_counter()
            results = srv.serve_batch(reqs)
            dt = time.perf_counter() - t0
    for i, r in enumerate(results):
        require(bool(np.asarray(r.health.ok)),
                f"serve: problem {i} ({reqs[i][0]}) health not ok")
    batches = [e for e in events if e["kind"] == "serve_batch"]
    bad = [e["kind"] for e in events
           if e["kind"] in ("serve_quarantine", "serve_device",
                            "serve_shed")]
    require(not bad, f"serve: unexpected {bad}")
    require(all(e["failovers"] == 0 for e in batches), "serve: failover")
    return results, batches, dt


def serve_check_warm(srv, batches, name):
    require(all(not e["compiled"] and e["retraces"] == 0
                for e in batches),
            f"{name}: warm pass compiled or retraced")
    require(srv.health_info()["quarantined"] == 0, f"{name}: quarantine")


# ---------------------------------------------------------------- modes


def one_chip(st, jax, rng):
    from slate_tpu import serve, tune
    from slate_tpu.robust import certify as cert
    from slate_tpu.serve import bucket

    grid = st.Grid(1, 1)
    LU, GELS = st.MethodLU, st.MethodGels
    s = SIZES
    pallas = [(op, tune.TilePlan("pallas", 128, 8)) for op in PALLAS_OPS]
    pp, pg, pq = s["pallas_posv"], s["pallas_gesv"], s["pallas_gels"]
    pairs = [  # (XLA-plan job, Pallas-plan job)
        (posv_job(st, jax, grid, "pallas/posv:xla", **pp),
         posv_job(st, jax, grid, "pallas/posv", **pp, overrides=pallas)),
        (gesv_job(st, jax, grid, "pallas/gesv:xla", **pg, method=LU.CALU,
                  dominant=True),
         gesv_job(st, jax, grid, "pallas/gesv", **pg, method=LU.CALU,
                  dominant=True, overrides=pallas)),
        (gels_job(st, jax, grid, "pallas/gels:xla", **pq, method=GELS.QR,
                  consistent=True),
         gels_job(st, jax, grid, "pallas/gels", **pq, method=GELS.QR,
                  consistent=True, overrides=pallas)),
    ]
    jobs = [gemm_job(st, jax, grid, "gemm", **s["gemm"]),
            posv_job(st, jax, grid, "posv", **s["posv"]),
            gesv_job(st, jax, grid, "gesv", **s["gesv"],
                     method=LU.PartialPiv),
            gels_job(st, jax, grid, "gels", **s["gels"], method=GELS.Auto),
            heev_job(st, jax, grid, **s["heev"])]
    jobs += [j for pair in pairs for j in pair]
    for job in jobs:
        job.lower(st, jax)

    sv, ps = s["serve"], s["pallas_serve"]
    reqs = serve_requests(rng, sv["problems"], sv["nrhs"], sv["sizes"])
    rreqs = serve_requests(rng, ps["problems"], ps["nrhs"],
                           (1, ps["bucket"] // 3, ps["bucket"] - 17,
                            ps["bucket"]), square_ls=True,
                           well_conditioned=True)
    ladder = bucket.BucketLadder((ps["bucket"],), "tuned")
    ragged = [(op, tune.TilePlan("pallas", ps["nb"], 8))
              for op in BATCH_OPS]
    servers = {
        "serve": serve.Server(cache=serve.ExecutableCache()),
        "xla": serve.Server(ladder=ladder, cache=serve.ExecutableCache()),
        "f32": serve.Server(ladder=ladder, cache=serve.ExecutableCache()),
        "bf16": serve.Server(opts={st.Option.Precision: st.Precision.Bf16},
                             ladder=ladder, cache=serve.ExecutableCache()),
    }
    cold = {}      # server -> (compile seconds, cold-pass seconds)
    # two compile workers: XLA's eigh alone takes several GB of host
    # memory to compile, and the machine has 40 GiB
    with ThreadPoolExecutor(max_workers=2) as pool:
        # the longest compiles (XLA's eigh, the pivoted LU) start first
        futures = [pool.submit(job.compile) for job in
                   sorted(jobs, key=lambda j: j.name not in ("heev",
                                                            "gesv"))]
        # the serving cold passes compile inside serve_batch, meanwhile
        for key, srv in servers.items():
            _, _, dt = serve_pass(srv, reqs if key == "serve" else rreqs,
                                  ragged if key in ("f32", "bf16") else ())
            cold[key] = (srv.cache.stats()["compile_ms"] / 1e3, dt)
            progress(f"serve cold pass {key} in {dt:.1f} s")
        for f in futures:
            f.result()

    for job in jobs[:5]:
        job.run(jax, rng)
    run_pairs(jax, rng, pairs, "xla", bf16_products=True)

    # serve: warm pass after the cold one compiled every bucket
    srv = servers["serve"]
    results, batches, warm_s = serve_pass(srv, reqs)
    serve_check_warm(srv, batches, "serve")
    emit("serve", shapes={"problems": len(reqs), "sizes": sv["sizes"],
                          "nrhs": sv["nrhs"]},
         compile_s=cold["serve"][0], cold_s=cold["serve"][1],
         run_s=warm_s, **serve_fields(reqs, results, "serve"),
         escalations=sum(e["escalated"] for e in batches),
         batches=len(batches), failovers=0, quarantined=0, retraces=0)

    # the ragged batch is well conditioned by construction, so the two
    # routes' answers must agree to the library tolerance at the bucket
    ref_results = serve_pass(servers["xla"], rreqs)[0]
    serve_fields(rreqs, ref_results, "pallas/serve_xla")
    kappa = max(np.linalg.cond(a.astype(np.float64)) for _, a, _ in rreqs)
    require(kappa <= 10.0, f"pallas/serve: kappa(A) {kappa} > 10")
    tol = cert.tolerance(np.float32, ps["bucket"])
    for key in ("f32", "bf16"):
        srv = servers[key]
        k = sum(pallas_kernels(e) for e in srv.cache.executables())
        require(k > 0, f"pallas/serve_{key}: no Pallas kernel compiled")
        results, batches, warm_s = serve_pass(srv, rreqs)
        serve_check_warm(srv, batches, f"pallas/serve_{key}")
        diff = max(rel_diff(r.x, q.x) for r, q in zip(results, ref_results))
        require(diff <= tol, f"pallas/serve_{key}: differs from the XLA "
                f"route by {diff} > {tol}")
        emit(f"pallas/serve_{key}",
             shapes={"problems": len(rreqs), "bucket": ps["bucket"],
                     "nb": ps["nb"]},
             compile_s=cold[key][0], cold_s=cold[key][1], run_s=warm_s,
             **serve_fields(rreqs, results, f"pallas/serve_{key}"),
             vs_xla=diff, tol=tol, kappa_max=kappa,
             escalations=sum(e["escalated"] for e in batches),
             pallas_kernels=k, failovers=0, quarantined=0, retraces=0)


def run_pairs(jax, rng, pairs, what, bf16_products=False):
    """Run each (reference, candidate) job pair on the SAME data and
    hold the candidate's X to a tolerance around the reference's: the
    library tolerance where both multiply alike (the grids), and
    2 kappa(A) EPS_BF16 where the reference's default-precision products
    and the candidate's f32 kernels differ (``bf16_products``)."""
    from slate_tpu.robust import certify as cert
    for ref_job, job in pairs:
        state = rng.bit_generator.state
        ref, host = ref_job.run(jax, rng)
        rng.bit_generator.state = state
        out, _ = job.run(jax, rng)
        x = np.asarray(solution(out).to_dense())
        diff = rel_diff(x, np.asarray(solution(ref).to_dense()))
        fields = {}
        if bf16_products:
            fields["kappa"] = float(np.linalg.cond(host[0].astype(
                np.float64)))
            tol = 2.0 * fields["kappa"] * EPS_BF16
        else:
            tol = cert.tolerance(np.float32, x.shape[0])
        require(diff <= tol, f"{job.name}: differs from {what} by {diff}"
                f" > {tol}")
        emit(job.name + ":vs_" + what, rel_diff=diff, tol=tol, **fields)
        del ref, out


def four_chips(st, jax, rng, phases=MESH_PHASES):
    """BASELINE config 2 on a 2x2 grid: gemm, posv and gesv each against
    Grid(1, 1) on a dominant-diagonal matrix (one pivot order), and
    gesv_general, partial pivoting on a Gaussian matrix whose row swaps
    cross the process rows, against the host certificate alone."""
    devs = jax.devices()
    require(len(devs) >= 4, f"--chips 4 needs 4 devices, have {len(devs)}")
    g4 = st.Grid(2, 2, devices=devs[:4])
    g1 = st.Grid(1, 1)
    s = SIZES["mesh"]
    n, nb, nrhs = s["n"], s["nb"], s["nrhs"]
    PP = st.MethodLU.PartialPiv
    makers = {"gemm": lambda g, t: gemm_job(st, jax, g, t, n, nb),
              "posv": lambda g, t: posv_job(st, jax, g, t, n, nb, nrhs),
              "gesv": lambda g, t: gesv_job(st, jax, g, t, n, nb, nrhs, PP,
                                            dominant=True)}
    pairs = [(make(g1, f"{kind}:grid1x1"), make(g4, f"{kind}:grid2x2"))
             for kind, make in makers.items() if kind in phases]
    alone = ([gesv_job(st, jax, g4, "gesv_general:grid2x2", n, nb, nrhs,
                       PP)] if "gesv_general" in phases else [])
    jobs = [j for pair in pairs for j in pair] + alone
    for job in jobs:
        job.lower(st, jax)
    with ThreadPoolExecutor(max_workers=3) as pool:
        for f in [pool.submit(job.compile) for job in jobs]:
            f.result()
    spread = st.Matrix.from_numpy(np.ones((n, nb), np.float32), nb,
                                  grid=g4).storage.data.sharding.device_set
    require(len(spread) == 4, f"2x2 operand spans {len(spread)} devices")
    run_pairs(jax, rng, pairs, "grid1x1")
    for job in alone:
        job.run(jax, rng)
    emit("mesh", grid=[2, 2], phases=list(phases),
         operand_devices=len(spread), peak_bytes=peak_bytes(devs[:4]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2 block-cyclic path")
    ap.add_argument("--only", nargs="+", choices=MESH_PHASES,
                    default=MESH_PHASES,
                    help="with --chips 4: run only these mesh phases")
    args = ap.parse_args(argv)

    import jax
    dev = device_info(jax)
    if dev["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {dev}")
    built = build_native()
    # the plan cache that ships with the checkout, never ~/.cache
    os.environ["SLATE_TUNE_CACHE"] = str(ROOT / "tune_plans.json")
    import slate_tpu as st
    from slate_tpu import native
    from slate_tpu.util.runtime import use_compile_cache
    require(native.available() or not built, "native build did not load")
    emit("setup", device=dev, jax=jax.__version__,
         packing=(f"native v{native.version()}" if native.available()
                  else "numpy fallback (no compiler)"),
         compile_cache=use_compile_cache(),
         plan_cache=os.environ["SLATE_TUNE_CACHE"])
    rng = np.random.default_rng(SEED)
    if args.chips == 4:
        four_chips(st, jax, rng, args.only)
    else:
        one_chip(st, jax, rng)
    emit("memory", peak_bytes=peak_bytes(jax.devices()[:args.chips]))
    print(json.dumps({"ok": True, "device": device_info(jax)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
