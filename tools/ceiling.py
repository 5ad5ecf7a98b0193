"""Hardware-ceiling + per-piece cost measurements for the headline solvers.

Answers the round-5 profile questions (VERDICT weak #1/#2/#8):
  - raw `jnp.dot` FLOP/s at bench shapes, f32 vs bf16 (is XLA's default f32
    matmul really running at the bf16 MXU rate?)
  - cost of one cyclic<->dense layout round trip at n=16384 (the re-tiling
    overhead the single-target drivers pay per call)
  - per-invocation cost of the XLA panel primitives the blocked solvers
    sequence 32+ times: cholesky(512), triangular_solve(15872x512),
    lu(512x512) single + vmapped over 32 chunks
  - one full potrf step (panel + trsm + trailing syrk) at k=0 vs its gemm

Timing follows bench.py: operands as jit args, iters dependent
applications chained in one lax.scan, one scalar fetched.
Each measurement prints one JSON line.  Findings live in docs/PERF.md.
"""

import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

OUT = open(os.path.join(_ROOT, "docs", "ceiling.jsonl"), "a", buffering=1)


def time_chain(body, init, args, iters, reps=3):
    """Seconds per body application (best of reps), chained to be dependent."""

    def chained(c0, *ops):
        c, _ = lax.scan(lambda c, _: (body(c, *ops), None), c0, None,
                        length=iters)
        while getattr(c, "ndim", 0) > 0:
            c = c[(0,) * c.ndim]
        return c

    run = jax.jit(chained)
    np.asarray(jax.device_get(run(init, *args)))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(jax.device_get(run(init, *args)))
        times.append(time.perf_counter() - t0)
    return min(times) / iters


def emit(name, secs, flops=None, extra=None):
    line = {"probe": name, "ms": round(secs * 1e3, 3)}
    if flops:
        line["gflops"] = round(flops / secs / 1e9, 1)
        line["mfu_vs_197tf"] = round(flops / secs / 197e12, 3)
    if extra:
        line.update(extra)
    print(json.dumps(line), flush=True)
    OUT.write(json.dumps(line) + "\n")


def probe_dot(n, dtype, iters):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((n, n)), dtype)
    b = jnp.asarray(rng.standard_normal((n, n)), dtype)

    def body(c, a):
        return (a @ c) * (1.0 / n)

    s = time_chain(body, b, (a,), iters)
    emit(f"dot_n{n}_{jnp.dtype(dtype).name}", s, 2.0 * n**3)


def probe_layout(n, nb, iters):
    """Cost of one dense->tiles->cyclic + back round trip (Grid(1,1))."""
    from slate_tpu.core import layout
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((n, n)).astype(np.float32))

    def body(c):
        tiles = layout.tile_dense(c, nb, nb)
        cyc = layout.canonical_to_cyclic(tiles, 1, 1)
        can = layout.cyclic_to_canonical(cyc, n // nb, n // nb, 1, 1)
        return layout.untile_dense(can, n, n)

    s = time_chain(lambda c: body(c), a, (), iters)
    emit(f"layout_roundtrip_n{n}_nb{nb}", s,
         extra={"note": "2x pack+unpack passes of n^2 f32"})


def probe_cholesky(nb, iters):
    rng = np.random.default_rng(2)
    a0 = rng.standard_normal((nb, nb)).astype(np.float32)
    a = jnp.asarray(a0 @ a0.T + nb * np.eye(nb, dtype=np.float32))

    def body(c, a):
        l = lax.linalg.cholesky(a * (1 + c * 1e-30))
        return l[0, 0] * 1e-30

    s = time_chain(body, jnp.float32(0.0), (a,), iters)
    emit(f"xla_cholesky_{nb}", s, nb**3 / 3)


def probe_trsm(m, nb, iters):
    rng = np.random.default_rng(3)
    l = jnp.asarray(np.tril(rng.standard_normal((nb, nb))).astype(np.float32)
                    + nb * np.eye(nb, dtype=np.float32))
    b = jnp.asarray(rng.standard_normal((m, nb)).astype(np.float32))

    def body(c, l, b):
        x = lax.linalg.triangular_solve(l, b * (1 + c * 1e-30),
                                        left_side=False, lower=True,
                                        transpose_a=True)
        return x[0, 0] * 1e-30

    s = time_chain(body, jnp.float32(0.0), (l, b), iters)
    emit(f"xla_trsm_{m}x{nb}", s, float(m) * nb * nb)


def probe_lu(nb, batch, iters, rows=None):
    rng = np.random.default_rng(4)
    rows = rows or nb
    shape = (batch, rows, nb) if batch > 1 else (rows, nb)
    a = jnp.asarray(rng.standard_normal(shape).astype(np.float32))

    def body(c, a):
        lu, _, _ = lax.linalg.lu(a * (1 + c * 1e-30))
        return lu[(0,) * lu.ndim] * 1e-30

    s = time_chain(body, jnp.float32(0.0), (a,), iters)
    emit(f"xla_lu_{rows}x{nb}_batch{batch}", s,
         batch * (rows * nb * nb - nb**3 / 3.0))


def probe_full_trsm(n, nrhs, iters):
    """Whole-triangle solve (the potrs/getrs path): L [n, n] vs [n, nrhs]."""
    rng = np.random.default_rng(7)
    l = jnp.asarray((np.tril(rng.standard_normal((n, n)))
                     + n * np.eye(n)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((n, nrhs)).astype(np.float32))

    def body(c, l, b):
        x = lax.linalg.triangular_solve(l, b * (1 + c * 1e-30),
                                        left_side=True, lower=True)
        return x[0, 0] * 1e-30

    s = time_chain(body, jnp.float32(0.0), (l, b), iters)
    emit(f"xla_full_trsm_{n}x{nrhs}", s, float(n) * n * nrhs)


def probe_full_chol(n, iters):
    """XLA's own monolithic cholesky(n) — the vendor bar for potrf."""
    rng = np.random.default_rng(8)
    a0 = rng.standard_normal((n, n)).astype(np.float32) * 0.001
    a = jnp.asarray(a0 + a0.T + 4 * np.eye(n, dtype=np.float32))

    def body(c, a):
        l = lax.linalg.cholesky(a * (1 + c * 1e-30))
        return l[0, 0] * 1e-30

    s = time_chain(body, jnp.float32(0.0), (a,), iters)
    emit(f"xla_full_cholesky_{n}", s, n**3 / 3)


def probe_full_qr(m, n, iters):
    """XLA's monolithic qr — the vendor bar for geqrf tall-skinny."""
    rng = np.random.default_rng(9)
    a = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))

    def body(c, a):
        q, r = lax.linalg.qr(a * (1 + c * 1e-30), full_matrices=False)
        return r[0, 0] * 1e-30

    s = time_chain(body, jnp.float32(0.0), (a,), iters)
    emit(f"xla_full_qr_{m}x{n}", s, 2.0 * m * n * n - 2.0 * n**3 / 3)


def probe_potrf_step(n, nb, iters):
    """One right-looking potrf step at k=0: panel chol + trsm + full syrk."""
    rng = np.random.default_rng(5)
    a0 = rng.standard_normal((n, n)).astype(np.float32) * 0.001
    a = jnp.asarray(a0 + a0.T + 4 * np.eye(n, dtype=np.float32))

    def body(c, a):
        a = a * (1 + c * 1e-30)
        lkk = lax.linalg.cholesky(a[:nb, :nb])
        panel = lax.linalg.triangular_solve(
            lkk, a[nb:, :nb], left_side=False, lower=True, transpose_a=True)
        upd = a[nb:, nb:] - panel @ panel.T
        return upd[0, 0] * 1e-30

    s = time_chain(body, jnp.float32(0.0), (a,), iters)
    gemm_flops = 2.0 * (n - nb) ** 2 * nb
    emit(f"potrf_step_n{n}_nb{nb}", s, gemm_flops,
         {"note": "chol+trsm+full-square syrk; flops = syrk as full gemm"})


def probe_qr_panel(m, nb, iters):
    from slate_tpu.internal.qr import householder_panel_blocked
    rng = np.random.default_rng(6)
    a = jnp.asarray(rng.standard_normal((m, nb)).astype(np.float32))

    def body(c, a):
        v, t = householder_panel_blocked(a * (1 + c * 1e-30))
        return v[0, 0] * 1e-30

    s = time_chain(body, jnp.float32(0.0), (a,), iters)
    emit(f"qr_panel_{m}x{nb}", s, 2.0 * m * nb * nb)


GROUPS = {
    "dots": lambda: [probe_dot(n, dt, it)
                     for n, it in ((4096, 30), (8192, 10), (16384, 4))
                     for dt in (jnp.float32, jnp.bfloat16)],
    "panels": lambda: [probe_trsm(15872, 512, 20),
                       probe_lu(512, 1, 30),
                       probe_lu(512, 32, 10)],
    "chols": lambda: [probe_layout(16384, 512, 8),
                      probe_cholesky(512, 50),
                      probe_cholesky(1024, 20)],
    "layouts": lambda: [probe_layout(4096, 256, 30),
                        probe_layout(8192, 512, 10),
                        probe_full_trsm(16384, 256, 6)],
    "fulls": lambda: [probe_layout(16384, 512, 8),
                      probe_full_chol(16384, 3),
                      probe_full_qr(131072, 1024, 3)],
    "lutall": lambda: [probe_lu(512, 1, 6, rows=4096),
                       probe_lu(512, 4, 4, rows=4096),
                       probe_lu(512, 1, 4, rows=16384),
                       probe_lu(1024, 1, 4, rows=16384)],
    "lufull": lambda: [probe_lu(16384, 1, 2)],
    "steps": lambda: [probe_potrf_step(16384, 512, 6),
                      probe_potrf_step(16384, 1024, 6),
                      probe_qr_panel(131072, 256, 10),
                      probe_qr_panel(131072, 512, 10)],
}


def main():
    dev = jax.devices()[0].device_kind
    print(json.dumps({"probe": "device", "kind": dev}), flush=True)
    for name in (sys.argv[1:] or list(GROUPS)):
        GROUPS[name]()


if __name__ == "__main__":
    main()
