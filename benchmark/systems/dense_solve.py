"""System adapter: one large dense solve at a time through the public
drivers ``st.gesv`` / ``st.posv`` on device tile storage.

Set-up makes ``A_base`` and ``b`` on the device from the seed in one
jitted call and compiles (or loads) the one solve program.  The window
is a closed loop: solve ``i`` factors ``A_base + s_i I`` (``s_i`` from
the traffic generator) and its x is copied to the host before the next
is dispatched, as a caller that uses its answer runs.  After the window
every solve's x is held to the f64 host residual of its own operands,
made again from the seed by the benchmark's own generator.

The window also times each solve's two host phases, the dispatch and
the wait for x, and the cyclic collector's pauses, so that a run whose
solves stall can tell where (standard error only).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import flops, generator, reference


def build(config, mix, devs, log):
    return DenseSolve(config, mix, devs, log)


def canonical_tiles(key, n: int, nb: int, kind: str):
    """A_base as canonical tiles ``[n/nb, n/nb, nb, nb]``: HPL's uniform
    [-0.5, 0.5] for ``general``; ``0.001 (G + G^T) + 4 I`` with Gaussian
    G for ``spd``."""
    import jax
    import jax.numpy as jnp
    t = n // nb
    if kind == "general":
        return jax.random.uniform(key, (t, t, nb, nb), jnp.float32, -0.5, 0.5)
    g = jax.random.normal(key, (t, t, nb, nb), jnp.float32)
    sym = 0.001 * (g + jnp.transpose(g, (1, 0, 3, 2)))
    eye = jnp.eye(t, dtype=jnp.float32)[:, :, None, None] * jnp.eye(
        nb, dtype=jnp.float32)
    return sym + 4.0 * eye


def rhs(key, n: int):
    import jax
    import jax.numpy as jnp
    return jax.random.uniform(key, (n, 1), jnp.float32, -0.5, 0.5)


def operand_keys(seed: int):
    """The keys of A_base and b: every whole seed up to 2**64 maps to its
    own pair."""
    import jax
    root = jax.random.key(np.uint32(seed % (1 << 32)))
    root = jax.random.fold_in(root, np.uint32((seed >> 32) % (1 << 32)))
    return jax.random.split(root)


def dense_from_tiles(tiles) -> np.ndarray:
    t, _, nb, _ = tiles.shape
    return np.ascontiguousarray(
        np.asarray(tiles).transpose(0, 2, 1, 3).reshape(t * nb, t * nb))


class DenseSolve:
    def __init__(self, config, mix, devs, log):
        self.config, self.mix, self.devs, self.log = config, mix, devs, log
        self.n, self.nb = int(config["n"]), int(config["nb"])
        self.op = mix["op"]
        self.kind = config["matrix"][self.op]
        p, q = config["grid"][str(len(devs))]
        self.grid_shape = (int(p), int(q))
        self.records = []          # (shift, x host copy, health ok)
        self.host_s = []           # (dispatch, wait) seconds per solve
        self.gc_s = []             # the collector's pauses in the window
        self.window_s = None

    # ------------------------------------------------------------ set-up

    def grid(self):
        import slate_tpu as st
        p, q = self.grid_shape
        return (st.Grid(p, q, devices=self.devs) if p * q > 1
                else st.Grid(1, 1))

    def diagonal_tiles(self):
        """Storage (row, column) of each diagonal tile (i, i): tile row i
        of the canonical order lives at storage row (i % p) * mtl + i // p,
        and likewise for columns."""
        p, q = self.grid_shape
        t = self.n // self.nb
        mtl, ntl = -(-t // p), -(-t // q)
        return (np.array([(i % p) * mtl + i // p for i in range(t)]),
                np.array([(i % q) * ntl + i // q for i in range(t)]))

    def solve_fn(self, grid):
        """The timed program: ``(A tiles, A_base's diagonal tiles, b
        tiles, s) -> (A tiles, x, ok)``, the public driver on
        ``A_base + s I`` in device tile storage.  A's buffer is donated and
        only its diagonal tiles are rewritten, so making the operand costs
        the solve no pass over A."""
        import jax.numpy as jnp

        import slate_tpu as st
        from slate_tpu.core.storage import TileStorage
        n, nb, op = self.n, self.nb, self.op
        diag_s, diag_t = self.diagonal_tiles()
        opts = {st.Option.ErrorPolicy: st.ErrorPolicy.Info}
        if op == "gesv":
            opts[st.Option.MethodLU] = st.MethodLU.PartialPiv

        def solve(a, diag, bdata, s):
            a = a.at[diag_s, diag_t].set(
                diag + s * jnp.eye(nb, dtype=diag.dtype))
            A = st.Matrix(TileStorage(a, n, n, nb, nb, grid),
                          kind=st.TileKind.UserOwned)
            B = st.Matrix(TileStorage(bdata, n, 1, nb, nb, grid),
                          kind=st.TileKind.UserOwned)
            if op == "gesv":
                _, X, h = st.gesv(A, B, opts)
            else:
                _, X, h = st.posv(A.hermitian(st.Uplo.Lower), B, opts)
            return a, X.to_dense()[:, 0], h.ok

        return solve

    def setup(self, seed: int, seconds: float) -> None:
        import jax
        import jax.numpy as jnp

        from slate_tpu.core import layout
        from slate_tpu.core.storage import TileStorage

        self.seed = seed
        n, nb, kind = self.n, self.nb, self.kind
        p, q = self.grid_shape
        grid = self.grid()
        self._ka, self._kb = operand_keys(seed)
        diag_s, diag_t = self.diagonal_tiles()
        with jax.profiler.TraceAnnotation("bench.operands"):
            make = jax.jit(
                lambda k: layout.canonical_to_cyclic(
                    canonical_tiles(k, n, nb, kind), p, q),
                out_shardings=grid.tile_sharding())
            self.a = make(self._ka)
            self.diag = jax.jit(lambda a: a[diag_s, diag_t])(self.a)
            self.b = TileStorage.from_dense(rhs(self._kb, n), nb, nb,
                                            grid).data
        self.step = jax.jit(self.solve_fn(grid), donate_argnums=0)
        t0 = time.perf_counter()
        self.a, x, ok = self.step(self.a, self.diag, self.b,
                                  jnp.float32(0.0))
        np.asarray(x)
        self.log(f"warm solve (compile or cache load) "
                 f"{time.perf_counter() - t0:.3f} s, health ok {bool(ok)}")

    # ------------------------------------------------------------ window

    def window(self, seconds: float) -> None:
        import jax
        import jax.numpy as jnp
        pause = []

        def on_gc(phase, _info):
            if phase == "start":
                pause.append(time.perf_counter())
            elif pause:
                self.gc_s.append(time.perf_counter() - pause.pop())

        gc.callbacks.append(on_gc)
        try:
            t0 = time.perf_counter()
            i = 0
            while time.perf_counter() - t0 < seconds:
                with jax.profiler.TraceAnnotation("bench.operands"):
                    s = generator.shift(self.mix, self.seed, i)
                with jax.profiler.TraceAnnotation("bench.solve"):
                    t1 = time.perf_counter()
                    self.a, x, ok = self.step(self.a, self.diag, self.b,
                                              jnp.float32(s))
                    t2 = time.perf_counter()
                    x, ok = np.asarray(x), bool(ok)
                    self.host_s.append((t2 - t1, time.perf_counter() - t2))
                self.records.append((s, x, ok))
                i += 1
            self.window_s = time.perf_counter() - t0
        finally:
            gc.callbacks.remove(on_gc)

    def release(self) -> None:
        self.a = self.diag = self.b = self.step = None

    # ------------------------------------------------------------ results

    def check(self):
        """Every solve of the window against its f64 host residual.  The
        numbers compared are the worst, over the window's solves, of each
        residual measure the configuration sets a limit on for this op
        (``reference.RESIDUALS``)."""
        import jax

        from benchmark.harness import Check
        limits = self.limits()
        if not self.records:
            self.certified = []
            return 0, 0, [Check(f"{k}_max", float("inf"), lim)
                          for k, lim in limits.items()]
        with jax.profiler.TraceAnnotation("bench.certify"):
            a = dense_from_tiles(jax.device_get(
                canonical_tiles(self._ka, self.n, self.nb, self.kind)))
            b = np.asarray(jax.device_get(rhs(self._kb, self.n)))
            shifts = [s for s, _, _ in self.records]
            x = np.stack([xi for _, xi, _ in self.records], axis=1)
            bb = np.repeat(b, len(shifts), axis=1)
            r, anorm = reference.shifted_residuals(a, shifts, x, bb)
            self.residuals = reference.residual_measures(
                anorm, x.astype(np.float64), bb, r)
        self.certified = [
            bool(ok) and all(self.residuals[k][j] <= lim
                             for k, lim in limits.items())
            for j, (_, _, ok) in enumerate(self.records)]
        failed = sum(not ok for _, _, ok in self.records)
        checks = [Check(f"{k}_max", float(np.max(self.residuals[k])), lim)
                  for k, lim in limits.items()]
        return len(self.records), failed, checks

    def limits(self) -> dict:
        return {k: float(v)
                for k, v in self.config["limits"][self.op].items()}

    def end_to_end(self) -> dict:
        good = sum(self.certified)
        return {"solve_s": self.window_s / good if good else float("inf")}

    def context(self) -> dict:
        return {"solves": len(self.records),
                "window_s": self.window_s,
                "flops_per_solve": flops.solve_flops(self.op, self.n, 1)}

    def notes(self) -> list:
        out = [f"solves completed in the window: {len(self.records)}",
               f"window {self.window_s!r} s"]
        if not self.records:
            return out + ["no solve in the window"]
        for k, v in getattr(self, "residuals", {}).items():
            out.append(f"{k}: min {float(np.min(v))!r} median "
                       f"{float(np.median(v))!r} max {float(np.max(v))!r}")
        xn = [float(np.abs(x).max()) for _, x, _ in self.records]
        out.append(f"||x||_inf: median {float(np.median(xn))!r} "
                   f"max {max(xn)!r}")
        out += host_phases(self.host_s, self.gc_s)
        return out


def host_phases(host_s, gc_s, excess_s: float = 0.02) -> list:
    """Where the window's host time went: each solve's dispatch (the
    call that enqueues the program) and wait (until x and its health are
    on the host), and the solves that took ``excess_s`` or more over the
    median, split by the phase that held the excess."""
    d = np.array([h[0] for h in host_s])
    w = np.array([h[1] for h in host_s])
    md, mw = float(np.median(d)), float(np.median(w))
    slow = (d + w) >= md + mw + excess_s
    in_d = int(np.sum(slow & (d - md >= w - mw)))
    return [f"dispatch ms: median {1e3 * md!r} max {1e3 * float(d.max())!r}",
            f"wait ms: median {1e3 * mw!r} max {1e3 * float(w.max())!r}",
            f"solves {1e3 * excess_s:g} ms or more over the median: "
            f"{int(slow.sum())} ({in_d} in dispatch, "
            f"{int(slow.sum()) - in_d} in wait), their excess "
            f"{1e3 * float(np.sum((d + w - md - mw)[slow]))!r} ms",
            f"collector pauses in the window: {len(gc_s)}, "
            f"{1e3 * sum(gc_s)!r} ms, longest "
            f"{1e3 * max(gc_s, default=0.0)!r} ms"]
