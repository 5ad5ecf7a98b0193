#!/usr/bin/env python3
"""Time the two partially-pivoted LU panels on a TPU: XLA's LU custom
call (``lax.linalg.lu``) against ``internal/getrf.panel_lu_recursive``.

Prints one JSON line per (rows, width, panel) with compile seconds, the
best of five run milliseconds, and ||P A - L U|| / ||A|| in f64 on the
host.  The shapes are the drivers': nb=512 panels of a partial-pivot
gesv (rows shrink from n to nb) and 128-wide serving panels.  Run it on
the chip: ``chiprun -- python tools/panel_lu_time.py``.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

SHAPES = ((128, 128), (256, 128), (512, 512), (1024, 512), (2048, 512),
          (4096, 512), (8192, 512), (16384, 512))


def main() -> int:
    import jax
    from jax import lax

    from slate_tpu.internal import getrf
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("panel_lu_time: needs a TPU")
    rng = np.random.default_rng(0)
    panels = {"xla": lambda p: lax.linalg.lu(p)[::2],
              "recursive": getrf.panel_lu_recursive}
    for rows, w in SHAPES:
        a = rng.standard_normal((rows, w), dtype=np.float32)
        dev = jax.device_put(a)
        for name, fn in panels.items():
            if name == "xla" and rows > getrf.XLA_LU_MAX_ROWS:
                continue                    # refused by the chip compiler
            t0 = time.perf_counter()
            run = jax.jit(fn).lower(dev).compile()
            compile_s = time.perf_counter() - t0
            jax.block_until_ready(run(dev))
            times = []
            for _ in range(5):
                t0 = time.perf_counter()
                lu, perm = jax.block_until_ready(run(dev))
                times.append(time.perf_counter() - t0)
            lu = np.asarray(lu, np.float64)
            ell = np.tril(lu, -1)[:, :w] + np.eye(rows, w)
            err = np.linalg.norm(a[np.asarray(perm)].astype(np.float64)
                                 - ell @ np.triu(lu[:w]))
            print(json.dumps({"rows": rows, "w": w, "panel": name,
                              "compile_s": compile_s,
                              "run_ms": min(times) * 1e3,
                              "resid": err / np.linalg.norm(a)}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
