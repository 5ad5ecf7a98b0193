#!/usr/bin/env python3
"""The control: the plain reference put in the program's place, at a
lower precision than the configuration states, judged by the same
numbers as a run.  A limit is sound only where the control fails it.

    python3 benchmark/control.py --workload linsys.gesv --seeds 1 2 3 \
        --prec fp8 default

Each seed's operands are made as a run makes them and the first
``--solves`` shifted systems of its closed loop are solved by
``reference.lu_solve`` / ``reference.cholesky_solve``.  Each line is one
(seed, precision) with the worst and median of every residual measure,
and the median ``||x||_inf``.  The runs of the cell give the program's
own readings.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import generator, harness, reference  # noqa: E402


def alter(x):
    """The answer-altered fault of a dense solve: one entry of x moved by
    a tenth of |x|."""
    x = np.array(x, copy=True)
    x.flat[0] += 0.1 * np.abs(x).max()
    return x


def dense_readings(config, mix, seed: int, prec: str, solves: int,
                   fault: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from benchmark.systems import dense_solve as ds
    n, nb = int(config["n"]), int(config["nb"])
    kind = config["matrix"][mix["op"]]
    ka, kb = ds.operand_keys(seed)
    tiles = ds.canonical_tiles(ka, n, nb, kind)
    b = ds.rhs(kb, n)
    solver = {"gesv": reference.lu_solve,
              "posv": reference.cholesky_solve}[mix["op"]]

    @jax.jit
    def solve(tiles, b, s):
        t = tiles.shape[0]
        a = jnp.transpose(tiles, (0, 2, 1, 3)).reshape(t * nb, t * nb)
        return solver(a + s * jnp.eye(n, dtype=a.dtype), b, nb, prec)[:, 0]

    shifts = [generator.shift(mix, seed, i) for i in range(solves)]
    x = np.stack([np.asarray(solve(tiles, b, jnp.float32(s)))
                  for s in shifts], axis=1)
    if fault:
        x[:, 0] = alter(x[:, 0])
    a_host = ds.dense_from_tiles(jax.device_get(tiles))
    bb = np.repeat(np.asarray(jax.device_get(b)), solves, axis=1)
    r, anorm = reference.shifted_residuals(a_host, shifts, x, bb)
    out = {}
    for k, v in reference.residual_measures(
            anorm, x.astype(np.float64), bb, r).items():
        out[f"{k}_max"] = float(np.max(v))
        out[f"{k}_median"] = float(np.median(v))
    out["x_inf_median"] = float(np.median(np.abs(x).max(axis=0)))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--prec", nargs="+", default=["fp8"],
                    choices=reference.PRECISIONS)
    ap.add_argument("--solves", type=int, default=3)
    ap.add_argument("--fault", action="store_true",
                    help="also read the answer-altered fault on the "
                         "first answer (see alter)")
    args = ap.parse_args()
    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    config = harness.load_config(spec, cell)
    mix = harness.load_traffic(cell)
    import jax
    harness.devices_for(jax, 1)
    harness.prepare_runtime(jax)
    for prec in args.prec:
        for seed in args.seeds:
            for fault in (False, True) if args.fault else (False,):
                out = dense_readings(config, mix, seed, prec, args.solves,
                                     fault)
                out = {k: (v if math.isfinite(v) else str(v))
                       for k, v in out.items()}
                print(json.dumps({"workload": args.workload, "seed": seed,
                                  "prec": prec, "fault": fault, **out}),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
