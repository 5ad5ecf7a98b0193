#!/usr/bin/env python3
"""Rehearse the cells without the chip: compile each cell's programs at
full size for a described TPU v5e and print their memory analysis.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [cell ...]
    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --cpu-run linsys.posv \
        --n 1024

A dense cell compiles its one solve program (on as many described chips
of a ``v5e:2x2`` topology as the cell takes).  Nothing runs, so nothing
here is a time.  ``--cpu-run`` instead runs the named cell through the
whole harness on the CPU at a small ``--n`` (and ``--nb``), checking
the path end to end.  This is a script and not a test: describing a
topology loads the TPU compiler's library, which one process at a time
may hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import harness  # noqa: E402


def _native_kernels() -> None:
    """Compile Pallas kernels natively: the library decides from the
    default backend, which is the CPU here."""
    from slate_tpu.internal import potrf
    from slate_tpu.util import runtime
    for mod in (runtime, potrf):
        if hasattr(mod, "pallas_interpret"):
            mod.pallas_interpret = lambda: False


def dense(system, topo) -> list:
    import jax
    import jax.numpy as jnp
    chips = len(system.devs)
    system.devs = list(topo.devices[:chips])
    grid = system.grid()
    n, nb = system.n, system.nb
    p, q = system.grid_shape
    t = n // nb
    mtl, ntl = -(-t // p), -(-t // q)
    sharding = (grid.tile_sharding() if chips > 1 else
                jax.sharding.SingleDeviceSharding(topo.devices[0]))
    rep = (grid.replicated_sharding() if chips > 1 else sharding)
    specs = (jax.ShapeDtypeStruct((p * mtl, q * ntl, nb, nb), jnp.float32,
                                  sharding=sharding),
             jax.ShapeDtypeStruct((t, nb, nb), jnp.float32, sharding=rep),
             jax.ShapeDtypeStruct((p * mtl, q, nb, nb), jnp.float32,
                                  sharding=sharding),
             jax.ShapeDtypeStruct((), jnp.float32, sharding=rep))
    t0 = time.perf_counter()
    compiled = jax.jit(system.solve_fn(grid), donate_argnums=0).lower(
        *specs).compile()
    mem = compiled.memory_analysis()
    return [{"program": f"{system.op} n={n} nb={nb} grid={p}x{q}",
             "compile_s": time.perf_counter() - t0,
             "temp_bytes": mem.temp_size_in_bytes,
             "argument_bytes": mem.argument_size_in_bytes,
             "output_bytes": mem.output_size_in_bytes,
             "pallas_kernels": compiled.as_text().count("tpu_custom_call")}]


def aot(cells) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    _native_kernels()
    spec = harness.load_spec()
    for name in cells or [c["name"] for c in spec["workloads"]]:
        cell = harness.find_cell(spec, name)
        config = harness.load_config(spec, cell)
        mix = harness.load_traffic(cell)
        devs = [None] * int(cell["chips"])
        system = harness.system_module(config).build(
            config, mix, devs, lambda m: None)
        for row in dense(system, topo):
            print(json.dumps({"cell": name, **row}), flush=True)
    return 0


def cpu_run(cell_name: str, n: int, nb: int, seconds: float) -> int:
    """The whole harness on the CPU's devices, the cell's configuration
    cut to order ``n``, from a copy of the benchmark's files."""
    import shutil
    root = Path(tempfile.mkdtemp(prefix="bench-rehearse-"))
    try:
        shutil.copytree(harness.ROOT / "benchmark", root / "benchmark")
        spec = harness.load_spec()
        shutil.copy(harness.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
        cell = harness.find_cell(spec, cell_name)
        for entry in spec["configs"]:
            if entry["name"] == cell["config"]:
                path = root / entry["file"]
                config = json.loads(path.read_text())
                config.update(n=n, nb=nb)
                path.write_text(json.dumps(config))
        result = harness.run_cell(cell_name, 1, seconds, False,
                                  time.perf_counter(), root=root,
                                  require_tpu=False)
        print(json.dumps(result), flush=True)
        return 0 if result["correct"] else 1
    finally:
        shutil.rmtree(root, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cells", nargs="*")
    ap.add_argument("--cpu-run", metavar="CELL")
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--nb", type=int, default=128)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    if args.cpu_run:
        return cpu_run(args.cpu_run, args.n, args.nb, args.seconds)
    return aot(args.cells)


if __name__ == "__main__":
    sys.exit(main())
