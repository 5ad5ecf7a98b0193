"""The four-chip Cholesky cell against the one-chip one, on the CPU.

At a small order, on four of the CPU's eight devices: the 2x2 cell's
operands are the one-chip cell's, its solve agrees with the one-chip
solve and with the f64 reference, and its collective reader gives the
number its definition gives.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import harness, trace  # noqa: E402

SPEC = harness.load_spec(ROOT)
TINY = {"n": 256, "nb": 64}
GRID_CELL, ONE_CELL = "linsys.posv_2x2", "linsys.posv"
SEED = 2**33 + 25


def _system(name):
    import jax
    cell = harness.find_cell(SPEC, name)
    config = dict(harness.load_config(SPEC, cell, ROOT), **TINY)
    mix = harness.load_traffic(cell, ROOT)
    devs = jax.devices()[:int(cell["chips"])]
    return harness.system_module(config, ROOT).build(config, mix, devs,
                                                     lambda m: None)


@pytest.fixture(scope="module")
def solved():
    """Each cell set up from one seed, its operand A_base read back, and
    its first solve of the window (the same shift in both)."""
    from slate_tpu.core import layout
    out = {}
    for name in (GRID_CELL, ONE_CELL):
        system = _system(name)
        system.setup(SEED, 1e-6)
        p, q = system.grid_shape
        t = system.n // system.nb
        # set-up's warm solve hands back A with a zero shift: A_base
        a = np.asarray(layout.cyclic_to_canonical(system.a, t, t, p, q))
        system.window(0.01)
        system.release()
        out[name] = (system, a)
    return out


def test_grid_cell_is_the_one_chip_cell_on_four_chips():
    grid = harness.find_cell(SPEC, GRID_CELL)
    one = harness.find_cell(SPEC, ONE_CELL)
    assert grid["chips"] == 4 and grid["traffic"] == one["traffic"]
    cg = harness.load_config(SPEC, grid, ROOT)
    c1 = harness.load_config(SPEC, one, ROOT)
    assert cg["grid"] == {"4": [2, 2]}
    for key in ("system", "n", "nb", "dtype", "matmul_precision"):
        assert cg[key] == c1[key], key
    assert cg["matrix"]["posv"] == c1["matrix"]["posv"]
    assert cg["limits"]["posv"] == c1["limits"]["posv"]


def test_grid_operands_are_the_one_chip_tiles(solved):
    grid, a_grid = solved[GRID_CELL]
    one, a_one = solved[ONE_CELL]
    assert grid.grid_shape == (2, 2) and one.grid_shape == (1, 1)
    assert a_grid.shape == a_one.shape == (4, 4, 64, 64)
    assert np.array_equal(a_grid, a_one)


def test_grid_solve_agrees_with_one_chip_and_reference(solved):
    """Both programs solve ``A_base + s_0 I`` for the seed's first shift,
    and each solve passes the f64 host reference under the cell's limit.
    Each may round every product's operands to bf16 (one pass, XLA's
    default on the TPU; exact on the CPU): a relative backward error of
    up to 2**-9 a product.  A lies within 0.05 of 4 I at this order (0.001
    times a symmetric Gaussian matrix, spectral radius ~2 sqrt(2n)), so
    its condition number is under 1.03 and x carries that error nearly
    unscaled: the two answers agree to 2 * 2**-9 = 2**-8 of ||x||_inf."""
    (grid, _), (one, _) = solved[GRID_CELL], solved[ONE_CELL]
    s_grid, x_grid, ok_grid = grid.records[0]
    s_one, x_one, ok_one = one.records[0]
    assert s_grid == s_one and ok_grid and ok_one
    err = np.abs(x_grid.astype(np.float64) - x_one).max()
    assert err <= 2.0**-8 * np.abs(x_one).max()
    for system in (grid, one):
        attempted, failed, checks = system.check()
        assert attempted >= 1 and failed == 0
        assert checks and all(c.ok() for c in checks), checks


# ------------------------------------------------ the collective reader


def _ms(t):
    return t * 1e6


def _synthetic(collective: bool):
    """Two chips, each running the solve program twice in a 100-ms
    window; the executions last 20 ms and each holds, in ms from its
    start: a matmul over [0, 8], a collective over [6, 14] (2 ms beside
    the matmul, 6 ms alone) and another op over [14, 20]."""
    devices = {}
    for dev in (0, 1):
        mods, ops = [], []
        for t0 in (10.0, 50.0):
            mods.append([_ms(t0), _ms(20.0), "jit_solve(1)"])
            ops.append([_ms(t0), _ms(8.0), "fusion.1", "matmul"])
            ops.append([_ms(t0 + 6), _ms(8.0), "all-reduce.2",
                        "collective" if collective else "other"])
            ops.append([_ms(t0 + 14), _ms(6.0), "copy.3", "other"])
        devices[dev] = {"modules": mods, "ops": ops}
    return {"devices": devices, "dispatch": [_ms(1.0)],
            "host": [[0.0, _ms(100.0), "bench.window"]]}


def _read(tr, solves=2):
    red = trace.reduce(tr, window_span="bench.window")
    read = harness.reader("collective_exposed_ms.solve", ROOT)
    return read({"trace": red, "system": {"solves": solves}, "peak": None})


def test_collective_reader_on_known_intervals():
    """Per chip: 12 ms exposed over 44 ms of op time (16 + 16 + 12, each
    op its own time), 40 ms busy: 12 / 44 * 40 / 2 solves ms a solve."""
    tr = _synthetic(collective=True)
    red = trace.reduce(tr, window_span="bench.window")
    for d in red["devices"].values():
        assert d["modules_covered"] == 2
        assert d["collective_exposed_s"] == pytest.approx(12e-3)
    assert _read(tr) == pytest.approx(12 / 44 * 40 / 2)
    assert _read(tr, solves=4) == pytest.approx(12 / 44 * 40 / 4)


def test_collective_reader_is_silent_without_collectives():
    assert _read(_synthetic(collective=False)) is None
    assert _read(_synthetic(collective=True), solves=0) is None


def test_collective_reader_is_listed_for_the_grid_cell_only():
    entry = next(m for m in SPEC["per_layer"]
                 if m["name"] == "collective_exposed_ms.solve")
    assert entry["workloads"] == [GRID_CELL]
    assert entry["moves"] == "solve_s"
