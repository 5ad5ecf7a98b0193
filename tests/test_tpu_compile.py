"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

Interpret mode runs every Pallas kernel on the CPU, but only the chip's
compiler sees its tiling and VMEM limits.  These tests compile each
kernel the dispatch seams can route to, at the widths the seams admit,
with the installed TPU compiler against a described ``v5e:2x2``
topology (on-chip-measurement guide section 2) — each refusal they
guard was found that way and repaired in the kernel or its seam.

The topology is described inside a module fixture, never at import, so
every xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.  ``chip_smoke.py`` on a host
without a TPU must fail before it compiles anything.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache without the chip: keep these compiles out of it.
    # The chip runs with x64 off (conftest turns it on for the CPU
    # tests), so compile as it would.
    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_enable_x64", prev[1])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.mark.parametrize("n", [512, 1024])
def test_chol_tile(one_chip, n):
    from slate_tpu.internal.pallas_chol import chol_tile_pallas
    c = _compile(chol_tile_pallas, one_chip, ((n, n), F32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("nb", [256, 512])
def test_chol_panel_fused(one_chip, nb):
    """nb=512, the seam's widest, needs the kernels' VMEM budget."""
    from slate_tpu.internal.pallas_chol import chol_panel_fused
    _compile(chol_panel_fused, one_chip, ((4096, nb), F32),
             ((4096, 1024), F32), ((1024, nb), F32))


@pytest.mark.parametrize("nb", [256, 512])
def test_lu_panel_fused(one_chip, nb):
    from slate_tpu.internal.pallas_lu import lu_panel_fused
    _compile(lu_panel_fused, one_chip, ((4096, nb), F32))


def test_lu_select(one_chip):
    from slate_tpu.internal.pallas_lu import lu_select_pallas
    _compile(lu_select_pallas, one_chip, ((4096, 512), F32))


@pytest.mark.parametrize("mm,w", [(8192, 256), (4096, 512)])
def test_qr_panel_at_the_seams_largest(one_chip, mm, w):
    """The largest panels _qr_panel_ok admits compile; the next size up
    is not admitted (16384 x 128 and 8192 x 512 exceed the budget)."""
    from slate_tpu import tune
    from slate_tpu.internal import qr
    from slate_tpu.internal.pallas_qr import qr_panel_pallas
    with tune.plan_override("geqrf_panel", tune.TilePlan("pallas", w, 8)):
        assert qr._qr_panel_ok(F32, mm, w)
        assert not qr._qr_panel_ok(F32, 2 * mm, w)
    _compile(qr_panel_pallas, one_chip, ((mm, w), F32))


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("op", ["batch_potrf", "batch_getrf",
                                "batch_geqrf"])
def test_batched_ragged_panels(one_chip, op, dtype):
    """The serving rung's ragged kernels at bucket 256; bf16 storage
    needs every in-kernel dot to take one operand dtype."""
    import functools

    from slate_tpu.internal import batched
    rows = 256 + 64 if op == "batch_geqrf" else 256
    _compile(functools.partial(getattr(batched, op), nb=128), one_chip,
             ((48, rows, 256), dtype), ((48,), I32))


@pytest.mark.parametrize("op", ["solve", "chol_solve",
                                "least_squares_solve"])
def test_bf16_serving_program(one_chip, monkeypatch, op):
    """The certified bf16 rung's whole serving program at bucket 256,
    traced at the serving product precision (HIGHEST): the bf16 Pallas
    panels inside must still pin their dots to what Mosaic accepts."""
    import slate_tpu as st
    from slate_tpu import tune
    from slate_tpu.serve import batched
    # this process's backend is the CPU; compile the kernels for the chip
    monkeypatch.setattr(batched, "pallas_interpret", lambda: False)
    fn = batched.make_batched(op, {st.Option.Precision: st.Precision.Bf16})
    with tune.plan_override(batched.RAGGED_OPS[op],
                            tune.TilePlan("pallas", 128, 8)):
        c = _compile(fn, one_chip, ((16, 256, 256), F32),
                     ((16, 256, 16), F32), ((16,), I32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("rows", [8192, 16384])
def test_pivoted_panel(one_chip, rows):
    """XLA's LU custom call up to XLA_LU_MAX_ROWS, the recursive panel
    past it (XLA's refuses 16384 x 128 for VMEM)."""
    from slate_tpu.internal import getrf
    assert (rows > getrf.XLA_LU_MAX_ROWS) == (rows == 16384)
    _compile(getrf.panel_lu, one_chip, ((rows, 512), F32))


def test_chip_smoke_refuses_a_host_without_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=120)
    assert res.returncode != 0
    assert res.stdout == ""          # not even the set-up line
    assert "needs a TPU" in res.stderr
