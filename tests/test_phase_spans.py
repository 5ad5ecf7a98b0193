"""The one-chip gesv and posv, and the benchmark's posv on a 2x2 grid,
name their phases in the compiled program.

Each ``util/trace.span`` opens a ``jax.named_scope``, which reaches the
compiled HLO as the instructions' ``op_name`` metadata; the benchmark
joins a device trace's op events to those names (``benchmark/phases.py``).
A named scope changes metadata only: the program compiled with every
span a null context is the same text once metadata is stripped.
"""

import contextlib
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.core.storage import TileStorage
from slate_tpu.util import trace as _trace

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import phases as _phases  # noqa: E402
from benchmark.systems import dense_solve as _dense  # noqa: E402

N, NB = 512, 128
LAYOUT = ["slate.layout/to_dense", "slate.layout/from_dense"]
PHASES = {
    "gesv": ["slate.getrf/panel", "slate.getrf/swap", "slate.getrf/trsm",
             "slate.getrf/gemm", "slate.getrf/health",
             "slate.getrs/permute"] + LAYOUT,
    "posv": ["slate.potrf/herk", "slate.potrf/panel",
             "slate.potrf/health"] + LAYOUT,
    "posv_2x2": ["slate.potrf/panel", "slate.potrf/bcast",
                 "slate.potrf/herk", "slate.potrf/health",
                 "slate.trsm/bcast", "slate.trsm/update",
                 "slate.potrs/health", "slate.layout/to_dense"],
}
GRID_OP = "posv_2x2"
COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
               "all-to-all", "reduce-scatter")
_OPCODE = re.compile(r"^\s*(?:ROOT )?%?(?P<inst>[^\s=]+) = .*? "
                     r"(?P<op>[a-z][a-z0-9-]*)\(")
# metadata: each instruction's op_name and source, and the tables of
# source files and stack frames that the instructions' metadata points to
_META = re.compile(r", metadata=\{[^}]*\}|^(?:FileNames|FunctionNames|"
                   r"FileLocations|StackFrames)\n|^\d+ .*\n", re.M)


@pytest.fixture(autouse=True)
def _fresh_sentinel():
    """Each test compiles the same signature afresh on purpose."""
    obs.reset_sentinel()
    yield
    obs.reset_sentinel()


def _solve(op):
    """The benchmark's one-chip solve program: A and b as device tiles,
    the public driver under ErrorPolicy.Info, x back as a dense vector."""
    grid = st.Grid(1, 1)
    opts = {st.Option.ErrorPolicy: st.ErrorPolicy.Info}
    if op == "gesv":
        opts[st.Option.MethodLU] = st.MethodLU.PartialPiv

    def solve(a, b):
        A = st.Matrix(TileStorage(a, N, N, NB, NB, grid),
                      kind=st.TileKind.UserOwned)
        B = st.Matrix(TileStorage(b, N, 1, NB, NB, grid),
                      kind=st.TileKind.UserOwned)
        if op == "gesv":
            _, X, h = st.gesv(A, B, opts)
        else:
            _, X, h = st.posv(A.hermitian(st.Uplo.Lower), B, opts)
        return X.to_dense()[:, 0], h.ok

    return solve


def _compiled_text(op) -> str:
    if op == GRID_OP:
        return _grid_text()
    t = N // NB
    a = jax.ShapeDtypeStruct((t, t, NB, NB), jnp.float32)
    b = jax.ShapeDtypeStruct((t, 1, NB, NB), jnp.float32)
    return jax.jit(_solve(op)).lower(a, b).compile().as_text()


def _grid_system():
    """The benchmark's posv adapter (``systems/dense_solve.py``) on a 2x2
    grid of four CPU devices."""
    config = {"n": N, "nb": NB, "grid": {"4": [2, 2]},
              "matrix": {"posv": "spd"}}
    system = _dense.build(config, {"op": "posv"}, jax.devices()[:4],
                          lambda msg: None)
    return system, system.grid()


def _grid_text() -> str:
    """The benchmark's timed 2x2 posv program: the diagonal-tile write,
    the distributed factor and sweeps, x gathered to a dense vector."""
    system, grid = _grid_system()
    t = N // NB
    tiles = grid.tile_sharding()
    rep = grid.replicated_sharding()
    args = (jax.ShapeDtypeStruct((t, t, NB, NB), jnp.float32,
                                 sharding=tiles),
            jax.ShapeDtypeStruct((t, NB, NB), jnp.float32, sharding=rep),
            jax.ShapeDtypeStruct((t, 2, NB, NB), jnp.float32,
                                 sharding=tiles),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=rep))
    return jax.jit(system.solve_fn(grid), donate_argnums=0).lower(
        *args).compile().as_text()


def _op_names(text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("op", sorted(PHASES))
def test_every_phase_reaches_the_compiled_program(op):
    names = ["/" + n + "/" for n in _op_names(_compiled_text(op))]
    for phase in PHASES[op]:
        assert any("/" + phase + "/" in n for n in names), phase


@pytest.mark.parametrize("op", sorted(PHASES))
def test_spans_change_metadata_only(op, monkeypatch):
    spanned = _compiled_text(op)
    real = _trace.span
    for mod in list(sys.modules.values()):
        if (getattr(mod, "__name__", "").startswith("slate_tpu")
                and getattr(mod, "span", None) is real):
            monkeypatch.setattr(mod, "span",
                                lambda name: contextlib.nullcontext())
    bare = _compiled_text(op)
    assert not any("/" + p + "/" in "/" + n + "/"
                   for n in _op_names(bare) for p in PHASES[op])
    assert _META.sub("", bare) == _META.sub("", spanned)


@pytest.mark.parametrize("op", sorted(PHASES))
def test_spanned_solve_still_solves(op):
    """The spans sit on the path the benchmark times: the answer holds."""
    rng = np.random.default_rng(7)
    g = rng.standard_normal((N, N)).astype(np.float32)
    a = (0.01 * (g + g.T) + 4 * np.eye(N, dtype=np.float32)
         if op.startswith("posv")
         else g + N ** 0.5 * np.eye(N, dtype=np.float32))
    b = rng.standard_normal((N, 1)).astype(np.float32)
    system, grid = _grid_system() if op == GRID_OP else (None, st.Grid(1, 1))
    at = TileStorage.from_dense(jnp.asarray(a), NB, NB, grid).data
    bt = TileStorage.from_dense(jnp.asarray(b), NB, NB, grid).data
    if system is not None:
        diag = jnp.stack([a[i:i + NB, i:i + NB] for i in range(0, N, NB)])
        _, x, ok = jax.jit(system.solve_fn(grid))(at, diag, bt,
                                                  jnp.float32(0.0))
    else:
        x, ok = jax.jit(_solve(op))(at, bt)
    assert bool(ok)
    r = a.astype(np.float64) @ np.asarray(x, np.float64) - b[:, 0]
    assert np.abs(r).max() <= 1e-3 * np.abs(b).max()


def test_every_collective_of_the_grid_program_is_in_a_phase():
    """Each collective instruction of the 2x2 program (the masked-psum
    broadcasts, the sweeps' broadcasts, the health reductions, x's
    gather) lies in a named phase, by its own ``op_name`` or the one it
    inherits, so the benchmark's phase table can place its time."""
    text = _grid_text()
    scopes = _phases.instruction_scopes(text)
    found = {}
    for line in text.splitlines():
        m = _OPCODE.match(line)
        if m and m["op"].startswith(COLLECTIVES):
            name = scopes[m["inst"]][0]
            phase = _phases.phase_of(name, PHASES[GRID_OP])
            assert phase in PHASES[GRID_OP], (m["inst"], name)
            found.setdefault(phase, set()).add(m["op"])
    for phase in ("slate.potrf/panel", "slate.potrf/bcast",
                  "slate.trsm/bcast", "slate.potrs/health"):
        assert "all-reduce" in found.get(phase, ()), (phase, found)
