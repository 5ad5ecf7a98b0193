"""The benchmark harness on the CPU: discovery by name, seeds, peaks, and
the refusal to run without a chip."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import generator, harness, reference  # noqa: E402
from benchmark.systems import dense_solve  # noqa: E402

SPEC = harness.load_spec(ROOT)
CELLS = [c["name"] for c in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_finds_its_files(name):
    cell = harness.find_cell(SPEC, name)
    config = harness.load_config(SPEC, cell, ROOT)
    harness.load_traffic(cell, ROOT)
    assert hasattr(harness.system_module(config, ROOT), "build")
    e2e = harness.metrics_for(SPEC, cell, "end_to_end")
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    layer = harness.metrics_for(SPEC, cell, "per_layer")
    assert layer
    for m in layer:
        assert m["moves"] in names
        assert callable(harness.reader(m["name"], ROOT))


def test_unknown_names_are_errors():
    with pytest.raises(harness.SpecError):
        harness.find_cell(SPEC, "no.such.cell")
    with pytest.raises(harness.SpecError):
        harness.reader("no_such_metric", ROOT)


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v9 imaginary", "cpu"])
def test_peaks_table_refuses_unknown_kinds(kind):
    if kind == "TPU v5 lite":
        peak = harness.peaks(kind, ROOT)
        assert peak["flops_per_s"] == 197e12
        assert peak["bytes_per_s"] == 819e9
    else:
        with pytest.raises(harness.SpecError, match="not in"):
            harness.peaks(kind, ROOT)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_shifts_same_seed_same_work(seed):
    """Every seed draws the same number of shifts from the same range, so
    every seed does the same work; the seed alone picks the values."""
    for cell in SPEC["workloads"]:
        mix = harness.load_traffic(cell, ROOT)
        lo, hi = mix["shift"]
        a = [generator.shift(mix, seed, i) for i in range(64)]
        assert a == [generator.shift(mix, seed, i) for i in range(64)]
        assert a != [generator.shift(mix, seed + 1, i) for i in range(64)]
        assert all(lo <= s < hi for s in a)
        assert len(set(a)) == len(a)


@pytest.mark.parametrize("seed", [5, 2**33 + 5])
def test_dense_operands_same_seed_same_matrix(seed):
    from benchmark.systems import dense_solve as ds
    mk = lambda s: np.asarray(ds.canonical_tiles(  # noqa: E731
        ds.operand_keys(s)[0], 256, 64, "general"))
    a = mk(seed)
    assert a.dtype == np.float32
    assert np.array_equal(a, mk(seed))
    assert not np.array_equal(a, mk(seed + 1))
    assert -0.5 <= a.min() and a.max() <= 0.5
    spd = ds.dense_from_tiles(np.asarray(ds.canonical_tiles(
        ds.operand_keys(seed)[0], 256, 64, "spd")))
    assert np.array_equal(spd, spd.T)
    assert np.linalg.eigvalsh(spd.astype(np.float64)).min() > 0
    mix = {"shift": [0.0, 1.0]}
    assert generator.shift(mix, seed, 4) == generator.shift(mix, seed, 4)
    assert generator.shift(mix, seed, 4) != generator.shift(mix, seed, 5)


@pytest.mark.parametrize("scale", [1.0, 1e-3, 1e3])
def test_residual_measures_on_known_answers(scale):
    """Both measures on a 2x2 system with known residuals: exact answers
    read 0, and an x whose error lies along A's weak direction reads
    small against ||A|| ||x|| and large against ||b||."""
    a = scale * np.array([[1.0, 1.0], [1.0, 1.0 + 1e-6]])
    b = scale * np.array([[1.0, 0.5], [1.0, 0.5]])
    x = np.array([[1.0, 0.5], [0.0, 0.0]])
    x_off = x + np.array([[1e3], [-1e3]])
    for xx in (x, x_off):
        r = b - a @ xx
        anorm = np.full(2, np.abs(a).sum(axis=1).max())
        got = reference.residual_measures(anorm, xx, b, r)
        want_rb = np.abs(r).max(axis=0) / np.abs(b).max(axis=0)
        np.testing.assert_allclose(got["residual_over_b"], want_rb)
        want_hpl = np.abs(r).max(axis=0) / (
            reference.EPS32 * (anorm * np.abs(xx).max(axis=0)
                               + np.abs(b).max(axis=0)) * 2)
        np.testing.assert_allclose(got["hpl_scaled_residual"], want_hpl)
    assert np.all(got["residual_over_b"] > 1e-4)
    assert np.all(got["hpl_scaled_residual"] * reference.EPS32 * 2
                  < got["residual_over_b"] / 1000)
    bad = reference.residual_measures(anorm, x, np.zeros_like(b), r)
    assert np.all(np.isinf(bad["residual_over_b"]))


def test_host_phases_name_the_stalled_phase():
    host = [(0.001, 0.1)] * 20 + [(0.06, 0.1), (0.001, 0.16), (0.001, 0.11)]
    lines = dense_solve.host_phases(host, [0.002, 0.003])
    assert any("2 (1 in dispatch, 1 in wait)" in ln for ln in lines), lines
    assert any("collector pauses in the window: 2" in ln for ln in lines)


def test_run_without_a_chip_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


# ------------------------------------------------ adding a cell by files


TINY = {"n": 256, "nb": 64}


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """A copy of BENCHMARK.json and the benchmark's directory, with the
    harness's process-wide settings kept out of this test process."""
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    monkeypatch.setattr(harness, "prepare_runtime", lambda jax, root: None)
    monkeypatch.setenv("SLATE_TUNE_CACHE", str(tmp_path / "plans.json"))
    return tmp_path


def test_added_files_add_a_cell_without_edits(tree):
    """A new configuration, mix, metric and cell are files plus entries:
    the harness finds and runs them and no existing file changes."""
    before = {p: p.read_bytes() for p in (tree / "benchmark").rglob("*")
              if p.is_file()}
    spec = json.loads((tree / "BENCHMARK.json").read_text())
    dense = next(c for c in spec["configs"]
                 if json.loads((tree / c["file"]).read_text())["system"]
                 == "dense_solve")
    config = json.loads((tree / dense["file"]).read_text())
    config.update(TINY)
    (tree / "benchmark/configs/tiny_added.json").write_text(
        json.dumps(config))
    (tree / "benchmark/traffic/added_closed.json").write_text(json.dumps(
        {"loop": "closed", "op": "posv", "nrhs": 1, "shift": [0.5, 0.75]}))
    (tree / "benchmark/metrics/solves_added.count.py").write_text(
        "def read(ctx):\n    return float(ctx['system']['solves'])\n")
    spec["configs"].append(dict(dense, name="tiny_added",
                                file="benchmark/configs/tiny_added.json"))
    spec["workloads"].append({"name": "added.cell", "config": "tiny_added",
                              "traffic": "added_closed", "chips": 1,
                              "why": "added by files"})
    for m in spec["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append("added.cell")
    spec["per_layer"].append({"name": "solves_added.count", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "solve_s",
                              "workloads": ["added.cell"]})
    (tree / "BENCHMARK.json").write_text(json.dumps(spec))

    for trace in (False, True):
        out = harness.run_cell("added.cell", 3, 0.5, trace,
                               time.perf_counter(), root=tree,
                               require_tpu=False, log=lambda m: None)
        assert out["correct"] is True and out["failed"] == 0
        assert out["attempted"] >= 1
        assert list(out)[-1] == "checks"
        if trace:
            assert out["metrics"]["solves_added.count"]["value"] == \
                out["attempted"]
        else:
            assert set(out["metrics"]) == {"solve_s", "setup_s"}
    for path, data in before.items():
        assert path.read_bytes() == data
