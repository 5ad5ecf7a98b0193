"""The comparison that decides ``correct`` has to be able to fail.

On the CPU at small sizes: the control (the plain reference at a lower
precision than the configuration states) reads well past what the
reference at the stated precision reads on one of the cell's numbers,
and a run whose timed path is broken underneath comes out not correct:
a solve that returns its input unchanged, an answer altered where it is
made.
"""

import json
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import control, harness  # noqa: E402

SPEC = harness.load_spec(ROOT)
TINY = {"n": 256, "nb": 64}


def _cells(system):
    out = []
    for cell in SPEC["workloads"]:
        config = harness.load_config(SPEC, cell, ROOT)
        if config["system"] == system:
            out.append((cell, config, harness.load_traffic(cell, ROOT)))
    return out


DENSE = _cells("dense_solve")


@pytest.mark.parametrize("cell,config,mix", DENSE,
                         ids=[c[0]["name"] for c in DENSE])
def test_dense_control_fails_where_stated_precision_passes(cell, config,
                                                           mix):
    """The residuals scale with n and the matrix's conditioning, so a
    limit set at the cell's n does not carry to this size: on one of the
    numbers the cell compares, on every seed, the control has to read
    three times what the stated precision reads, as the limit's two
    readings must."""
    small = dict(config, n=512, nb=128)
    keys = [f"{k}_max" for k in config["limits"][mix["op"]]]
    ratios = {k: [] for k in keys}
    for seed in (1, 2, 3):
        stated = control.dense_readings(small, mix, seed, "default", 2)
        low = control.dense_readings(small, mix, seed, "fp8", 2)
        for k in keys:
            ratios[k].append(low[k] / stated[k])
    assert any(min(r) > 3 for r in ratios.values()), ratios


# ------------------------------------------------ faults under a run


@pytest.fixture
def tree(tmp_path, monkeypatch):
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in spec["configs"]:
        path = tmp_path / entry["file"]
        config = json.loads(path.read_text())
        if config["system"] == "dense_solve":
            config.update(TINY)
        path.write_text(json.dumps(config))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(harness, "prepare_runtime", lambda jax, root: None)
    monkeypatch.setenv("SLATE_TUNE_CACHE", str(tmp_path / "plans.json"))
    return tmp_path


def _run(tree, cell, seconds=0.5):
    return harness.run_cell(cell, 11, seconds, False, time.perf_counter(),
                            root=tree, require_tpu=False, log=lambda m: None)


def _patch_solution(monkeypatch, op, alter):
    """Wrap the public driver so that its X passes through ``alter``."""
    import slate_tpu as st
    from slate_tpu.core.storage import TileStorage
    original = getattr(st, op)

    def broken(A, B, opts=None):
        F, X, h = original(A, B, opts)
        s = X.storage
        data = alter(s.data, B.storage.data)
        return F, st.Matrix(TileStorage(data, s.m, s.n, s.mb, s.nb,
                                        s.grid)), h

    monkeypatch.setattr(st, op, broken)


def _some_check_fails(out):
    return any(not c["value"] <= c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("cell,config,mix", DENSE,
                         ids=[c[0]["name"] for c in DENSE])
def test_dense_run_is_correct_unbroken(tree, cell, config, mix):
    out = _run(tree, cell["name"])
    assert out["correct"] is True, out["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
@pytest.mark.parametrize("cell,config,mix", DENSE,
                         ids=[c[0]["name"] for c in DENSE])
def test_dense_fault_makes_run_incorrect(tree, monkeypatch, cell, config,
                                         mix, fault):
    if fault == "unchanged":      # the solve hands back its input
        alter = lambda x, b: b  # noqa: E731
    else:                         # one entry of x moved by a tenth of |x|
        alter = lambda x, b: x.at[0, 0, 0, 0].add(  # noqa: E731
            0.1 * abs(x).max())
    _patch_solution(monkeypatch, mix["op"], alter)
    out = _run(tree, cell["name"])
    assert out["correct"] is False
    assert _some_check_fails(out)
