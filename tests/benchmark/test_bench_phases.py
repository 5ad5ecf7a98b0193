"""Device time per named phase: the join of a trace's op events with the
compiled program's op_name metadata (``benchmark/phases.py``), on
hand-made events and on traces recorded on a v5e chip with their
programs' text (``benchmark/data``)."""

import gzip
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import flops, harness, phases, trace  # noqa: E402

MS = 1e6  # ns
DATA = ROOT / "benchmark" / "data"
SCOPE = "jit(solve)/slate.gesv/slate.getrf/"

HLO = f"""HloModule jit_solve, is_scheduled=true, entry_computation_layout={{(f32[8]{{0}})->f32[8]{{0}}}}

FileNames
1 "solve.py"

%fused_computation.1 (p: f32[8]) -> f32[8] {{
  %p = f32[8]{{0}} parameter(0)
  ROOT %neg.1 = f32[8]{{0}} negate(%p), metadata={{op_name="{SCOPE}slate.getrf/panel/neg" stack_frame_id=3}}
}}

%body.1 (p.1: f32[8]) -> f32[8] {{
  %p.1 = f32[8]{{0}} parameter(0)
  ROOT %dynamic-update-slice.7 = f32[8]{{0}} dynamic-update-slice(%p.1, %p.1)
}}

ENTRY %main.9 (a.1: f32[8]) -> (f32[8], f32[8]) {{
  %a.1 = f32[8]{{0}} parameter(0)
  %while.1 = f32[8]{{0}} while(%a.1), condition=%c, body=%body.1, metadata={{op_name="{SCOPE}slate.getrf/panel/gather"}}
  %fusion.2 = f32[8]{{0}} fusion(%a.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{SCOPE}slate.getrf/panel/slate.layout/to_dense/transpose"}}
  %copy.8 = f32[8]{{0}} copy(%while.1)
  %fusion.3 = f32[8]{{0}} fusion(%copy.8), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{SCOPE}slate.getrf/gemm/dot_general"}}
  %copy.4 = f32[8]{{0}} copy(%a.1), metadata={{op_name="jit(solve)/slate.gesv/slate.getrs/slate.trsm/reshape"}}
  %fusion.5 = f32[8]{{0}} fusion(%a.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(solve)/scatter"}}
  %copy.6 = f32[8]{{0}} copy(%a.1)
  %copy.11 = f32[8]{{0}} copy(%fusion.3)
  ROOT %tuple.12 = (f32[8]{{0}}, f32[8]{{0}}) tuple(%copy.6, %copy.11)
}}
"""


def _tr(second_program=True, unjoined=True):
    """Two executions of ``jit_solve``: a loop holding a layout pass, a
    reshape and a write of the loop's own body, then a copy the compiler
    made, a matmul and the adapter's scatter; then an output copy, a
    copy with no scope at all and an op the text lacks.  Between them,
    one execution of another program whose op shares no name with the
    text."""
    mods = [[1 * MS, 10 * MS, "jit_solve(123)"],
            [13 * MS, 5 * MS, "jit_solve(123)"]]
    ops = [[1 * MS, 8 * MS, "while.1 f32[8] while", "other"],
           [2 * MS, 3 * MS, "fusion.2 f32[8] fusion Loop", "other"],
           [6 * MS, 1 * MS, "copy.4 f32[8] copy", "other"],
           [7.5 * MS, 1 * MS, "dynamic-update-slice.7 f32[8] "
            "dynamic-update-slice", "other"],
           [9 * MS, 0.5 * MS, "copy.8 f32[8] copy", "other"],
           [9.5 * MS, 0.5 * MS, "fusion.3 f32[8] fusion Output", "matmul"],
           [10 * MS, 0.5 * MS, "fusion.5 f32[8] fusion Loop", "other"],
           [13 * MS, 1 * MS, "copy.11 f32[8] copy", "other"],
           [14 * MS, 2 * MS, "copy.6 f32[8] copy", "other"]]
    if second_program:
        mods.append([12 * MS, 0.5 * MS, "jit_convert_element_type(9)"])
        ops.append([12 * MS, 0.4 * MS, "convert.1 f32[] convert", "other"])
    ops.append([16 * MS, 2 * MS, "fusion.9 f32[8] fusion Loop" if unjoined
                else "fusion.5 f32[8] fusion Loop", "other"])
    return {"devices": {0: {"modules": mods, "ops": ops}},
            "host": [[0.5 * MS, 19.5 * MS, "bench.window"],
                     [0.6 * MS, 10.6 * MS, "bench.solve"],
                     [0.7 * MS, 0.3 * MS, "bench.dispatch"],
                     [12.6 * MS, 6 * MS, "bench.solve"]],
            "dispatch": [0.9 * MS, 12.8 * MS]}


def test_module_name_and_instruction_scopes():
    assert phases.module_name(HLO) == "jit_solve"
    scopes = phases.instruction_scopes(HLO)
    assert scopes["neg.1"][0].endswith("slate.getrf/panel/neg")
    assert scopes["fusion.5"] == ("jit(solve)/scatter", False)
    assert scopes["copy.6"] == ("", False) and scopes["a.1"] == ("", False)
    assert "1" not in scopes          # the stack-frame tables hold none
    with pytest.raises(ValueError):
        phases.module_name("ENTRY %main")


@pytest.mark.parametrize("inst,scope", [
    ("dynamic-update-slice.7", "slate.getrf/panel/gather"),  # its loop's
    ("copy.8", "slate.getrf/gemm/dot_general"),     # what reads it
    ("copy.11", "slate.getrf/gemm/dot_general"),    # what it reads
])
def test_compiler_made_instructions_inherit_a_scope(inst, scope):
    name, inherited = phases.instruction_scopes(HLO)[inst]
    assert name == SCOPE + scope and inherited


@pytest.mark.parametrize("op_name,phase", [
    (SCOPE + "slate.getrf/panel/while/body/dynamic_slice",
     "slate.getrf/panel"),
    (SCOPE + "slate.getrf/panel/slate.layout/to_dense/transpose",
     "slate.layout/to_dense"),
    ("jit(solve)/slate.posv/slate.potrf/slate.potrf/herk/dot_general",
     "slate.potrf/herk"),
    ("jit(solve)/slate.gesv/slate.getrs/slate.getrs/permute/gather",
     "slate.getrs/permute"),
    ("jit(solve)/slate.gesv/slate.getrs/slate.trsm/reshape",
     "slate.trsm/other"),
    ("jit(solve)/slate.gesv/reduce_and", "slate.gesv/other"),
    (SCOPE + "slate.getrf/bcast_ahead/all-gather", "slate.getrf/other"),
    ("jit(solve)/scatter", "unscoped"),
    ("", "unscoped"),
])
def test_innermost_span_wins(op_name, phase):
    assert phases.phase_of(op_name) == phase


def test_table_joins_own_times_and_leaves_other_programs_out():
    tab = phases.table(_tr(), HLO)
    d = tab["devices"][0]
    got = {b: (pytest.approx(s * 1e3), n) for b, (s, n) in d["phases"].items()}
    # the loop [1, 9] holds [2, 5], [6, 7] and [7.5, 8.5]: 5 ms of its own
    assert got == {"slate.getrf/panel": (4.0, 2),
                   "slate.layout/to_dense": (3.0, 1),
                   "slate.trsm/other": (1.0, 1),
                   "slate.getrf/gemm": (2.0, 3),
                   "unscoped": (2.5, 2),
                   "unjoined": (2.0, 1)}
    assert d["executions"] == 2
    assert d["op_s"] == pytest.approx(14.5e-3)
    assert d["joined_s"] == pytest.approx(12.5e-3)
    assert d["inherited_s"] == pytest.approx(2.5e-3)
    assert d["instructions"]["copy.6 f32[8] copy"] == [pytest.approx(2e-3), 1]
    assert "convert.1 f32[] convert" not in d["instructions"]
    assert tab["spans"] == ["slate.getrf/gemm", "slate.getrf/panel",
                            "slate.layout/to_dense", "slate.trsm/other"]
    assert tab["top"]["unjoined"][0][0].startswith("fusion.9")
    assert [k for k, _ in tab["top"]["unscoped"]] == [
        "copy.6 f32[8] copy", "fusion.5 f32[8] fusion Loop"]
    assert phases.events_per_execution(tab)["slate.getrf/gemm"] == 1.5


def test_covered_follows_reduce():
    for tr in (_tr(), _tr(second_program=False, unjoined=False)):
        red = trace.reduce(tr, devices=[0])
        execs, ops, own = phases.covered(tr, devices=[0])[0]
        assert len(execs) == red["devices"][0]["modules_covered"]
        assert len(ops) == red["devices"][0]["ops"]


def test_phases_sum_to_busy_per_solve():
    tr = _tr()
    red = trace.reduce(tr, devices=[0])
    ms = phases.ms_by_phase(phases.table(tr, HLO), red, 2)
    # busy [1, 11], [12, 12.5], [13, 18]: 15.5 ms over two solves
    assert sum(ms.values()) == pytest.approx(7.75)
    assert ms["slate.getrf/panel"] == pytest.approx(4 / 14.5 * 7.75)


def test_ms_per_solve_scales_as_nonmatmul_does():
    """With one program in the window, the phases share the denominator
    of ``nonmatmul_ms.solve`` (all recorded op time) and its scale (busy
    time per solve)."""
    tr = _tr(second_program=False, unjoined=False)
    red = trace.reduce(tr, devices=[0])
    tab = phases.table(tr, HLO)
    d, r = tab["devices"][0], red["devices"][0]
    assert d["op_s"] == pytest.approx(
        r["matmul_s"] + r["other_s"] + r["collective_s"])
    nonmatmul = harness.reader("nonmatmul_ms.solve", ROOT)(
        {"trace": red, "system": {"solves": 2}})
    assert nonmatmul == pytest.approx(1e3 * r["other_s"] / d["op_s"]
                                      * red["busy_s"] / 2)
    ms = phases.ms_by_phase(tab, red, 2)
    assert sum(ms.values()) == pytest.approx(1e3 * red["busy_s"] / 2)
    assert ms["slate.trsm/other"] == pytest.approx(
        1e3 * 1e-3 / d["op_s"] * red["busy_s"] / 2)


def test_ms_per_solve_is_none_without_the_spans():
    tr = _tr()
    red = trace.reduce(tr, devices=[0])
    tab = phases.table(tr, HLO)
    assert phases.ms_per_solve(tab, red, 2, ["slate.getrf/swap"]) is None
    assert phases.ms_per_solve(tab, red, 0,
                               phases.METRICS["panel_ms.solve"]) is None
    # a program without the phase spans (driver scopes alone)
    bare = HLO.replace("slate.getrf/panel/", "").replace(
        "slate.getrf/gemm/", "").replace("slate.layout/to_dense/", "")
    assert "slate.getrf/" not in bare.replace(SCOPE, "")
    tab = phases.table(tr, bare)
    for names in phases.METRICS.values():
        assert phases.ms_per_solve(tab, red, 2, names) is None
    assert phases.ms_per_solve(phases.table(tr, HLO), red, 2,
                               phases.METRICS["panel_ms.solve"]) == \
        pytest.approx(4 / 14.5 * 7.75)


def test_idle_by_label_holds_every_gap():
    tr = _tr()
    red = trace.reduce(tr, devices=[0])
    idle = phases.idle_by_label(tr, devices=[0])
    assert sum(idle.values()) + red["busy_s"] == pytest.approx(
        red["window_s"])
    # (0.5, 1): its middle 0.75 lies in bench.dispatch, inside the solve
    assert idle == {"bench.dispatch": pytest.approx(0.5e-3),
                    "none": pytest.approx(1e-3 + 2e-3),     # 11-12, 18-20
                    "bench.solve": pytest.approx(0.5e-3)}   # 12.5-13
    top = {round(s * 1e6): name for name, s in red["idle_gaps"]}
    assert top[2000] == top[1000] == "none"


# ------------------------------------------------ recorded on the chip


def test_existing_metrics_on_the_recorded_posv_trace():
    """The accepted readers read as before on the recorded posv trace."""
    tr = trace.load_xspace(str(DATA / "posv_n2048_v5e.xplane.pb"))
    ctx = {"trace": trace.reduce(tr),
           "system": {"solves": 2,
                      "flops_per_solve": flops.solve_flops("posv", 2048, 1)},
           "peak": harness.peaks("TPU v5 lite", ROOT)}
    want = {"device_idle.solve": 77.28582293495556,
            "nonmatmul_ms.solve": 1.2870830298845255,
            "mfu.solve": 1.084525649925121}
    for name, value in want.items():
        assert harness.reader(name, ROOT)(ctx) == pytest.approx(
            value, rel=1e-12)


SPANS = {
    "gesv": ["slate.getrf/panel", "slate.getrf/swap", "slate.getrf/trsm",
             "slate.getrf/gemm", "slate.getrf/health", "slate.getrs/permute",
             "slate.layout/to_dense", "slate.layout/from_dense"],
    "posv": ["slate.potrf/herk", "slate.potrf/panel", "slate.potrf/health",
             "slate.layout/to_dense", "slate.layout/from_dense"],
}


@pytest.fixture(scope="module", params=sorted(SPANS))
def recorded_phases(request, tmp_path_factory):
    """A traced cell at n=2048 on a v5e chip, with its program's text
    (``benchmark/phase_table.py --n 2048 --nb 512 --save``)."""
    op = request.param
    stem = DATA / f"{op}_n2048_phases_v5e"
    path = tmp_path_factory.mktemp(op) / "trace.xplane.pb"
    path.write_bytes(gzip.decompress(
        (stem.parent / (stem.name + ".xplane.pb.gz")).read_bytes()))
    tr = trace.load_xspace(str(path))
    hlo = gzip.decompress((stem.parent / (stem.name + ".hlo.txt.gz"))
                          .read_bytes()).decode()
    return op, tr, hlo


def test_recorded_ops_join_the_program_text(recorded_phases):
    op, tr, hlo = recorded_phases
    tab = phases.table(tr, hlo)
    d = tab["devices"][0]
    assert d["executions"] >= 2
    assert d["joined_s"] >= 0.99 * d["op_s"] > 0
    for span in SPANS[op]:
        assert span in tab["spans"], span
        assert d["phases"][span][0] > 0, span
    assert d["phases"].get(phases.UNSCOPED, [0.0])[0] <= 0.05 * d["op_s"]


def test_recorded_phases_sum_to_busy(recorded_phases):
    _, tr, hlo = recorded_phases
    red = trace.reduce(tr)
    tab = phases.table(tr, hlo)
    solves = tab["devices"][0]["executions"]
    ms = phases.ms_by_phase(tab, red, solves)
    assert sum(ms.values()) == pytest.approx(1e3 * red["busy_s"] / solves)
    for name, names in phases.METRICS.items():
        value = phases.ms_per_solve(tab, red, solves, names)
        assert (value is None) == (name == "swap_ms.solve"
                                   and "slate.getrf/swap" not in tab["spans"])


def test_phase_table_runs_a_cut_cell(tmp_path, monkeypatch):
    """The script's whole path at a tiny order, on the CPU: the program
    text comes from the jitted object's caches and names the phases, and
    ``--save`` keeps the text and the per-instruction times (the CPU's
    trace holds no TPU plane, so no phase time here)."""
    from benchmark import phase_table
    monkeypatch.setattr(harness, "prepare_runtime", lambda jax, root=None:
                        None)
    monkeypatch.setenv("SLATE_TUNE_CACHE", str(tmp_path / "plans.json"))
    out = phase_table.run("linsys.posv", 2**33 + 5, 0.3, 256, 64,
                          str(tmp_path), require_tpu=False)
    assert out["correct"] and out["attempted"] >= 1
    assert out["program_text_s"] < 5.0
    assert set(SPANS["posv"]) <= set(out["spans"])
    assert out["phases_ms"] == {}
    assert all(v is None for v in out["phase_metrics"].values())
    for suffix in ("hlo.txt.gz", "ops.json.gz", "xplane.pb.gz"):
        assert (tmp_path / f"posv_n256.{suffix}").is_file()
    assert phases.module_name(gzip.decompress(
        (tmp_path / "posv_n256.hlo.txt.gz").read_bytes()).decode()) == \
        "jit_solve"
