"""The trace reduction: busy union from program executions, op
categories, exposed collectives, idle gaps named by host spans, the
device clock put on the host's, and executions whose ops the trace lost;
on hand-made events and on a small trace recorded on a v5e chip (two
posv solves at n=2048, ``benchmark/data``)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import trace  # noqa: E402

MS = 1e6  # ns
RECORDED = ROOT / "benchmark" / "data" / "posv_n2048_v5e.xplane.pb"


def _tr():
    return {
        "devices": {
            0: {"modules": [[1 * MS, 3 * MS, "jit_solve"],
                            [6 * MS, 1.5 * MS, "jit_solve"],
                            [12 * MS, 5 * MS, "jit_solve"]],    # past w1
                "ops": [[1 * MS, 2 * MS, "fusion.1", "matmul"],
                        [2 * MS, 2 * MS, "fusion.2", "other"],  # overlaps
                        [6 * MS, 1 * MS, "all-reduce.3", "collective"],
                        [6.5 * MS, 1 * MS, "fusion.1", "matmul"],
                        [12 * MS, 5 * MS, "fusion.9", "other"]]},
            1: {"modules": [[1 * MS, 1 * MS, "jit_solve"]],
                "ops": [[1 * MS, 1 * MS, "all-gather.1", "collective"]]},
        },
        "host": [[0.5 * MS, 10 * MS, "bench.window"],
                 [0.5 * MS, 9 * MS, "bench.solve"],
                 [4.2 * MS, 1 * MS, "bench.operands"],
                 [20 * MS, 1 * MS, "bench.certify"]],
        "dispatch": [0.9 * MS, 5.9 * MS],
    }


def test_union_and_overlap():
    merged = trace.union([(3, 4), (1, 2), (1.5, 3.5), (6, 7)])
    assert merged == [[1, 4], [6, 7]]
    assert trace.length(merged) == 4
    assert trace.overlap(merged, [[0, 1.5], [3.5, 6.5]]) == 1.5


def test_reduce_busy_categories_and_exposed_collectives():
    red = trace.reduce(_tr(), window_span="bench.window", devices=[0, 1])
    assert red["window_s"] == pytest.approx(0.010)
    assert red["clock_offset_s"] == 0.0     # no op starts before dispatch
    d0 = red["devices"][0]
    # executions [1, 4] and [6, 7.5]; [12, 17] lies past the window (10.5)
    assert d0["busy_s"] == pytest.approx(4.5e-3)
    assert d0["modules"] == 2 and d0["modules_covered"] == 2
    assert d0["matmul_s"] == pytest.approx(3e-3)
    assert d0["other_s"] == pytest.approx(2e-3)
    assert d0["collective_s"] == pytest.approx(1e-3)
    # all-reduce [6, 7] overlaps the matmul [6.5, 7.5] for half of it
    assert d0["collective_exposed_s"] == pytest.approx(0.5e-3)
    d1 = red["devices"][1]
    assert d1["collective_exposed_s"] == pytest.approx(1e-3)
    assert red["busy_s"] == pytest.approx((4.5e-3 + 1e-3) / 2)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(3e-3)]
    gaps = dict((round(s * 1e3, 6), name) for name, s in red["idle_gaps"])
    assert gaps[3.0] == "bench.solve"       # (7.5, 10.5): inside the solve
    assert gaps[2.0] == "bench.operands"    # (4, 6): its middle at 5
    assert gaps[0.5] == "bench.solve"       # (0.5, 1)


def test_lost_ops_leave_their_execution_out():
    tr = _tr()
    tr["devices"][0]["ops"] = tr["devices"][0]["ops"][:2]   # lost the rest
    d0 = trace.reduce(tr, devices=[0])["devices"][0]
    assert d0["modules"] == 2 and d0["modules_covered"] == 1
    assert d0["busy_s"] == pytest.approx(4.5e-3)
    assert d0["collective_s"] == 0.0 and d0["ops"] == 2


def test_device_clock_moved_to_the_first_dispatch():
    tr = _tr()
    for d in tr["devices"].values():
        for ev in d["modules"] + d["ops"]:
            ev[0] -= 0.7 * MS          # a device clock 0.6 ms behind
    red = trace.reduce(tr, devices=[0])
    assert red["clock_offset_s"] == pytest.approx(0.6e-3)
    assert red["devices"][0]["busy_s"] == pytest.approx(4.5e-3)


@pytest.mark.parametrize("skew_ms", [-1550.0, -1.2, 1.2, 1550.0, 60e3])
def test_device_clock_kept_inside_the_window(skew_ms):
    """One long solve fills the window: however far the device clock
    lies from the host's, the execution is put inside the window and
    counts whole."""
    tr = {"devices": {0: {"modules": [[(2.5 + skew_ms) * MS, 1574.5 * MS,
                                       "jit_solve"]],
                          "ops": [[(2.5 + skew_ms) * MS, 1574.5 * MS,
                                   "while.1", "other"]]}},
          "host": [[0.0, 1579.0 * MS, "bench.window"],
                   [0.5 * MS, 1578.0 * MS, "bench.solve"]],
          "dispatch": [1.5 * MS]}
    red = trace.reduce(tr, devices=[0])
    assert red["busy_s"] == pytest.approx(1.5745)
    assert red["devices"][0]["modules_covered"] == 1
    assert red["devices"][0]["other_s"] == pytest.approx(1.5745)
    shift = red["clock_offset_s"] * 1e3
    assert 1.5 <= 2.5 + skew_ms + shift <= 1579.0 - 1574.5


def _nested():
    return {"devices": {0: {"modules": [[1 * MS, 10 * MS, "jit_solve"]],
                            "ops": [[1 * MS, 8 * MS, "while.1", "other"],
                                    [2 * MS, 3 * MS, "fusion.1 dot",
                                     "matmul"],
                                    [3 * MS, 1 * MS, "fusion.2", "other"],
                                    [6 * MS, 2 * MS, "copy.1", "other"],
                                    [9 * MS, 2 * MS, "fusion.1 dot",
                                     "matmul"]]}},
            "host": [[0.5 * MS, 11 * MS, "bench.window"]],
            "dispatch": [0.9 * MS]}


def test_nested_ops_count_their_own_time():
    d0 = trace.reduce(_nested(), devices=[0])["devices"][0]
    # the loop [1, 9] holds [2, 5] (which holds [3, 4]) and [6, 8]: 3 of
    # its own; the matmul at [2, 5] holds 1 ms of fusion.2
    assert d0["matmul_s"] == pytest.approx(4e-3)
    assert d0["other_s"] == pytest.approx(6e-3)
    assert d0["matmul_s"] + d0["other_s"] == pytest.approx(d0["busy_s"])


def test_nonmatmul_reader_scales_the_share_to_busy_per_solve():
    from benchmark import harness
    read = harness.reader("nonmatmul_ms.solve", ROOT)
    red = trace.reduce(_nested(), devices=[0])
    # 6 of 10 ms recorded op time outside matmuls, 10 ms busy, 2 solves
    assert read({"trace": red, "system": {"solves": 2}}) == pytest.approx(3.0)
    assert read({"trace": red, "system": {"solves": 0}}) is None


def test_own_times_keep_overlapping_ops_whole():
    own, leaf = trace.own_times([(0, 4, "a"), (2, 6, "b"), (3, 5, "c")])
    assert own == [4, 2, 2] and leaf == [True, False, True]


@pytest.mark.parametrize("name,cat", [
    ("%fusion.12 = f32[512,512]{0,1:T(8,128)} fusion(f32[512,512] %a), "
     "kind=kOutput, calls=%fused_computation.3", "matmul"),
    ("%convolution.3 = f32[8,8]{1,0} convolution(f32[8,8] %a, f32[8,8] %b)",
     "matmul"),
    ("%fusion.9 = f32[8] fusion(f32[8] %all-reduce.3), kind=kLoop", "other"),
    ("%all-reduce.1 = f32[8] all-reduce(f32[8] %x)", "collective"),
    ("%collective-permute-start.2 = (f32[8], f32[8]) "
     "collective-permute-start(f32[8] %x)", "collective"),
    ("%copy.1 = f32[8]{0} copy(f32[8]{0} %x)", "other"),
    ("%custom-call.90 = f32[1,128,128] custom-call(f32[1,128,128] %x)",
     "other"),
])
def test_category(name, cat):
    assert trace.category(name) == cat


def test_short_name():
    text = ("%fusion.405 = f32[20480,20480]{0,1:T(8,128)} fusion(f32[20480,"
            "20480]{0,1:T(8,128)} %bitcast.1010), kind=kLoop, "
            "calls=%fused_computation.437")
    assert trace.short_name(text) == "fusion.405 f32[20480,20480] fusion Loop"


def test_missing_window_is_an_error():
    with pytest.raises(ValueError, match="no host span"):
        trace.reduce({"devices": {}, "host": []})


@pytest.fixture(scope="module")
def recorded():
    return trace.load_xspace(str(RECORDED))


def test_recorded_chip_trace_loads(recorded):
    assert list(recorded["devices"]) == [0]
    dev = recorded["devices"][0]
    assert len(dev["modules"]) == 2 and len(dev["ops"]) == 748
    assert all(m[2].startswith("jit_") for m in dev["modules"])
    names = [h[2] for h in recorded["host"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.solve") == 2
    assert len(recorded["dispatch"]) == 2
    cats = {c for *_, c in dev["ops"]}
    assert cats == {"matmul", "other"}


def test_recorded_chip_trace_reduces(recorded):
    red = trace.reduce(recorded)
    d0 = red["devices"][0]
    # the device clock ran 1.2 ms behind the host's in this trace
    assert red["clock_offset_s"] == pytest.approx(1.196e-3, abs=1e-5)
    assert d0["modules"] == 2 and d0["modules_covered"] == 2
    assert d0["ops"] == 748
    assert red["busy_s"] == pytest.approx(2.688e-3, rel=1e-3)
    assert d0["matmul_s"] + d0["other_s"] <= red["busy_s"] + 1e-9
    assert d0["matmul_s"] > 0
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["device_ops"][0][0].startswith("custom-call")
    assert sum(g for _, g in red["idle_gaps"]) <= red["window_s"]
