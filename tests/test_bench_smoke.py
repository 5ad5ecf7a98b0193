"""CPU smoke tests for bench.py: every metric function emits one parseable
JSON line at toy sizes, and the SLATE_BENCH_BUDGET_S harness skips (never
kills) metrics that would blow the budget — every step still emits its
line, and the run exits non-zero when any step errored or was cut.
"""

import importlib.util
import json
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    saved = sys.modules.get("bench")
    sys.modules["bench"] = mod
    spec.loader.exec_module(mod)
    yield mod
    if saved is not None:
        sys.modules["bench"] = saved
    else:
        sys.modules.pop("bench", None)


def _lines(capsys):
    out = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    return [json.loads(ln) for ln in out]


TOY = [
    ("bench_potrf_fused", dict(n=256, nb=128, bw=8, iters=1)),
    ("bench_geqrf_panel", dict(m=256, n=128, iters=1)),
    ("bench_gemm", dict(n=64, nb=32, iters=2)),
    ("bench_posv", dict(n=64, nb=32, nrhs=4, iters=1)),
    ("bench_gesv", dict(n=64, nb=32, nrhs=4, iters=1)),
    ("bench_gesv_rbt", dict(n=64, nb=32, nrhs=4, iters=1)),
    ("bench_gesv_abft", dict(n=64, nb=32, nrhs=4, iters=1)),
    ("bench_posv_abft", dict(n=64, nb=32, nrhs=4, iters=1)),
    ("bench_geqrf", dict(m=96, n=32, nb=32, iters=1)),
    ("bench_gels", dict(m=96, n=32, nb=32, nrhs=4, iters=1)),
    ("bench_heev", dict(n=64, nb=32, iters=1)),
    ("bench_svd", dict(n=64, nb=32, iters=1)),
]


@pytest.mark.parametrize("name,kwargs", TOY, ids=[t[0] for t in TOY])
def test_metric_emits_json(bench, capsys, name, kwargs):
    getattr(bench, name)(**kwargs)
    lines = _lines(capsys)
    assert len(lines) == 1
    line = lines[0]
    assert line["schema"] == "slate-bench-v1"
    assert line["unit"] == "GFLOP/s"
    assert "chip" in line
    # a CPU timing is no speed: check the line's structure, not its value
    assert isinstance(line["value"], (int, float)) and line["value"] >= 0
    assert isinstance(line["vs_baseline"], (int, float))
    assert line["flops"] > 0 and line["device_ms"] > 0
    if "abft" in name:
        assert isinstance(line["abft_overhead_pct"], (int, float))
        assert isinstance(line["plain_gflops"], (int, float))


def test_potrf_ooc_emits_gflops_and_slowdown(bench, capsys):
    """bench_potrf_ooc self-emits two lines: the streaming path's raw
    GFLOP/s and its slowdown vs the in-core potrf at the same size."""
    bench.bench_potrf_ooc(n=48, nb=16, iters=1)
    by_metric = {ln["metric"]: ln for ln in _lines(capsys)}
    assert set(by_metric) == {"durability_potrf_ooc_gflops",
                              "durability_potrf_ooc_slowdown"}
    gf = by_metric["durability_potrf_ooc_gflops"]
    assert gf["schema"] == "slate-bench-v1" and "chip" in gf
    assert gf["unit"] == "GFLOP/s" and gf["value"] > 0
    slow = by_metric["durability_potrf_ooc_slowdown"]
    assert slow["unit"] == "x" and slow["value"] > 0


def test_checkpoint_overhead_emits_pct_and_save_ms(bench, capsys):
    """bench_checkpoint_overhead self-emits the every-step checkpoint
    cadence's relative cost and the per-snapshot wall cost."""
    bench.bench_checkpoint_overhead(n=48, nb=16, iters=1)
    by_metric = {ln["metric"]: ln for ln in _lines(capsys)}
    assert set(by_metric) == {"durability_ckpt_overhead_pct",
                              "durability_ckpt_save_ms"}
    pct = by_metric["durability_ckpt_overhead_pct"]
    assert pct["schema"] == "slate-bench-v1" and "chip" in pct
    assert pct["unit"] == "%" and isinstance(pct["value"], (int, float))
    ms = by_metric["durability_ckpt_save_ms"]
    assert ms["unit"] == "ms" and isinstance(ms["value"], (int, float))


def test_serve_mixed_emits_throughput_and_waste(bench, capsys):
    """bench_serve_mixed emits its own two lines (problems/s and padding
    waste %) — it bypasses _emit, whose unit is hardwired to GFLOP/s."""
    bench.bench_serve_mixed(problems=9, nrhs=2, reps=1, sizes=(12, 24, 40))
    lines = _lines(capsys)
    by_metric = {ln["metric"]: ln for ln in lines}
    assert set(by_metric) == {"serve_mixed_problems_per_s",
                              "serve_mixed_padding_waste_pct"}
    pps = by_metric["serve_mixed_problems_per_s"]
    assert pps["schema"] == "slate-bench-v1" and "chip" in pps
    assert pps["unit"] == "problems/s" and pps["value"] > 0
    waste = by_metric["serve_mixed_padding_waste_pct"]
    assert waste["unit"] == "%"
    assert 0.0 <= waste["value"] <= 100.0


def test_serve_ragged_emits_both_routes(bench, capsys):
    """bench_serve_ragged emits raw AND waste-adjusted problems/s for the
    ragged and vmapped-XLA routes plus the workload's padding waste and
    the speedup ratio — six lines, self-emitted like bench_serve_mixed."""
    bench.bench_serve_ragged(problems=6, nrhs=2, reps=1, bucket=16)
    by_metric = {ln["metric"]: ln for ln in _lines(capsys)}
    assert set(by_metric) == {
        "serve_ragged_padding_waste_pct",
        "serve_ragged_ragged_problems_per_s",
        "serve_ragged_xla_problems_per_s",
        "serve_ragged_ragged_adjusted_problems_per_s",
        "serve_ragged_xla_adjusted_problems_per_s",
        "serve_ragged_speedup"}
    waste = by_metric["serve_ragged_padding_waste_pct"]
    assert waste["unit"] == "%" and 0.0 <= waste["value"] <= 100.0
    for route in ("ragged", "xla"):
        raw = by_metric[f"serve_ragged_{route}_problems_per_s"]
        adj = by_metric[f"serve_ragged_{route}_adjusted_problems_per_s"]
        assert raw["schema"] == "slate-bench-v1" and "chip" in raw
        assert raw["unit"] == "problems/s" and raw["value"] > 0
        assert adj["unit"] == "problems/s"
        assert adj["value"] >= raw["value"]   # adjusted divides by 1-waste
    assert by_metric["serve_ragged_speedup"]["unit"] == "x"
    assert by_metric["serve_ragged_speedup"]["value"] > 0


def test_serve_bf16_emits_both_routes_and_accept_rate(bench, capsys):
    """bench_serve_bf16 pins the precision-rung line contract: raw AND
    waste-adjusted problems/s for the bf16-rung and f32-only routes, the
    certificate accept-rate over live slots, and the speedup ratio — six
    self-emitted lines carrying the bench schema."""
    bench.bench_serve_bf16(problems=6, nrhs=2, reps=1, bucket=16)
    by_metric = {ln["metric"]: ln for ln in _lines(capsys)}
    assert set(by_metric) == {
        "serve_precision_bf16_problems_per_s",
        "serve_precision_f32_problems_per_s",
        "serve_precision_bf16_adjusted_problems_per_s",
        "serve_precision_f32_adjusted_problems_per_s",
        "serve_precision_accept_rate_pct",
        "serve_precision_bf16_speedup"}
    for route in ("bf16", "f32"):
        raw = by_metric[f"serve_precision_{route}_problems_per_s"]
        adj = by_metric[f"serve_precision_{route}_adjusted_problems_per_s"]
        assert raw["schema"] == "slate-bench-v1" and "chip" in raw
        assert raw["unit"] == "problems/s" and raw["value"] > 0
        assert adj["unit"] == "problems/s"
        assert adj["value"] >= raw["value"]   # adjusted divides by 1-waste
    accept = by_metric["serve_precision_accept_rate_pct"]
    assert accept["unit"] == "%" and 0.0 <= accept["value"] <= 100.0
    # the workload is well-conditioned by construction: the certificate
    # must accept most problems or the rung is not doing its job
    assert accept["value"] >= 50.0
    assert by_metric["serve_precision_bf16_speedup"]["unit"] == "x"
    assert by_metric["serve_precision_bf16_speedup"]["value"] > 0


def test_serve_bf16_skips_clean_under_budget_preemption(bench, capsys):
    """Preempted by the budget pool, the metric reports a skipped line
    instead of dying, and counts as a cut step."""
    failures = bench._run_isolated(
        [(bench.bench_serve_bf16,
          dict(problems=6, nrhs=2, reps=1, bucket=16))], budget_s=1e-6)
    assert failures == 1
    lines = _lines(capsys)
    assert len(lines) == 1
    assert lines[0]["metric"] == "bench_serve_bf16_skipped"
    assert lines[0]["skipped"] is True
    assert lines[0]["schema"] == "slate-bench-v1"


def test_serve_survival_emits_survival_metrics(bench, capsys):
    """bench_serve_survival replays a Poisson arrival stream against a
    live background-flush Server and self-emits five lines: throughput,
    admitted p99, shed and quarantine rates, and the SLO verdict."""
    bench.bench_serve_survival(problems=8, rate_hz=2000.0, nrhs=2,
                               sizes=(8, 16), budget_ms=60000.0)
    by_metric = {ln["metric"]: ln for ln in _lines(capsys)}
    assert set(by_metric) == {
        "serve_survival_problems_per_s",
        "serve_survival_latency_p99_ms",
        "serve_survival_shed_per_1k",
        "serve_survival_quar_per_1k",
        "serve_survival_slo_pass"}
    pps = by_metric["serve_survival_problems_per_s"]
    assert pps["schema"] == "slate-bench-v1" and "chip" in pps
    assert pps["unit"] == "problems/s" and pps["value"] >= 0
    assert by_metric["serve_survival_latency_p99_ms"]["unit"] == "ms"
    for rate in ("shed_per_1k", "quar_per_1k"):
        line = by_metric[f"serve_survival_{rate}"]
        assert line["unit"] == "per_1k"
        assert 0.0 <= line["value"] <= 1000.0
    gate = by_metric["serve_survival_slo_pass"]
    assert gate["unit"] == "bool" and gate["value"] in (0, 1)


def test_serve_pool_emits_pool_metrics(bench, capsys):
    """bench_serve_pool replays the stream against a 1-member and a
    K-member pool server with a live device kill and self-emits four
    lines: pool throughput, scaling vs one device, failover recovery
    wall, and the retune hot-swap count."""
    bench.bench_serve_pool(problems=8, rate_hz=2000.0, nrhs=2,
                           sizes=(8, 16), members=2)
    by_metric = {ln["metric"]: ln for ln in _lines(capsys)}
    assert set(by_metric) == {
        "serve_pool_problems_per_s",
        "serve_pool_scaling",
        "serve_pool_failover_recovery_ms",
        "serve_pool_retune_swaps"}
    pps = by_metric["serve_pool_problems_per_s"]
    assert pps["schema"] == "slate-bench-v1" and "chip" in pps
    assert pps["unit"] == "problems/s" and pps["value"] > 0
    assert by_metric["serve_pool_scaling"]["unit"] == "x"
    assert by_metric["serve_pool_scaling"]["value"] > 0
    rec = by_metric["serve_pool_failover_recovery_ms"]
    assert rec["unit"] == "ms"
    assert rec["value"] is None or rec["value"] >= 0
    swaps = by_metric["serve_pool_retune_swaps"]
    assert swaps["unit"] == "count" and swaps["value"] >= 0


def test_step_lists_cover_every_metric(bench):
    """Both step lists must include the RBT speculation metric and stay
    callable (functions exist, kwargs are their signature's names)."""
    import inspect
    for steps in (bench.QUICK_STEPS, bench.FULL_STEPS):
        names = [fn.__name__ for fn, _ in steps]
        assert "bench_gesv_rbt" in names
        assert "bench_gesv_abft" in names
        assert "bench_posv_abft" in names
        assert "bench_serve_mixed" in names
        assert "bench_serve_ragged" in names
        assert "bench_serve_bf16" in names
        assert "bench_serve_survival" in names
        assert "bench_serve_pool" in names
        assert "bench_potrf_ooc" in names
        assert "bench_checkpoint_overhead" in names
        for fn, kwargs in steps:
            sig = inspect.signature(fn)
            assert set(kwargs) == set(sig.parameters)


def test_budget_preempts_slow_metric(bench, capsys):
    """A metric that overruns the pool is SIGALRM-preempted and reported
    as skipped; the harness moves on instead of hanging to rc=124."""

    def sleepy():
        time.sleep(30)

    t0 = time.monotonic()
    failures = bench._run_isolated([(sleepy, {})], budget_s=0.3)
    elapsed = time.monotonic() - t0
    assert elapsed < 5
    assert failures == 1
    lines = _lines(capsys)
    assert len(lines) == 1
    assert lines[0]["skipped"] is True
    assert lines[0]["metric"] == "sleepy_skipped"
    assert "preempted" in lines[0]["reason"]
    # triage fields: which phase it died in and how long it got
    assert lines[0]["schema"] == "slate-bench-v1"
    assert lines[0]["phase"] == "compile"
    assert lines[0]["elapsed_s"] >= 0.3


def test_budget_skips_up_front(bench, capsys, monkeypatch):
    """When earlier metrics ate the whole pool, later ones emit a skipped
    line up front — one JSON line per step, no matter what."""
    t = [0.0]

    def fake_clock():
        t[0] += 40.0
        return t[0]

    monkeypatch.setattr(bench.time, "monotonic", fake_clock)
    ran = []

    def quick():
        ran.append(1)

    def never():
        raise AssertionError("must be skipped before running")

    # pool = 2 * 30 = 60s of fake time: quick runs (clock 80 > deadline
    # 100? no: deadline = 40 + 60 = 100, check at 80), never is skipped
    failures = bench._run_isolated([(quick, {}), (never, {})], budget_s=30)
    assert failures == 1
    assert ran == [1]
    lines = _lines(capsys)
    assert len(lines) == 1
    assert lines[0]["skipped"] is True
    assert lines[0]["metric"] == "never_skipped"
    assert lines[0]["reason"] == "time budget exhausted"
    assert lines[0]["schema"] == "slate-bench-v1" and "chip" in lines[0]


def test_no_budget_is_unlimited(bench, capsys):
    ran = []
    bench._run_isolated([(lambda: ran.append(1), {})], budget_s=None)
    assert ran == [1]


def test_failures_are_isolated_and_main_exits_nonzero(bench, capsys,
                                                      monkeypatch):
    """A raising metric emits an error line, the later step still runs,
    and main() returns 1: a run with a failed step is not a success."""

    def boom():
        raise RuntimeError("synthetic")

    ran = []
    monkeypatch.setattr(bench, "QUICK", True)
    monkeypatch.setattr(bench, "QUICK_STEPS",
                        [(boom, {}), (lambda: ran.append(1), {})])
    rc = bench.main()
    assert rc == 1
    assert ran == [1]
    lines = _lines(capsys)
    assert len(lines) == 1                # boom's error line; the lambda
    assert lines[0]["metric"] == "boom_error"   # emits nothing itself
    assert "synthetic" in lines[0]["error"]


def test_watchdog_fires_and_exits_nonzero(bench, capsys, monkeypatch):
    """The watchdog thread escapes even a stuck C++ compile (where SIGALRM
    is queued but never delivered): past the grace deadline it emits a
    skipped line for every step not yet done and hard-exits 1."""
    monkeypatch.setattr(bench, "_WATCHDOG_GRACE_S", 0.0)
    exited = []
    fired = time.monotonic()

    def fake_exit(rc):
        exited.append((rc, time.monotonic() - fired))

    def stuck():
        pass                              # stands in for a blocked compile

    steps = [(stuck, {}), (stuck, {})]
    done = {0}                            # step 0 already emitted its line
    stop = bench._install_watchdog(steps, deadline=time.monotonic() - 1,
                                   done=done, exit_fn=fake_exit)
    deadline = time.monotonic() + 5
    while not exited and time.monotonic() < deadline:
        time.sleep(0.01)
    stop.set()
    assert exited and exited[0][0] == 1
    lines = _lines(capsys)
    assert len(lines) == 1                # only the NOT-done index reported
    assert lines[0]["metric"] == "stuck_skipped"
    assert "watchdog" in lines[0]["reason"]


def test_watchdog_stands_down_on_stop(bench, capsys):
    """stop.set() before the deadline means no exit and no skip lines."""
    exited = []
    stop = bench._install_watchdog([(time.sleep, {})],
                                   deadline=time.monotonic() + 0.2,
                                   done=set(), exit_fn=exited.append)
    stop.set()
    time.sleep(0.5)
    assert exited == []
    assert _lines(capsys) == []


def test_main_arms_watchdog_before_first_compile(bench, monkeypatch):
    """The r05 stall happened inside the FIRST compile; the watchdog must
    already be armed when _chip_peak (first device contact) runs."""
    order = []
    monkeypatch.setattr(bench, "BUDGET_S", 30.0)
    monkeypatch.setattr(
        bench, "_install_watchdog",
        lambda *a, **k: (order.append("watchdog"),
                         __import__("threading").Event())[1])
    monkeypatch.setattr(
        bench, "_chip_peak",
        lambda: (order.append("chip_peak"), (None, "cpu"))[1])
    monkeypatch.setattr(bench, "_run_isolated", lambda *a, **k: 0)
    assert bench.main() == 0
    assert order == ["watchdog", "chip_peak"]


def test_sweep_nb_mode_emits_candidate_lines(bench, capsys, monkeypatch):
    """--sweep-nb emits one JSON line per candidate plan with the plan
    knobs inline, and main still returns 0."""
    from slate_tpu.tune import TilePlan, autotune

    def fake_sweep(op, n, dtype, iters):
        yield TilePlan(kernel="xla", nb=n, bw=8), 10.0
        yield TilePlan(kernel="pallas", nb=128, bw=16), 20.0

    monkeypatch.setattr(autotune, "sweep", fake_sweep)
    monkeypatch.setattr(bench, "_chip_peak", lambda: (None, "cpu"))
    rc = bench.main(("--sweep-nb",))
    assert rc == 0
    lines = _lines(capsys)
    from slate_tpu.tune import OPS
    assert len(lines) == 2 * len(OPS)
    for line in lines:
        assert line["schema"] == "slate-bench-v1"
        assert line["metric"].startswith("sweep_")
        assert line["kernel"] in ("xla", "pallas")
        assert isinstance(line["nb"], int) and isinstance(line["bw"], int)
        assert line["unit"] == "GFLOP/s"
        assert line["value"] > 0


def test_bench_lines_priced_from_obs_flops_registry(bench, capsys,
                                                    monkeypatch):
    """One registry, two consumers: a bench line's flops count is the
    obs.flops model verbatim, and its mfu agrees with what a timed obs
    event would compute from the same flops/seconds measurement."""
    import math

    from slate_tpu.obs import flops

    monkeypatch.setattr(bench, "PEAK", 1e12)
    bench.bench_gemm(n=64, nb=32, iters=2)
    (line,) = _lines(capsys)
    assert line["flops"] == flops.op_flops("gemm", [(64, 64), (64, 64)])
    assert line["device_ms"] is not None and line["device_ms"] > 0
    assert isinstance(line["mfu"], float) and line["mfu"] > 0
    with flops.peak_override(1e12):
        event_style = flops.mfu(line["flops"], line["device_ms"] * 1e-3)
    assert event_style is not None
    # bench prices from the unrounded seconds; allow the device_ms
    # round-trip (1 µs quantization) plus the two mfu roundings — the
    # line's mfu is rounded to 3 decimals, a 5e-4 quantum, so the
    # absolute band must sit strictly above it
    assert math.isclose(line["mfu"], event_style, rel_tol=0.05,
                        abs_tol=6e-4)


def test_bench_lines_carry_device_ms_and_flops(bench, capsys):
    bench.bench_posv(n=64, nb=32, nrhs=4, iters=1)
    (line,) = _lines(capsys)
    from slate_tpu.obs import flops
    assert line["flops"] == flops.op_flops("posv", [(64, 64), (64, 4)])
    assert line["device_ms"] > 0
    # GFLOP/s, flops and device_ms must be one consistent measurement;
    # value is emitted rounded to 1 decimal, so allow that 0.05 absolute
    # quantum on top of the relative band (at CPU speeds the unrounded
    # GFLOP/s sits near the rounding boundary and rel_tol alone flakes)
    derived = line["flops"] / (line["device_ms"] * 1e-3) / 1e9
    import math
    assert math.isclose(derived, line["value"], rel_tol=0.05,
                        abs_tol=0.051)
