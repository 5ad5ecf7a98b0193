"""Device-time truth tests (obs.flops / obs.slo / obs.compare + the
timing mode wired through util.trace.annotate and serve/server.py).

The load-bearing guarantees:

- the flops registry prices every public op analytically, and BOTH
  consumers — timed driver events and bench.py lines — derive mfu from
  the SAME model (the bench side is asserted in test_bench_smoke.py);
- ``obs.timing()`` stamps ``device_ms`` on the outermost EAGER boundary
  only: traced frames never sync, and the jaxpr is byte-identical with
  timing on or off (the jaxpr-identity guarantee extends to timing);
- the perf-regression sentinel (``--compare``) classifies the real
  rounds r04 -> r05 (tests/data: their shared metric lines; all improved,
  exit 0) and gates the reverse diff (exit 1);
- SLO budgets evaluate against the serving aggregate with metric-owned
  directions, fail LOUDLY on missing data, and export Prometheus text;
- malformed/truncated JSONL is counted and reported, never fatal.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.obs import __main__ as obs_cli
from slate_tpu.obs import compare as obs_compare
from slate_tpu.obs import events as obs_events
from slate_tpu.obs import flops, metrics, slo

REPO = Path(__file__).resolve().parent.parent
DATA = Path(__file__).resolve().parent / "data"


def _hpd(rng, n=32):
    a = rng.standard_normal((n, n))
    return a @ a.T / n + n * np.eye(n)


def _posv(rng, n=32, nb=16, k=4):
    return st.posv(st.HermitianMatrix.from_numpy(_hpd(rng, n), nb),
                   st.Matrix.from_numpy(rng.standard_normal((n, k)), nb))


# ------------------------------------------------------- flops registry


def test_flop_models_match_classic_counts():
    assert flops.op_flops("gemm", [(64, 32), (32, 48)]) == \
        2.0 * 64 * 32 * 48
    assert flops.op_flops("potrf", [(96, 96)]) == 96 ** 3 / 3.0
    assert flops.op_flops("posv", [(32, 32), (32, 4)]) == \
        32 ** 3 / 3.0 + 2.0 * 32 * 32 * 4
    assert flops.op_flops("gesv", [(32, 32), (32, 4)]) == \
        2.0 * 32 ** 3 / 3.0 + 2.0 * 32 * 32 * 4
    assert flops.op_flops("geqrf", [(96, 32)]) == \
        2.0 * 96 * 32 ** 2 - 2.0 * 32 ** 3 / 3.0
    assert flops.op_flops("gels", [(96, 32), (96, 4)]) == \
        2.0 * 96 * 32 ** 2 - 2.0 * 32 ** 3 / 3.0 + 4.0 * 96 * 32 * 4


def test_registry_is_total_over_serve_ops_and_rejects_garbage():
    # every serving op maps onto a registered dense model
    for model in flops.SERVE_OP_MODEL.values():
        assert model in flops.registered_ops()
    assert flops.op_flops("not_an_op", [(8, 8)]) is None
    assert flops.op_flops("gemm", []) is None          # shape-starved
    assert flops.op_flops("gemm", [("x", 3), (3, 3)]) is None
    assert flops.mfu(None, 1.0) is None
    assert flops.mfu(1e9, None) is None
    assert flops.achieved_gbps(None, 1.0) is None


def test_op_bytes_counts_operands_plus_result():
    # gemm f64: A(64x32) + B(32x48) read, C(64x32-result=first operand)
    nbytes = flops.op_bytes("gemm", [(64, 32), (32, 48)], "float64")
    assert nbytes == (64 * 32 + 32 * 48 + 64 * 32) * 8
    # unknown dtype falls back to 4-byte items
    assert flops.op_bytes("gemm", [(8, 8)], None) == (8 * 8 + 8 * 8) * 4


def test_peak_override_scopes():
    with flops.peak_override(1e12):
        assert flops.peak() == 1e12
        assert flops.mfu(5e11, 1.0) == 0.5
        assert flops.mfu(5e11, 0.5) == 1.0


def test_serve_flops_prices_live_problems_only():
    probs = [((32, 32), (32, 4)), ((20, 20), (20, 3))]
    want = (flops.op_flops("gesv", [(32, 32), (32, 4)])
            + flops.op_flops("gesv", [(20, 20), (20, 3)]))
    assert flops.serve_flops("solve", probs) == want
    assert flops.serve_flops("chol_solve", [((16, 16), (16, 2))]) == \
        flops.op_flops("posv", [(16, 16), (16, 2)])
    assert flops.serve_flops("unknown_op", probs) is None


# ----------------------------------------------------------- timing mode


def test_timing_event_fields_eager(rng):
    """Under obs.timing() an eager boundary blocks to device-ready and
    the event's mfu is EXACTLY the registry model over device_ms — the
    one-registry contract, asserted from the event itself."""
    with flops.peak_override(1e12):
        with obs.recording() as ev, obs.timing():
            _posv(rng)
        (e,) = ev
        assert e["device_ms"] is not None and e["device_ms"] > 0
        assert e["device_ms"] <= e["dur_ms"]
        secs = e["device_ms"] * 1e-3
        assert e["mfu"] == flops.mfu(
            flops.op_flops("posv", e["shapes"]), secs)
        assert e["achieved_gbps"] == flops.achieved_gbps(
            flops.op_bytes("posv", e["shapes"], e["dtype"]), secs)


def test_timing_off_leaves_fields_none(rng):
    with obs.recording() as ev:
        _posv(rng)
    (e,) = ev
    assert e["device_ms"] is None
    assert e["mfu"] is None and e["achieved_gbps"] is None


def test_traced_boundaries_never_sync(rng):
    """A jitted driver traces once; tracers hold no buffers, so the
    traced event must carry device_ms=None even with timing on."""
    a = jnp.asarray(_hpd(rng))
    b = jnp.asarray(rng.standard_normal((32, 4)))

    @jax.jit
    def run(a, b):
        from slate_tpu.core.storage import TileStorage
        M = st.Matrix(TileStorage.from_dense(a, 16, 16))
        L, X = st.posv(st.HermitianMatrix._from_view(M, st.Uplo.Lower),
                       st.Matrix(TileStorage.from_dense(b, 16, 16)))
        return X.to_dense()

    with obs.recording() as ev, obs.timing():
        run(a, b)
    (e,) = ev
    assert e["traced"] is True
    assert e["device_ms"] is None and e["mfu"] is None


def test_jaxpr_identity_timing_on_vs_off(rng):
    """Timing changes how the HOST waits, never what is traced."""
    from slate_tpu.core.storage import TileStorage

    def run(a, b):
        F, X = st.gesv(st.Matrix(TileStorage.from_dense(a, 16, 16)),
                       st.Matrix(TileStorage.from_dense(b, 16, 16)))
        return X.to_dense()

    a = jnp.asarray(rng.standard_normal((32, 32)) + 32 * np.eye(32))
    b = jnp.asarray(rng.standard_normal((32, 4)))
    off = str(jax.make_jaxpr(run)(a, b))
    with obs.recording(), obs.timing():
        on = str(jax.make_jaxpr(run)(a, b))
    assert on == off


def test_timing_env_var(monkeypatch):
    monkeypatch.delenv("SLATE_OBS_EVENTS", raising=False)
    monkeypatch.setenv("SLATE_OBS_TIMING", "1")
    try:
        obs_events._init_from_env()
        assert obs.timing_enabled()
    finally:
        obs.set_timing(False)
    assert not obs.timing_enabled()


def test_metrics_aggregate_device_time_columns(rng, tmp_path):
    path = tmp_path / "ev.jsonl"
    with flops.peak_override(1e12):
        obs.enable(str(path))
        try:
            with obs.timing():
                _posv(rng)
        finally:
            obs.disable()
    s = obs.summarize([str(path)])
    row = s["ops"]["posv"]
    assert row["device_p50_ms"] > 0
    assert row["mfu"] is not None
    text = metrics.render(s)
    assert "dev_p50_ms" in text and "mfu" in text


# --------------------------------------------- perf-regression sentinel


def test_compare_direction_and_noise_model():
    assert obs_compare.direction("gemm_n4096_gflops_per_chip") == "higher"
    assert obs_compare.direction("abft_overhead_pct") == "lower"
    assert obs_compare.direction("serve_latency_p99") == "lower"
    assert obs_compare.direction("roundtrip", "ms") == "lower"
    assert obs_compare.noise_pct("serve_mixed_problems_per_s") == 15.0
    assert obs_compare.noise_pct("sweep_potrf_xla") == 10.0
    assert obs_compare.noise_pct("gemm_n4096_gflops_per_chip") == \
        obs_compare.DEFAULT_NOISE_PCT
    # PERF r15 pipeline metrics ride the wider multi-device noise band,
    # and the speedup/overlap ratios count as higher-is-better
    assert obs_compare.noise_pct("summa_lookahead_d1_n8192_gflops") == 10.0
    assert obs_compare.noise_pct("dist_chol_lookahead_speedup_n16384") == \
        10.0
    assert obs_compare.direction("summa_lookahead_overlap_pct_n8192") == \
        "higher"


def _round(tmp_path, name, values):
    p = tmp_path / name
    p.write_text("".join(
        json.dumps({"schema": "slate-bench-v1", "metric": m, "value": v,
                    "unit": "GFLOP/s", "chip": "cpu"}) + "\n"
        for m, v in values.items()))
    return str(p)


def test_compare_classifies_and_gates(tmp_path):
    old = _round(tmp_path, "old.jsonl",
                 {"gemm": 100.0, "potrf": 100.0, "gone": 1.0})
    new = _round(tmp_path, "new.jsonl",
                 {"gemm": 120.0, "potrf": 97.0, "fresh": 2.0})
    r = obs_compare.compare(old, new)
    by = {row["metric"]: row for row in r["rows"]}
    assert by["gemm"]["class"] == "improved" and not by["gemm"]["gated"]
    assert by["potrf"]["class"] == "flat"     # -3% inside the 5% band
    assert r["only_old"] == ["gone"] and r["only_new"] == ["fresh"]
    assert r["regressions"] == []

    # -20% blows through max(gate, noise): regressed AND gated
    worse = _round(tmp_path, "worse.jsonl", {"gemm": 80.0, "potrf": 99.0})
    r = obs_compare.compare(old, worse)
    (bad,) = r["regressions"]
    assert bad["metric"] == "gemm" and bad["gated"]
    assert bad["delta_pct"] == -20.0


def test_compare_gate_threshold_is_the_ci_knob(tmp_path):
    """-6% is past the 5% noise band (regressed) but inside the default
    10% gate — tightening --gate is what turns it into a CI failure."""
    old = _round(tmp_path, "old.jsonl", {"gemm": 100.0})
    new = _round(tmp_path, "new.jsonl", {"gemm": 94.0})
    loose = obs_compare.compare(old, new)
    assert loose["rows"][0]["class"] == "regressed"
    assert not loose["regressions"]
    tight = obs_compare.compare(old, new, gate=5.0)
    assert tight["regressions"]
    assert obs_cli.main(["--compare", old, new]) == 0
    assert obs_cli.main(["--compare", old, new, "--gate", "5"]) == 1


def test_compare_noisy_metrics_get_wider_bands(tmp_path):
    # -12% on a serve metric stays flat (15% band); on a dense metric
    # it regresses
    old = _round(tmp_path, "old.jsonl",
                 {"serve_mixed_problems_per_s": 100.0, "gemm": 100.0})
    new = _round(tmp_path, "new.jsonl",
                 {"serve_mixed_problems_per_s": 88.0, "gemm": 88.0})
    by = {r["metric"]: r for r in obs_compare.compare(old, new)["rows"]}
    assert by["serve_mixed_problems_per_s"]["class"] == "flat"
    assert by["gemm"]["class"] == "regressed"


def test_compare_lower_better_metrics(tmp_path):
    old = _round(tmp_path, "old.jsonl", {"abft_overhead_pct": 20.0})
    new = _round(tmp_path, "new.jsonl", {"abft_overhead_pct": 10.0})
    (row,) = obs_compare.compare(old, new)["rows"]
    assert row["better"] == "lower" and row["class"] == "improved"
    (row,) = obs_compare.compare(new, old)["rows"]
    assert row["class"] == "regressed" and row["gated"]


def test_cli_compare_real_rounds_r04_to_r05(capsys):
    """The acceptance drill: diff two pre-schema wrapper rounds holding
    the shared metric lines of the driver's r04 and r05 chip records.
    Every shared metric improved r04 -> r05, so the gate passes; the
    reverse diff is 3 gated regressions and exit 1."""
    r04 = str(DATA / "bench_r04_metrics.json")
    r05 = str(DATA / "bench_r05_metrics.json")
    assert obs_cli.main(["--compare", r04, r05]) == 0
    out = capsys.readouterr().out
    assert "gemm_n4096_gflops_per_chip" in out
    assert "improved" in out and "(0 gated)" in out

    assert obs_cli.main(["--compare", r05, r04]) == 1
    out = capsys.readouterr().out
    assert "[GATED]" in out and "regressed" in out


def test_cli_compare_json_and_missing_file(tmp_path, capsys):
    r04 = str(DATA / "bench_r04_metrics.json")
    r05 = str(DATA / "bench_r05_metrics.json")
    assert obs_cli.main(["--json", "--compare", r04, r05]) == 0
    doc = json.loads(capsys.readouterr().out)
    shared = {r["metric"] for r in doc["rows"]}
    assert {"gemm_n4096_gflops_per_chip", "gemm_n8192_gflops_per_chip",
            "posv_n16384_gflops_per_chip"} <= shared
    assert all(r["class"] == "improved" for r in doc["rows"])
    assert obs_cli.main(["--compare", r04,
                         str(tmp_path / "nope.json")]) == 2


# ------------------------------------------------------------ SLO budgets


def _serve_rec(op="solve", dtype="float32", lat=(5.0, 7.0), **kw):
    rec = {"schema": "slate-obs-v1", "kind": "serve_batch", "op": op,
           "dtype": dtype, "bucket": [32, 8], "batch": 4,
           "problems": len(lat), "occupancy": len(lat) / 4,
           "padding_waste": 0.2, "escalated": 0, "compiled": False,
           "retraces": 0, "ladder": "geometric", "dur_ms": 2.0,
           "device_ms": None, "mfu": 0.25, "achieved_gbps": None,
           "queue_depth": len(lat),
           "age_at_flush_ms": [0.5] * len(lat), "latency_ms": list(lat)}
    rec.update(kw)
    return rec


def test_slo_aggregate_builds_union_row():
    recs = [_serve_rec(), _serve_rec(op="chol_solve", lat=(3.0,))]
    stats = slo.aggregate(recs)
    assert set(stats) == {"solve/float32", "chol_solve/float32", "*"}
    assert stats["*"]["problems"] == 3
    assert stats["*"]["latency_p99_ms"] is not None
    assert stats["solve/float32"]["latency_p50_ms"] == 6.0


def test_slo_evaluate_directions_and_loud_missing_data():
    stats = slo.aggregate([_serve_rec()])
    verdicts = slo.evaluate(stats, {
        "*": {"latency_p99_ms": 10.0},          # max bound: 7 <= 10 PASS
        "solve": {"mfu": 0.5},                  # min bound: 0.25 < 0.5 FAIL
        "solve/float32": {"esc_per_1k": 5.0},   # 0 <= 5 PASS
        "qr/float64": {"latency_p99_ms": 1.0},  # no such row: FAIL
    })
    by = {(v["target"], v["metric"]): v for v in verdicts}
    assert by[("*", "latency_p99_ms")]["ok"]
    assert not by[("solve", "mfu")]["ok"]
    assert by[("solve", "mfu")]["row"] == "solve/float32"   # bare-op match
    assert by[("solve/float32", "esc_per_1k")]["ok"]
    missing = by[("qr/float64", "latency_p99_ms")]
    assert not missing["ok"] and missing["value"] is None

    # a budget naming a metric the stream never measured must FAIL
    (v,) = slo.evaluate(stats, {"*": {"no_such_metric": 1.0}})
    assert not v["ok"] and v["value"] is None


def _write_serve_stream(tmp_path):
    p = tmp_path / "serve.jsonl"
    p.write_text("".join(json.dumps(_serve_rec()) + "\n"
                         for _ in range(3)))
    return str(p)


def test_cli_slo_exit_codes_pinned(tmp_path, capsys):
    stream = _write_serve_stream(tmp_path)
    good = tmp_path / "good.json"
    good.write_text(json.dumps({"*": {"latency_p99_ms": 100.0,
                                      "esc_per_1k": 5.0}}))
    assert obs_cli.main(["--slo", str(good), stream]) == 0
    out = capsys.readouterr().out
    assert "PASS" in out and "2/2 budget check(s) passed" in out

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"*": {"latency_p99_ms": 1.0}}))
    assert obs_cli.main(["--slo", str(bad), stream]) == 1
    assert "FAIL" in capsys.readouterr().out

    garbled = tmp_path / "garbled.json"
    garbled.write_text(json.dumps(["not", "a", "mapping"]))
    assert obs_cli.main(["--slo", str(garbled), stream]) == 2
    assert "budgets" in capsys.readouterr().err


def test_cli_prometheus_export(tmp_path, capsys):
    stream = _write_serve_stream(tmp_path)
    assert obs_cli.main(["--prom", stream]) == 0
    out = capsys.readouterr().out
    assert '# TYPE slate_serve_latency_p99_ms gauge' in out
    assert 'slate_serve_latency_p99_ms{op="solve",dtype="float32"} 7' \
        in out
    assert 'op="*"' in out                     # the union row exports too
    # every sample line parses as NAME{labels} VALUE
    for line in out.splitlines():
        if line.startswith("#") or not line:
            continue
        name_labels, value = line.rsplit(" ", 1)
        assert name_labels.startswith("slate_serve_")
        float(value)


# ------------------------------------------- malformed-input hardening


def test_load_records_counts_truncated_json(tmp_path):
    p = tmp_path / "events.jsonl"
    p.write_text(
        json.dumps(_serve_rec()) + "\n"
        "INFO some interleaved log line\n"
        '{"schema": "slate-obs-v1", "kind": "event", "op": "ges\n'
        '{"metric": "gemm", "value": 1.0}\n'
        '["a", "json", "array", "line"]\n')
    records, malformed = metrics.load_records([str(p)])
    # truncated dict line counts; the log line does not; the non-dict
    # array line counts (it parses but is not a record)
    assert malformed == 2
    assert len(records) == 2
    s = obs.summarize([str(p)])
    assert s["counts"]["malformed"] == 2
    text = metrics.render(s)
    assert "malformed=2 truncated/garbled line(s) skipped" in text


def test_render_omits_malformed_footer_when_clean(tmp_path):
    p = tmp_path / "events.jsonl"
    p.write_text(json.dumps(_serve_rec()) + "\n")
    assert "malformed" not in metrics.render(obs.summarize([str(p)]))


def test_load_records_harvests_wrapper_tail(tmp_path):
    p = tmp_path / "BENCH_rXX.json"
    p.write_text(json.dumps({
        "cmd": "python bench.py",
        "rc": 0,
        "tail": ("warming up...\n"
                 '{"schema": "slate-bench-v1", "metric": "gemm", '
                 '"value": 42.0, "unit": "GFLOP/s"}\n'),
    }, indent=1))
    records, malformed = metrics.load_records([str(p)])
    assert malformed == 0
    assert [r["metric"] for r in records] == ["gemm"]
