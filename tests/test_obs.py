"""Observability spine tests (slate_tpu/obs + util.trace wiring).

Coverage map:

- jaxpr identity: enabling events + span recording produces a
  byte-identical jaxpr for gesv / posv / gels — the zero-overhead
  contract (no io_callback, nothing rides in the computation);
- one event per public driver call: nested internal drivers collapse
  into the boundary's single event; a jitted driver emits exactly one
  (traced) event at trace time and none on cache hits;
- decision capture: resolved speculate/abft knobs, the path taken
  (speculated vs escalated), ABFT detect/correct counters from
  fault-injected runs, and resolve_plan decisions all land in the event;
- the retrace sentinel warns (once, rate-limited) on same-signature
  retrace churn and reports per-op stats;
- the span tracer records nested phase timings and exports valid
  Chrome trace JSON and span JSONL;
- metrics: summarize() aggregates event JSONL into per-op
  latency/rate tables and the ``python -m slate_tpu.obs`` CLI renders
  them (text and --json).
"""

import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import slate_tpu as st
from slate_tpu import obs
from slate_tpu.core.storage import TileStorage
from slate_tpu.obs import __main__ as obs_cli
from slate_tpu.obs import events as obs_events
from slate_tpu.options import Option
from slate_tpu.robust import faults

INFO = {Option.ErrorPolicy: "info"}
ABFT_INFO = {Option.ErrorPolicy: "info", Option.Abft: "on"}
SPEC_INFO = {Option.Speculate: "on", Option.ErrorPolicy: "info"}


def _problem(rng, n=32, nb=16, nrhs=4):
    a = rng.standard_normal((n, n)) + n * np.eye(n)
    b = rng.standard_normal((n, nrhs))
    return a, b


def _hpd(rng, n=32):
    a = rng.standard_normal((n, n))
    return a @ a.T / n + n * np.eye(n)


# ------------------------------------------------------ jaxpr identity


def _gesv_fn(nb):
    def run(a, b):
        F, X = st.gesv(st.Matrix(TileStorage.from_dense(a, nb, nb)),
                       st.Matrix(TileStorage.from_dense(b, nb, nb)))
        return X.to_dense()
    return run


def _posv_fn(nb):
    def run(a, b):
        M = st.Matrix(TileStorage.from_dense(a, nb, nb))
        L, X = st.posv(st.HermitianMatrix._from_view(M, st.Uplo.Lower),
                       st.Matrix(TileStorage.from_dense(b, nb, nb)))
        return X.to_dense()
    return run


def _gels_fn(nb):
    def run(a, b):
        X = st.gels(st.Matrix(TileStorage.from_dense(a, nb, nb)),
                    st.Matrix(TileStorage.from_dense(b, nb, nb)))
        return X.to_dense()
    return run


@pytest.mark.parametrize("maker,shape", [
    (_gesv_fn, ((32, 32), (32, 4))),
    (_posv_fn, ((32, 32), (32, 4))),
    (_gels_fn, ((48, 16), (48, 4))),
])
def test_jaxpr_identity_obs_on_vs_off(rng, maker, shape):
    """Enabling the full observability stack must not change the traced
    computation by a single equation — recording is host-side only."""
    (m, n), (bm, bn) = shape
    a = jnp.asarray(rng.standard_normal((m, n)) + np.eye(m, n) * m)
    if maker is _posv_fn:
        a = jnp.asarray(_hpd(rng, m))
    b = jnp.asarray(rng.standard_normal((bm, bn)))
    run = maker(16)
    off = str(jax.make_jaxpr(run)(a, b))
    with obs.recording():
        with obs.record_spans():
            on = str(jax.make_jaxpr(run)(a, b))
    assert on == off


# --------------------------------------------------- one event per call


def test_one_event_per_eager_call(rng):
    a, b = _problem(rng)
    A = st.Matrix.from_numpy(a, 16)
    B = st.Matrix.from_numpy(b, 16)
    with obs.recording() as ev:
        st.gesv(A, B)
        st.gesv(A, B)
    assert [e["op"] for e in ev] == ["gesv", "gesv"]
    for e in ev:
        assert e["traced"] is False
        assert e["status"] == "ok"
        assert e["dur_ms"] > 0
        assert e["shapes"] == [[32, 32], [32, 4]]
        assert e["policy"] == "Raise"
        assert e["path"].startswith(("direct:", "speculated:"))
        assert e["health"] is not None and e["health"]["ok"] is True


def test_one_event_per_jit_trace_none_on_cache_hit(rng):
    a, b = _problem(rng)
    run = jax.jit(_gesv_fn(16))
    aj, bj = jnp.asarray(a), jnp.asarray(b)
    with obs.recording() as ev:
        run(aj, bj)                      # traces once, then executes
        run(aj, bj)                      # cache hit: never re-enters python
        run(aj, bj)
    assert len(ev) == 1
    assert ev[0]["op"] == "gesv" and ev[0]["traced"] is True
    assert ev[0]["health"] is None       # tracers have no values


def test_nested_drivers_collapse_into_boundary_event(rng):
    """posv internally routes through potrf/trsm-family drivers; only the
    posv boundary may emit."""
    hpd, b = _hpd(rng), _problem(rng)[1]
    with obs.recording() as ev:
        st.posv(st.HermitianMatrix.from_numpy(hpd, 16),
                st.Matrix.from_numpy(b, 16))
    assert [e["op"] for e in ev] == ["posv"]


def test_event_on_driver_error(rng):
    n, nb = 16, 4
    r = np.triu(rng.standard_normal((n, n))) + 4 * np.eye(n)
    r[6, 6] = 0.0                        # exactly singular triangle
    R = st.TriangularMatrix.from_numpy(r, nb, st.Uplo.Upper)
    with obs.recording() as ev:
        with pytest.raises(st.SlateSingularError):
            st.trtri(R)
    assert len(ev) == 1
    assert ev[0]["op"] == "trtri"
    assert ev[0]["status"] == "error:SlateSingularError"


# ------------------------------------------------- decision capture


def test_event_captures_speculated_path(rng):
    a, b = _problem(rng, n=24, nb=8)
    with obs.recording() as ev:
        F, X, h = st.gesv(st.Matrix.from_numpy(a, 8),
                          st.Matrix.from_numpy(b, 8), SPEC_INFO)
    (e,) = ev
    assert e["speculate"] is True
    assert e["path"] == "speculated:rbt"
    assert e["policy"] == "Info"
    assert e["health"]["ok"] is True


def test_event_captures_escalation(rng):
    """A post_rbt strike defeats the speculative fast path; the event
    must show the escalated rung, not the primary attempt."""
    a, b = _problem(rng, n=24, nb=8)
    A = st.Matrix.from_numpy(a, 8)
    B = st.Matrix.from_numpy(b, 8)
    with obs.recording() as ev:
        with faults.inject(faults.FaultPlan(site="post_rbt",
                                            kind="bitflip")):
            F, X, h = st.gesv(A, B, SPEC_INFO)
    (e,) = ev
    assert bool(h.ok)
    assert e["path"].startswith("escalated:")
    assert e["escalations"] >= 1


def test_event_captures_abft_counters(rng):
    """A single injected bitflip must surface in the event's health as
    abft_detected/corrected == 1 with the struck tile located."""
    n, nb = 48, 16
    a, b = _problem(rng, n, nb)
    plan = faults.FaultPlan("post_panel", kind="bitflip", seed=5,
                            tile=(n // nb - 1, 0), nb=nb)
    with obs.recording() as ev:
        with faults.inject(plan):
            F, X, h = st.gesv(st.Matrix.from_numpy(a, nb),
                              st.Matrix.from_numpy(b, nb), ABFT_INFO)
    (e,) = ev
    assert e["abft"] is True
    assert e["health"]["abft_detected"] == 1
    assert e["health"]["abft_corrected"] == 1
    assert e["health"]["abft_site"] == [n // nb - 1, 0]
    assert e["health"]["ok"] is True


def test_event_captures_resolved_plan(rng):
    """potrf consults resolve_plan on the f32 128-multiple tile seam;
    the decision (here a test override) must land in the event."""
    from slate_tpu.tune.plans import TilePlan, plan_override
    n = 128
    hpd = _hpd(rng, n).astype(np.float32)
    b = rng.standard_normal((n, 4)).astype(np.float32)
    with plan_override("potrf_tile", TilePlan("xla", 128, 8)):
        with obs.recording() as ev:
            st.posv(st.HermitianMatrix.from_numpy(hpd, n),
                    st.Matrix.from_numpy(b, n))
    (e,) = ev
    ops = {p["op"]: p for p in e["plans"]}
    assert ops["potrf_tile"]["source"] == "override"
    assert ops["potrf_tile"]["kernel"] == "xla"


def test_ring_buffer_and_enable_disable(rng):
    a, b = _problem(rng)
    A = st.Matrix.from_numpy(a, 16)
    B = st.Matrix.from_numpy(b, 16)
    obs.clear()
    assert not obs.enabled()
    obs.enable()
    try:
        assert obs.enabled()
        st.gesv(A, B)
    finally:
        obs.disable()
    assert not obs.enabled()
    recent = obs.recent(1)
    assert recent and recent[0]["op"] == "gesv"
    obs.clear()
    assert obs.recent() == []


# ------------------------------------------------------------ sentinel


def test_sentinel_warns_on_retrace_churn(rng, monkeypatch):
    monkeypatch.setenv("SLATE_OBS_RETRACE_LIMIT", "2")
    obs.reset_sentinel()
    a, b = _problem(rng)
    aj, bj = jnp.asarray(a), jnp.asarray(b)
    try:
        with pytest.warns(obs.SlateRetraceWarning, match="re-jitting"):
            for _ in range(3):           # fresh jit each time: retraces
                jax.jit(_gesv_fn(16))(aj, bj)
        stats = obs.sentinel_stats()
        key = [k for k in stats if k.endswith("gesv")]
        assert key and stats[key[0]]["traces"] >= 3
        assert stats[key[0]]["max_per_signature"] >= 3
        # once per op: a fourth retrace must stay silent
        with warnings.catch_warnings():
            warnings.simplefilter("error", obs.SlateRetraceWarning)
            jax.jit(_gesv_fn(16))(aj, bj)
    finally:
        obs.reset_sentinel()


def test_sentinel_warns_on_signature_explosion(rng, monkeypatch):
    monkeypatch.setenv("SLATE_OBS_SIGNATURE_LIMIT", "2")
    obs.reset_sentinel()
    try:
        with pytest.warns(obs.SlateRetraceWarning, match="signatures"):
            for n in (16, 24, 32):       # distinct shapes: new signatures
                a, b = _problem(rng, n=n, nb=8)
                jax.jit(_gesv_fn(8))(jnp.asarray(a), jnp.asarray(b))
    finally:
        obs.reset_sentinel()


# -------------------------------------------------------------- tracer


def test_record_spans_and_exports(rng, tmp_path):
    hpd, b = _hpd(rng), _problem(rng)[1]
    with obs.record_spans() as rec:
        st.posv(st.HermitianMatrix.from_numpy(hpd, 16),
                st.Matrix.from_numpy(b, 16))
    names = {s["name"] for s in rec.spans}
    assert "slate.posv" in names
    assert all(s["dur_ms"] >= 0 for s in rec.spans)
    boundary = [s for s in rec.spans if s["name"] == "slate.posv"]
    assert boundary and boundary[0]["depth"] == 1

    chrome = tmp_path / "trace.json"
    rec.export_chrome_trace(str(chrome))
    doc = json.loads(chrome.read_text())
    assert doc["traceEvents"] and all(e["ph"] == "X"
                                      for e in doc["traceEvents"])
    assert {e["name"] for e in doc["traceEvents"]} == names

    jsonl = tmp_path / "spans.jsonl"
    rec.export_jsonl(str(jsonl))
    lines = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    assert len(lines) == len(rec.spans)
    assert all(ln["kind"] == "span" and ln["schema"] == obs.SCHEMA
               for ln in lines)


def test_spans_record_phase_breakdown_under_heev(rng):
    n = 32
    a = rng.standard_normal((n, n))
    A = st.HermitianMatrix.from_numpy(a + a.T, 16, st.Uplo.Lower)
    with obs.record_spans() as rec:
        st.heev(A)
    names = {s["name"] for s in rec.spans}
    assert {"slate.heev", "slate.heev/he2hb",
            "slate.heev/stage2"} <= names


def test_span_zero_overhead_without_recorder(rng):
    """No active recorder: span() must not allocate tokens or records."""
    from slate_tpu.obs import tracer
    assert tracer.active() is None
    hpd, b = _hpd(rng), _problem(rng)[1]
    st.posv(st.HermitianMatrix.from_numpy(hpd, 16),
            st.Matrix.from_numpy(b, 16))   # would crash if span needed one


# ------------------------------------------------------- metrics + CLI


def _write_events(path, rng):
    a, b = _problem(rng)
    A = st.Matrix.from_numpy(a, 16)
    B = st.Matrix.from_numpy(b, 16)
    obs.enable(str(path))
    try:
        st.gesv(A, B)
        st.gesv(A, B, SPEC_INFO)
        hpd = _hpd(rng)
        st.posv(st.HermitianMatrix.from_numpy(hpd, 16),
                st.Matrix.from_numpy(b, 16))
    finally:
        obs.disable()


def test_metrics_summarize_events(rng, tmp_path):
    p = tmp_path / "events.jsonl"
    _write_events(p, rng)
    s = obs.summarize([str(p)])
    assert s["counts"]["events"] == 3
    assert s["ops"]["gesv"]["count"] == 2
    assert s["ops"]["posv"]["count"] == 1
    assert s["ops"]["gesv"]["p50_ms"] > 0
    assert s["ops"]["gesv"]["error_rate"] == 0.0
    text = obs.render(s)
    assert "gesv" in text and "p50" in text


def test_cli_text_and_json(rng, tmp_path, capsys):
    p = tmp_path / "events.jsonl"
    _write_events(p, rng)
    assert obs_cli.main([str(p)]) == 0
    out = capsys.readouterr().out
    assert "gesv" in out

    assert obs_cli.main(["--json", str(p)]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["ops"]["gesv"]["count"] == 2


def test_cli_missing_file_is_reported(tmp_path, capsys):
    assert obs_cli.main([str(tmp_path / "nope.jsonl")]) == 2
    assert "nope.jsonl" in capsys.readouterr().err


def test_env_var_configures_recording(tmp_path, monkeypatch):
    monkeypatch.setenv("SLATE_OBS_EVENTS", str(tmp_path / "ev.jsonl"))
    try:
        obs_events._init_from_env()
        assert obs.enabled()
    finally:
        obs.disable()
        obs_events.configure(path="")


def test_chrome_export_preserves_span_nesting(rng):
    """The Perfetto/Chrome export must keep nested phase spans INSIDE
    their boundary span on the timeline: depth parent+1, same tid, and
    the child's [ts, ts+dur] interval contained in the parent's — that
    containment is what makes the rendered flame graph truthful."""
    import tempfile
    n = 32
    a = rng.standard_normal((n, n))
    A = st.HermitianMatrix.from_numpy(a + a.T, 16, st.Uplo.Lower)
    with obs.record_spans() as rec:
        st.heev(A)
    with tempfile.TemporaryDirectory() as d:
        path = d + "/trace.json"
        rec.export_chrome_trace(path)
        with open(path, encoding="utf-8") as fh:
            events = json.load(fh)["traceEvents"]

    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    (parent,) = by_name["slate.heev"]
    assert parent["args"]["depth"] == 1
    children = [e for name, evs in by_name.items() if name != "slate.heev"
                and name.startswith("slate.heev/") for e in evs]
    assert {e["name"] for e in children} >= {"slate.heev/he2hb",
                                             "slate.heev/stage2"}
    eps = 0.5                               # µs: ts/dur each round to 0.1
    p0, p1 = parent["ts"], parent["ts"] + parent["dur"]
    for ch in children:
        assert ch["args"]["depth"] >= parent["args"]["depth"] + 1
        assert ch["tid"] == parent["tid"]
        assert ch["ts"] >= p0 - eps
        assert ch["ts"] + ch["dur"] <= p1 + eps
        assert ch["dur"] <= parent["dur"]
    # phases must not overlap each other: he2hb finishes before stage2
    he2hb = [c for c in children if c["name"] == "slate.heev/he2hb"]
    stage2 = [c for c in children if c["name"] == "slate.heev/stage2"]
    assert he2hb and stage2
    assert he2hb[0]["ts"] + he2hb[0]["dur"] <= stage2[0]["ts"] + eps
