"""Serving survival-layer tests (docs/SERVING.md "Survival"): the
background flush loop, deadline-aware admission control, SLO-driven
backpressure, poison quarantine, and the chaos harness.

The load-bearing guarantees:

- the background loop delivers correct results to tickets while
  callers keep submitting from multiple threads, and shutdown() drains
  in-flight work or fails it loudly — never leaking a daemon thread or
  leaving a ticket unsettled;
- the watchdog converts a wedged flush (injected compile stall) into
  typed ``SlateServeTimeoutError`` failures on every pending request,
  and the wedged server refuses new work instead of queueing it into
  a black hole;
- overflow policies and deadline shedding are typed and accounted: a
  shed request's ticket holds the error, a ``serve_shed`` obs record
  is emitted, and under 2x overload the admitted requests' p99 still
  passes the declared SLO budget;
- a poisoned problem (escalation ladder exhausted) is retried in
  exactly one fresh batch, then quarantined to a singleton slow path
  with a ``serve_quarantine`` record — its neighbors' results stay
  correct throughout;
- request-id accounting: every admitted ticket settles exactly once
  (no request lost, none answered twice), including under chaos;
- a failed background flush is sticky: the next ``drain()`` re-raises
  the typed error even when the queue is already empty.

Everything here is deterministic on CPU: chaos comes from seeded
``robust.faults`` plans and the seeded Poisson workload generator, not
from real device failures.
"""

import json
import threading
import time

import numpy as np
import pytest

from slate_tpu import obs, serve
from slate_tpu.exceptions import (SlateServeError, SlateServeOverloadError,
                                  SlateServeTimeoutError)
from slate_tpu.obs import __main__ as obs_cli
from slate_tpu.obs import slo
from slate_tpu.robust import faults


def _rng():
    return np.random.default_rng(77)


def _mk_solve(rng, n, k=2, dtype=np.float32):
    a = rng.standard_normal((n, n)).astype(dtype)
    a += np.eye(n, dtype=dtype) * 4
    return a, rng.standard_normal((n, k)).astype(dtype)


def _poison_solve(n=8, k=2, dtype=np.float32):
    """A singular system: escalates in-graph AND stays unhealthy —
    deterministically exhausts the escalation ladder."""
    return np.zeros((n, n), dtype), np.ones((n, k), dtype)


def _check_solve(a, b, res, tol=1e-3):
    assert np.allclose(res.x, np.linalg.solve(
        a.astype(np.float64), b.astype(np.float64)), atol=tol)


def _serve_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("slate-serve-")]


def _shed_events(recs):
    return [e for e in recs if e.get("kind") == "serve_shed"]


# ------------------------------------------------------ background loop


def test_background_loop_delivers_correct_results():
    rng = _rng()
    cfg = serve.AdmissionConfig(flush_occupancy=4, max_batch_delay_ms=10.0)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    srv.start()
    assert srv.running()
    try:
        probs = [_mk_solve(rng, n) for n in (8, 8, 12, 12, 20, 20)]
        tickets = [srv.submit("solve", a, b) for a, b in probs]
        for (a, b), t in zip(probs, tickets):
            _check_solve(a, b, t.result(timeout=120.0))
            assert t.done() and t.error() is None
    finally:
        srv.shutdown()
    assert not srv.running()


def test_start_is_idempotent():
    srv = serve.Server(cache=serve.ExecutableCache())
    srv.start()
    try:
        before = _serve_threads()
        srv.start()                      # no second pair of threads
        assert _serve_threads() == before
    finally:
        srv.shutdown()


def test_concurrent_submit_under_live_loop_accounts_every_request():
    """4 threads pound submit() under the live loop: every ticket
    settles exactly once with a correct result, tids are unique, and a
    late duplicate delivery is dropped (first-write-wins)."""
    rng = _rng()
    cfg = serve.AdmissionConfig(max_queue=1024, flush_occupancy=6,
                                max_batch_delay_ms=2.0)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    probs = [_mk_solve(rng, n) for n in (8, 12, 20, 28)]
    srv.serve_batch([("solve", a, b) for a, b in probs])  # warm buckets
    srv.start()
    done, errs = [], []
    lock = threading.Lock()

    def pound(wid):
        try:
            local = []
            for i in range(8):
                a, b = probs[(wid + i) % len(probs)]
                local.append((a, b, srv.submit("solve", a, b)))
            for a, b, t in local:
                _check_solve(a, b, t.result(timeout=120.0))
                with lock:
                    done.append(t)
        except Exception as e:          # surfaced below, not swallowed
            with lock:
                errs.append(e)

    threads = [threading.Thread(target=pound, args=(w,)) for w in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(180.0)
    srv.shutdown()
    assert errs == []
    assert len(done) == 32
    assert len({t.tid for t in done}) == 32          # no double-admission
    # no request answered twice: a late write is refused
    assert all(not t.deliver("late") for t in done)


def test_shutdown_drains_queued_requests():
    rng = _rng()
    # occupancy watermark unreachably high: requests sit queued until
    # shutdown's drain settles them
    cfg = serve.AdmissionConfig(flush_occupancy=1000,
                                max_batch_delay_ms=60_000.0)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    srv.start()
    a, b = _mk_solve(rng, 8)
    tickets = [srv.submit("solve", a, b) for _ in range(3)]
    srv.shutdown(drain=True)
    for t in tickets:
        _check_solve(a, b, t.result(timeout=1.0))


def test_shutdown_without_drain_fails_loudly():
    rng = _rng()
    cfg = serve.AdmissionConfig(flush_occupancy=1000,
                                max_batch_delay_ms=60_000.0)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    srv.start()
    a, b = _mk_solve(rng, 8)
    with obs.recording() as recs:
        tickets = [srv.submit("solve", a, b) for _ in range(3)]
        srv.shutdown(drain=False)
    for t in tickets:
        with pytest.raises(SlateServeTimeoutError) as ei:
            t.result(timeout=1.0)
        assert ei.value.reason == "shutdown"
    assert len(_shed_events(recs)) == 3
    assert srv.queue.stats()["shed"] >= 3


def test_shutdown_never_leaks_daemon_threads():
    srv = serve.Server(cache=serve.ExecutableCache())
    assert _serve_threads() == []
    srv.start()
    assert len(_serve_threads()) == 2        # flush loop + watchdog
    srv.shutdown()
    assert _serve_threads() == []
    # submitting after shutdown is a typed closed-queue error
    a, b = _mk_solve(_rng(), 8)
    with pytest.raises(SlateServeTimeoutError) as ei:
        srv.submit("solve", a, b)
    assert ei.value.reason == "shutdown"


def test_warm_server_async_path_is_retrace_free():
    """The background path reuses the synchronous executables: a server
    warmed via serve_batch compiles nothing and retraces nothing when
    the same workload arrives through the live loop.  The occupancy
    watermark equals the workload size, so the loop flushes ONE batch
    with the same per-bucket group sizes the warm pass compiled."""
    rng = _rng()
    probs = [_mk_solve(rng, n) for n in (8, 8, 20, 20)]
    cfg = serve.AdmissionConfig(flush_occupancy=4,
                                max_batch_delay_ms=60_000.0)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    srv.serve_batch([("solve", a, b) for a, b in probs])   # warm
    srv.start()
    try:
        with obs.recording() as recs:
            tickets = [srv.submit("solve", a, b) for a, b in probs]
            for (a, b), t in zip(probs, tickets):
                _check_solve(a, b, t.result(timeout=120.0))
        evs = [e for e in recs if e.get("kind") == "serve_batch"]
        assert evs and all(not e["compiled"] for e in evs)
        assert all(e["retraces"] == 0 for e in evs)
    finally:
        srv.shutdown()


# ------------------------------------------------- watchdog / wedging


def test_watchdog_fails_wedged_flush_with_typed_error():
    """Injected compile stall >> watchdog budget: every pending ticket
    fails with SlateServeTimeoutError, the server reports wedged, and
    new submits are refused instead of silently queued."""
    rng = _rng()
    cfg = serve.AdmissionConfig(flush_occupancy=1, max_batch_delay_ms=1.0,
                                watchdog_timeout_s=0.2)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    srv.start()
    a, b = _mk_solve(rng, 8)
    try:
        with obs.recording() as recs:
            with faults.inject(faults.FaultPlan(
                    "serve_compile_stall", transient=True, delay_s=2.0)):
                t = srv.submit("solve", a, b)
                with pytest.raises(SlateServeTimeoutError) as ei:
                    t.result(timeout=30.0)
        assert ei.value.reason == "watchdog"
        assert srv.wedged() is not None
        info = srv.health_info()
        assert info["wedged"] is not None
        with pytest.raises(SlateServeTimeoutError) as ei2:
            srv.submit("solve", a, b)
        assert ei2.value.reason == "wedged"
        sheds = _shed_events(recs)
        assert any(e["reason"] == "watchdog" for e in sheds)
    finally:
        # the wedged flush thread is still sleeping through the injected
        # stall; wait it out so its late (dropped) delivery cannot leak
        # obs events into the next test's recording
        zombies = _serve_threads()
        srv.shutdown()
        for z in zombies:
            z.join(120.0)
        assert _serve_threads() == []


# ------------------------------------------- admission control policies


def test_overflow_reject_is_typed():
    rng = _rng()
    cfg = serve.AdmissionConfig(max_queue=4, overflow="reject")
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    a, b = _mk_solve(rng, 8)
    with obs.recording() as recs:
        for _ in range(4):
            srv.submit("solve", a, b)
        with pytest.raises(SlateServeOverloadError) as ei:
            srv.submit("solve", a, b)
    assert ei.value.policy == "reject"
    (shed,) = _shed_events(recs)
    assert shed["reason"] == "overflow_reject"
    for res in srv.drain():
        _check_solve(a, b, res)


def test_overflow_shed_oldest_fails_victim_ticket():
    rng = _rng()
    cfg = serve.AdmissionConfig(max_queue=4, overflow="shed_oldest")
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    a, b = _mk_solve(rng, 8)
    with obs.recording() as recs:
        tickets = [srv.submit("solve", a, b) for _ in range(5)]
    victim, survivors = tickets[0], tickets[1:]
    assert victim.done()
    with pytest.raises(SlateServeOverloadError) as ei:
        victim.result(timeout=0.1)
    assert ei.value.policy == "shed_oldest"
    (shed,) = _shed_events(recs)
    assert shed["reason"] == "overflow_shed_oldest"
    srv.drain()
    for t in survivors:
        _check_solve(a, b, t.result(timeout=1.0))


def test_overflow_block_times_out_typed():
    rng = _rng()
    cfg = serve.AdmissionConfig(max_queue=2, overflow="block",
                                block_timeout_s=0.05)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    a, b = _mk_solve(rng, 8)
    srv.submit("solve", a, b)
    srv.submit("solve", a, b)
    t0 = time.perf_counter()
    with pytest.raises(SlateServeOverloadError) as ei:
        srv.submit("solve", a, b)
    assert ei.value.policy == "block"
    assert time.perf_counter() - t0 >= 0.04


def test_overflow_block_unblocks_when_space_frees():
    rng = _rng()
    cfg = serve.AdmissionConfig(max_queue=2, overflow="block",
                                block_timeout_s=30.0)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    a, b = _mk_solve(rng, 8)
    srv.submit("solve", a, b)
    srv.submit("solve", a, b)
    admitted = threading.Event()

    def blocked_submit():
        srv.submit("solve", a, b)
        admitted.set()

    t = threading.Thread(target=blocked_submit)
    t.start()
    assert not admitted.wait(0.05)       # genuinely blocked on the full
    srv.drain()                          # queue; take_all frees space
    assert admitted.wait(10.0)
    t.join(10.0)
    for res in srv.drain():
        _check_solve(a, b, res)


def test_deadline_shed_at_admission_uses_governor_estimate():
    """A request whose deadline is tighter than the rolling service
    estimate is shed at submit — it never occupies a queue slot."""
    rng = _rng()
    cfg = serve.AdmissionConfig(slo_budget_ms=100.0)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    for _ in range(16):
        srv.queue.governor.observe(50.0)     # rolling p50 = 50ms
    a, b = _mk_solve(rng, 8)
    with obs.recording() as recs:
        with pytest.raises(SlateServeTimeoutError) as ei:
            srv.submit("solve", a, b, deadline_ms=1.0)
    assert ei.value.reason == "deadline"
    assert srv.queue.depth() == 0
    (shed,) = _shed_events(recs)
    assert shed["reason"] == "deadline"
    # a deadline wider than the estimate is admitted
    t = srv.submit("solve", a, b, deadline_ms=10_000.0)
    srv.drain()
    _check_solve(a, b, t.result(timeout=1.0))


def test_deadline_expiry_in_queue_sheds_at_flush():
    rng = _rng()
    srv = serve.Server(cache=serve.ExecutableCache())
    a, b = _mk_solve(rng, 8)
    t = srv.submit("solve", a, b, deadline_ms=1.0)
    time.sleep(0.02)
    with obs.recording() as recs:
        assert srv.drain() == []
    with pytest.raises(SlateServeTimeoutError) as ei:
        t.result(timeout=0.1)
    assert ei.value.reason == "deadline"
    (shed,) = _shed_events(recs)
    assert shed["reason"] == "deadline" and shed["age_ms"] > 0


def test_slo_backpressure_halves_capacity():
    gov = slo.LatencyGovernor(budget_ms=10.0, window=8)
    q = serve.AdmissionQueue(serve.AdmissionConfig(max_queue=8), gov)
    assert q.capacity() == 8
    for _ in range(8):
        gov.observe(50.0)                # p99 blows the 10ms budget
    assert gov.overloaded()
    assert q.capacity() == 4
    gov2 = slo.LatencyGovernor(budget_ms=None)
    for _ in range(8):
        gov2.observe(1e9)
    assert not gov2.overloaded()         # no budget -> no backpressure


def test_two_x_overload_shed_keeps_admitted_p99_in_budget():
    """The acceptance scenario: 2x the queue capacity offered under
    shed_oldest.  Exactly half is shed (typed + accounted) and the
    ADMITTED requests' p99 latency still passes the declared budget —
    shedding is how the server keeps its latency promise."""
    rng = _rng()
    budget_ms = 60_000.0                 # generous: CPU CI boxes vary
    cfg = serve.AdmissionConfig(max_queue=8, overflow="shed_oldest",
                                slo_budget_ms=budget_ms)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    a, b = _mk_solve(rng, 8)
    srv.serve_batch([("solve", a, b)])   # warm: steady-state latencies
    with obs.recording() as recs:
        tickets = [srv.submit("solve", a, b) for _ in range(16)]
        srv.drain()
    shed = [t for t in tickets if t.error() is not None]
    served = [t for t in tickets if t.error() is None]
    assert len(shed) == 8 and len(served) == 8
    assert all(isinstance(t.error(), SlateServeOverloadError)
               for t in shed)
    for t in served:
        _check_solve(a, b, t.result(timeout=1.0))
    stats = slo.aggregate(list(recs))
    union = stats["*"]
    assert union["problems"] == 8 and union["shed"] == 8
    assert union["shed_per_1k"] == 500.0   # 8 shed per 16 offered
    verdicts = slo.evaluate(stats, {"*": {"latency_p99_ms": budget_ms}})
    assert all(v["ok"] for v in verdicts)


# --------------------------------------------------- poison quarantine


def test_poison_quarantined_after_exactly_one_fresh_batch_retry():
    """A deterministic poison (singular system) rides the original
    batch, one fresh-batch retry, then the singleton quarantine path:
    three serve_batch records plus one serve_quarantine, neighbors
    correct the whole way."""
    rng = _rng()
    good_a, good_b = _mk_solve(rng, 8)
    bad_a, bad_b = _poison_solve(8)
    srv = serve.Server(cache=serve.ExecutableCache())
    with obs.recording() as recs:
        res = srv.serve_batch([("solve", good_a, good_b),
                               ("solve", bad_a, bad_b),
                               ("solve", good_a, good_b)])
    batches = [e for e in recs if e.get("kind") == "serve_batch"]
    quars = [e for e in recs if e.get("kind") == "serve_quarantine"]
    assert [e["problems"] for e in batches] == [3, 1, 1]
    (quar,) = quars
    assert quar["reason"] == "escalation_exhausted"
    assert quar["retries"] == 1          # exactly one fresh-batch retry
    assert not quar["ok"]
    # neighbors never see the poison: correct results, healthy flags
    _check_solve(good_a, good_b, res[0])
    _check_solve(good_a, good_b, res[2])
    assert bool(res[0].health.ok) and bool(res[2].health.ok)
    # the poisoned slot reports its own exhaustion, loudly
    assert res[1].escalated and not bool(res[1].health.ok)
    assert srv.health_info()["quarantined"] == 1


def test_poison_quarantine_on_background_path():
    rng = _rng()
    good_a, good_b = _mk_solve(rng, 8)
    bad_a, bad_b = _poison_solve(8)
    cfg = serve.AdmissionConfig(flush_occupancy=3,
                                max_batch_delay_ms=10.0)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    srv.start()
    try:
        with obs.recording() as recs:
            tg1 = srv.submit("solve", good_a, good_b)
            tp = srv.submit("solve", bad_a, bad_b)
            tg2 = srv.submit("solve", good_a, good_b)
            _check_solve(good_a, good_b, tg1.result(timeout=120.0))
            _check_solve(good_a, good_b, tg2.result(timeout=120.0))
            poisoned = tp.result(timeout=120.0)
        assert poisoned.escalated and not bool(poisoned.health.ok)
        assert [e["kind"] for e in recs].count("serve_quarantine") == 1
    finally:
        srv.shutdown()


# -------------------------------------------------------- sticky errors


def test_failed_background_flush_is_sticky_on_empty_drain(monkeypatch):
    """A flush that dies in the loop must not evaporate: the ticket
    holds the typed error AND the next drain() re-raises it even though
    the queue is empty by then — then clears it (raise once)."""
    rng = _rng()
    cfg = serve.AdmissionConfig(flush_occupancy=1, max_batch_delay_ms=1.0)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)

    def boom(*args, **kwargs):
        raise RuntimeError("injected flush failure")

    monkeypatch.setattr(srv, "_run_group", boom)
    srv.start()
    a, b = _mk_solve(rng, 8)
    try:
        t = srv.submit("solve", a, b)
        with pytest.raises(SlateServeError):
            t.result(timeout=30.0)
        assert srv.queue.depth() == 0
        # the ticket settles inside the flush; the server-level sticky
        # error lands when the flush returns — wait for that handoff
        deadline = time.perf_counter() + 10.0
        while srv._flush_error is None and time.perf_counter() < deadline:
            time.sleep(0.005)
        with pytest.raises(SlateServeError, match="injected"):
            srv.drain()
        assert srv.drain() == []         # sticky error raises ONCE
    finally:
        srv.shutdown()


def test_sync_drain_group_failure_lands_on_tickets(monkeypatch):
    rng = _rng()
    srv = serve.Server(cache=serve.ExecutableCache())

    def boom(*args, **kwargs):
        raise RuntimeError("injected group failure")

    monkeypatch.setattr(srv, "_run_group", boom)
    a, b = _mk_solve(rng, 8)
    t = srv.submit("solve", a, b)
    with pytest.raises(SlateServeError, match="injected"):
        srv.drain()
    assert isinstance(t.error(), SlateServeError)


# --------------------------------------------------------- chaos harness


def test_chaos_flush_delay_ages_the_batch():
    rng = _rng()
    srv = serve.Server(cache=serve.ExecutableCache())
    a, b = _mk_solve(rng, 8)
    srv.serve_batch([("solve", a, b)])   # warm
    srv.submit("solve", a, b)
    with obs.recording() as recs:
        with faults.inject(faults.FaultPlan("serve_flush_delay",
                                            delay_s=0.05)):
            (res,) = srv.drain()
    _check_solve(a, b, res)
    (ev,) = [e for e in recs if e.get("kind") == "serve_batch"]
    assert all(age >= 50.0 for age in ev["age_at_flush_ms"])


def test_chaos_cache_evict_forces_recompile_but_serves():
    rng = _rng()
    cache = serve.ExecutableCache()
    srv = serve.Server(cache=cache)
    a, b = _mk_solve(rng, 8)
    srv.serve_batch([("solve", a, b)])   # warm
    assert cache.stats()["entries"] == 1
    with obs.recording() as recs:
        with faults.inject(faults.FaultPlan("serve_cache_evict",
                                            transient=True)):
            (res,) = srv.serve_batch([("solve", a, b)])
    _check_solve(a, b, res)
    (ev,) = [e for e in recs if e.get("kind") == "serve_batch"]
    assert ev["compiled"]                # eviction forced the recompile
    assert cache.stats()["entries"] == 1


def test_host_fire_transient_consumes_once_per_activation():
    plan = faults.FaultPlan("serve_compile_stall", transient=True,
                            delay_s=0.1)
    assert faults.host_fire("serve_compile_stall") is None  # inactive
    with faults.inject(plan):
        assert faults.host_fire("serve_compile_stall") is plan
        assert faults.host_fire("serve_compile_stall") is None  # spent
    with faults.inject(plan):            # fresh activation, fresh strike
        assert faults.host_fire("serve_compile_stall") is plan
    persistent = faults.FaultPlan("serve_flush_delay", delay_s=0.1)
    with faults.inject(persistent):
        assert faults.host_fire("serve_flush_delay") is persistent
        assert faults.host_fire("serve_flush_delay") is persistent
    # traced sites never leak through the host hook
    with faults.inject(faults.FaultPlan("input")):
        assert faults.host_fire("input") is None


def test_poisson_workload_is_deterministic_and_well_formed():
    w1 = faults.poisson_workload(42, 12, 200.0, (8, 16))
    w2 = faults.poisson_workload(42, 12, 200.0, (8, 16))
    assert len(w1) == 12
    arrivals = [t for t, _, _, _ in w1]
    assert arrivals == sorted(arrivals)
    for (t1, op1, a1, b1), (t2, op2, a2, b2) in zip(w1, w2):
        assert t1 == t2 and op1 == op2
        assert np.array_equal(a1, a2) and np.array_equal(b1, b2)
    assert [t for t, *_ in faults.poisson_workload(
        43, 12, 200.0, (8, 16))] != arrivals
    # every request round-trips the server healthily (well-conditioned)
    srv = serve.Server(cache=serve.ExecutableCache())
    results = srv.serve_batch([(op, a, b) for _, op, a, b in w1[:6]])
    assert all(bool(r.health.ok) for r in results)


# ------------------------------------------------------- obs / CLI table


def test_cli_serving_table_renders_shed_and_quarantine_columns(
        tmp_path, capsys):
    """The metrics CLI smoke test: a stream with batches, sheds and a
    quarantine renders the serving table with the shed/1k and quar/1k
    columns populated."""
    rng = _rng()
    good_a, good_b = _mk_solve(rng, 8)
    bad_a, bad_b = _poison_solve(8)
    cfg = serve.AdmissionConfig(max_queue=2, overflow="shed_oldest")
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    with obs.recording() as recs:
        for _ in range(4):               # 2 admitted, 2 shed
            srv.submit("solve", good_a, good_b)
        srv.submit("solve", bad_a, bad_b)  # sheds one more, then poisons
        srv.drain()
    path = tmp_path / "events.jsonl"
    path.write_text("".join(json.dumps(e) + "\n" for e in recs))

    row = obs.summarize([str(path)])["serve"]["solve/float32"]
    assert row["shed"] == 3 and row["quarantined"] == 1
    # served problems count every executed batch slot: the original
    # pair, the poison's fresh-batch retry, and its quarantine singleton
    assert row["problems"] == 4
    assert row["shed_per_1k"] == round(1000.0 * 3 / 7, 2)
    assert row["quar_per_1k"] == 250.0   # 1 quarantined per 4 served
    assert obs_cli.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "shed/1k" in out and "quar/1k" in out
    assert "428.57" in out and " 250 " in out   # _fmt drops trailing .0


def test_health_info_reports_front_door_state():
    cfg = serve.AdmissionConfig(slo_budget_ms=250.0)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    info = srv.health_info()
    assert info["queue"]["depth"] == 0 and not info["queue"]["closed"]
    assert info["running"] is False and info["wedged"] is None
    assert info["quarantined"] == 0
    assert info["slo_budget_ms"] == 250.0 and info["slo_p99_ms"] is None
