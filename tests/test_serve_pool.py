"""Elastic device pool tests (docs/SERVING.md "Device pool"): multi-
device serving that survives losing a device, plus online ladder
retuning.

The load-bearing guarantees:

- **kill-a-device drill** (the acceptance drill): with K >= 2 pool
  members and a seeded ``serve_device_fail`` chaos plan killing member
  0, every ticket settles with a correct result (zero lost), the
  failed-over results are BIT-IDENTICAL to a no-fault run (the same
  packed batch redispatches the same executable), the sick member is
  quarantined after ``strike_limit`` strikes, a clean canary probe
  readmits it, and the whole sequence is visible as ``serve_device``
  obs records;
- a warm pool is retrace-free per device, pinned warnings-as-errors;
- a wedged member (``serve_device_slow`` past the dispatch deadline)
  fails over the same way — the zombie dispatch's late result is
  dropped by first-write-wins, never double-delivered;
- with one survivor the pool reports ``degraded()`` and keeps serving;
  with none it raises a loud typed ``SlateServeOverloadError``;
- per-device SLO truth: the governor files latencies per member,
  ``overload_fraction`` scales admission capacity by the sick share
  (not the world), and ``obs --slo`` budgets can target
  ``device:<id>`` rows;
- **online retune drill**: a bimodal size stream triggers EXACTLY one
  ladder hot-swap (``serve_retune`` record), subsequent flushes bucket
  on the fitted ladder, and per-batch ``padding_waste`` drops.

Everything here is deterministic on CPU: K members of the pool are the
same CPU device (tests/conftest.py forces 8 virtual devices), chaos
comes from seeded ``robust.faults`` plans with ``device=i`` targeting.
"""

import json
import threading
import time
import warnings

import jax
import numpy as np
import pytest

from slate_tpu import obs, serve
from slate_tpu.exceptions import SlateServeError, SlateServeOverloadError
from slate_tpu.obs import __main__ as obs_cli
from slate_tpu.obs import slo
from slate_tpu.robust import faults


def _rng():
    return np.random.default_rng(177)


def _mk_solve(rng, n, k=2, dtype=np.float32):
    a = rng.standard_normal((n, n)).astype(dtype)
    a += np.eye(n, dtype=dtype) * (4 + np.sqrt(n))
    return a, rng.standard_normal((n, k)).astype(dtype)


def _check_solve(a, b, res, tol=1e-3):
    assert np.allclose(res.x, np.linalg.solve(
        a.astype(np.float64), b.astype(np.float64)), rtol=tol, atol=tol)


def _pool_server(members=2, strike_limit=1, canary_interval_s=30.0,
                 dispatch_timeout_s=None, cache=None, admission=None):
    """A Server over a K-member pool; every member is the same CPU
    device, which shares executables (one compile warms the pool) while
    keeping the member-level failure machinery fully independent."""
    devs = [jax.local_devices()[0]] * members
    pool = serve.DevicePool(
        devs, serve.PoolConfig(strike_limit=strike_limit,
                               canary_interval_s=canary_interval_s,
                               dispatch_timeout_s=dispatch_timeout_s))
    return serve.Server(cache=cache or serve.ExecutableCache(),
                        admission=admission, pool=pool)


def _device_events(recs, event=None):
    out = [e for e in recs if e.get("kind") == "serve_device"]
    if event is not None:
        out = [e for e in out if e.get("event") == event]
    return out


def _batch_events(recs):
    return [e for e in recs if e.get("kind") == "serve_batch"]


# --------------------------------------------------------- pool basics


def test_pool_defaults_to_local_devices():
    pool = serve.DevicePool()
    assert pool.size() == len(jax.local_devices())
    assert pool.healthy_count() == pool.size()
    assert not pool.degraded()


def test_default_server_is_single_member():
    srv = serve.Server(cache=serve.ExecutableCache())
    assert srv.pool.size() == 1
    assert srv.pool.stats()["failovers"] == 0


def test_pool_config_validates():
    with pytest.raises(ValueError, match="strike_limit"):
        serve.PoolConfig(strike_limit=0)
    with pytest.raises(ValueError, match="canary_interval_s"):
        serve.PoolConfig(canary_interval_s=0.0)
    with pytest.raises(ValueError, match="device"):
        faults.FaultPlan("serve_device_fail", device=-1)


def test_round_robin_spreads_groups_across_members():
    """Two groups in one flush land on two distinct members — batches
    are in flight on different devices, not serialized behind one."""
    rng = _rng()
    srv = _pool_server(members=2)
    with obs.recording() as recs:
        for n in (16, 48):          # buckets 32 and 64 -> two groups
            for _ in range(2):
                srv.submit("solve", *_mk_solve(rng, n))
        srv.drain()
    devs = {e["device_id"] for e in _batch_events(recs)}
    assert devs == {0, 1}
    assert all(e["failovers"] == 0 for e in _batch_events(recs))


# -------------------------------------------------- kill-a-device drill


def _serve_once(srv, reqs):
    tickets = [srv.submit(op, a, b) for op, a, b in reqs]
    results = srv.drain()
    return [results[int(t)] for t in tickets]


@pytest.mark.parametrize("kind", ["nan", "inf"])
def test_kill_a_device_drill(kind):
    """The acceptance drill: kill member 0 (non-finite lie or dispatch
    exception), and the SAME packed batch fails over to member 1 with
    zero lost tickets, bit-identical results, quarantine, and canary
    readmission."""
    rng = _rng()
    reqs = [("solve", *_mk_solve(rng, 12)) for _ in range(4)]
    cache = serve.ExecutableCache()

    # baseline: no fault, same cache -> same executable
    base = _serve_once(_pool_server(members=2, cache=cache), reqs)

    srv = _pool_server(members=2, cache=cache)
    plan = faults.FaultPlan("serve_device_fail", kind=kind,
                            transient=True, device=0)
    with obs.recording() as recs:
        with faults.inject(plan):
            got = _serve_once(srv, reqs)

    # zero lost tickets, correct and BIT-IDENTICAL to the no-fault run
    assert len(got) == len(reqs)
    for (op, a, b), res, ref in zip(reqs, base, got):
        assert res is not None and ref is not None
        _check_solve(a, b, res)
        assert res.x.tobytes() == ref.x.tobytes()
        assert bool(res.health.ok) and not res.escalated

    # the failover ladder ran: strike -> quarantine(0) -> survivor(1)
    st = srv.pool.stats()
    assert st["failovers"] == 1 and st["quarantines"] == 1
    fo = _device_events(recs, "failover")
    assert [e["device_id"] for e in fo] == [0]
    assert fo[0]["reason"] == ("nonfinite" if kind == "nan"
                               else "exception")
    assert _device_events(recs, "quarantine")[0]["device_id"] == 0
    batches = _batch_events(recs)
    assert batches and batches[0]["device_id"] == 1
    assert batches[0]["failovers"] == 1
    assert srv.pool.healthy_count() == 1 and srv.pool.degraded()

    # clean canary -> readmission (the transient strike is spent)
    with obs.recording() as recs2:
        assert srv.pool.probe(0)
    assert srv.pool.healthy_count() == 2 and not srv.pool.degraded()
    assert srv.pool.stats()["readmissions"] == 1
    readmit = _device_events(recs2, "readmit")
    assert readmit and readmit[0]["device_id"] == 0
    assert readmit[0]["quarantined_ms"] is not None

    # the readmitted member serves again
    reqs2 = [("solve", *_mk_solve(rng, 12)) for _ in range(2)]
    for (op, a, b), res in zip(reqs2, _serve_once(srv, reqs2)):
        _check_solve(a, b, res)


def test_targeted_chaos_plan_is_not_eaten_by_other_members():
    """FaultPlan(device=1) misses member 0 WITHOUT consuming the
    transient strike — the kill lands on member 1 even when member 0
    reaches the site first."""
    plan = faults.FaultPlan("serve_device_fail", transient=True, device=1)
    with faults.inject(plan):
        assert faults.host_fire("serve_device_fail", device=0) is None
        assert faults.host_fire("serve_device_fail", device=1) is plan
        # spent: exactly one kill per activation
        assert faults.host_fire("serve_device_fail", device=1) is None
    assert faults.host_fire("serve_device_fail", device=1) is None


def test_warm_pool_is_retrace_free_per_device():
    """Warnings-as-errors pin: after one warm pass, repeat flushes on a
    K-member pool trace and compile NOTHING new on any member."""
    rng = _rng()
    srv = _pool_server(members=2)
    reqs = [("solve", *_mk_solve(rng, 16)) for _ in range(3)]
    _serve_once(srv, reqs)                       # warm every member
    traces0 = sum(s["traces"] for s in obs.sentinel_stats().values())
    with warnings.catch_warnings():
        warnings.simplefilter("error", obs.SlateRetraceWarning)
        with obs.recording() as recs:
            for _ in range(4):
                reqs = [("solve", *_mk_solve(rng, 16)) for _ in range(3)]
                for (op, a, b), res in zip(reqs, _serve_once(srv, reqs)):
                    _check_solve(a, b, res)
    assert sum(s["traces"]
               for s in obs.sentinel_stats().values()) == traces0
    assert all(e["retraces"] == 0 and not e["compiled"]
               for e in _batch_events(recs))


def test_wedged_member_deadline_failover():
    """serve_device_slow past the dispatch deadline reads as a wedged
    device: the pool moves on to a survivor; the zombie's late result
    is dropped (first-write-wins), never double-delivered."""
    rng = _rng()
    srv = _pool_server(members=2, dispatch_timeout_s=0.25)
    a, b = _mk_solve(rng, 12)
    _serve_once(srv, [("solve", a, b)])          # warm; rr now at 1
    plan = faults.FaultPlan("serve_device_slow", transient=True,
                            device=1, delay_s=1.5)
    with obs.recording() as recs:
        with faults.inject(plan):
            (res,) = _serve_once(srv, [("solve", a, b)])
    _check_solve(a, b, res)
    fo = _device_events(recs, "failover")
    assert fo and fo[0]["reason"] == "deadline" and fo[0]["device_id"] == 1
    assert srv.pool.stats()["failovers"] == 1
    # let the zombie dispatch thread drain before the test ends
    time.sleep(1.5)
    assert not [t for t in threading.enumerate()
                if t.name.startswith("slate-serve-dispatch")]


def test_canary_flake_refuses_readmission():
    """A flaky canary keeps the sick member quarantined; a later clean
    probe readmits it."""
    rng = _rng()
    srv = _pool_server(members=2)
    reqs = [("solve", *_mk_solve(rng, 12)) for _ in range(2)]
    kill = faults.FaultPlan("serve_device_fail", kind="inf", device=0)
    flake = faults.FaultPlan("serve_canary_flake", device=0)
    with obs.recording() as recs:
        with faults.inject(kill, flake):
            got = _serve_once(srv, reqs)         # member 0 dies
            assert srv.pool.healthy_count() == 1
            assert not srv.pool.probe(0)         # canary flakes
            assert srv.pool.healthy_count() == 1
    for (op, a, b), res in zip(reqs, got):
        _check_solve(a, b, res)
    pf = _device_events(recs, "probe_fail")
    assert pf and pf[0]["device_id"] == 0 and pf[0]["reason"] == "flake"
    assert srv.pool.probe(0)                     # plan gone: clean probe
    assert srv.pool.healthy_count() == 2


def test_pool_exhausted_raises_typed_overload():
    """Every member dead -> loud typed SlateServeOverloadError on the
    drain AND on every ticket; canary probes bring the pool back."""
    rng = _rng()
    srv = _pool_server(members=2)
    a, b = _mk_solve(rng, 12)
    kill = faults.FaultPlan("serve_device_fail", kind="inf")  # any member
    with faults.inject(kill):
        t = srv.submit("solve", a, b)
        with pytest.raises(SlateServeError):
            srv.drain()
        assert isinstance(t.error(), SlateServeError)
        assert srv.pool.healthy_count() == 0
    # recovery: clean canaries readmit both members
    assert srv.pool.probe(0) and srv.pool.probe(1)
    (res,) = _serve_once(srv, [("solve", a, b)])
    _check_solve(a, b, res)


def test_degraded_single_survivor_keeps_serving():
    rng = _rng()
    srv = _pool_server(members=3)
    kill = faults.FaultPlan("serve_device_fail", kind="inf", device=0)
    reqs = [("solve", *_mk_solve(rng, 12)) for _ in range(2)]
    with faults.inject(kill):
        for (op, a, b), res in zip(reqs, _serve_once(srv, reqs)):
            _check_solve(a, b, res)
    assert srv.pool.healthy_count() == 2
    info = srv.health_info()
    assert info["pool"]["devices"] == 3
    assert info["pool"]["healthy"] == 2
    assert not info["degraded"]


def test_background_loop_kill_drill_zero_lost_tickets():
    """The drill under the background flush loop: a transient device
    kill mid-stream loses nothing — every admitted ticket settles with
    a correct result."""
    rng = _rng()
    cfg = serve.AdmissionConfig(flush_occupancy=4, max_batch_delay_ms=10.0)
    srv = _pool_server(members=2, admission=cfg)
    srv.start()
    try:
        probs = [_mk_solve(rng, 12) for _ in range(12)]
        plan = faults.FaultPlan("serve_device_fail", transient=True,
                                device=0)
        with faults.inject(plan):
            tickets = [(a, b, srv.submit("solve", a, b))
                       for a, b in probs]
            for a, b, t in tickets:
                _check_solve(a, b, t.result(timeout=60.0))
    finally:
        srv.shutdown()
    assert srv.pool.stats()["failovers"] >= 1


# ------------------------------------------------ per-device SLO truth


def test_governor_files_per_device_tails():
    gov = slo.LatencyGovernor(budget_ms=100.0)
    for _ in range(20):
        gov.observe(10.0, device=0)
        gov.observe(400.0, device=1)
    assert gov.p99_ms(0) < 100.0 < gov.p99_ms(1)
    assert gov.overloaded(1) and not gov.overloaded(0)
    assert gov.overload_fraction() == 0.5
    p99s = gov.device_p99s()
    assert set(p99s) == {0, 1}


def test_overload_fraction_scales_capacity_not_halves():
    """One slow member out of four trims capacity by an eighth; the
    union-only stream keeps the pre-pool halving."""
    cfg = serve.AdmissionConfig(max_queue=64, slo_budget_ms=100.0)
    q = serve.AdmissionQueue(cfg)
    for dev in range(4):
        for _ in range(10):
            q.governor.observe(400.0 if dev == 0 else 10.0, device=dev)
    assert q.governor.overload_fraction() == 0.25
    assert q.capacity() == int(64 * (1 - 0.25 / 2))    # 56, not 32
    # union-only governor: fraction collapses to the old halving
    q2 = serve.AdmissionQueue(serve.AdmissionConfig(
        max_queue=64, slo_budget_ms=100.0))
    for _ in range(10):
        q2.governor.observe(400.0)
    assert q2.governor.overload_fraction() == 1.0
    assert q2.capacity() == 32


def test_slo_budgets_target_device_rows():
    """aggregate() grows device:<id> rows from device-stamped batches,
    and --slo budgets can fail a single slow member's own row."""
    rng = _rng()
    srv = _pool_server(members=2)
    with obs.recording() as recs:
        for _ in range(3):
            reqs = [("solve", *_mk_solve(rng, n)) for n in (8, 24)
                    for _ in range(2)]
            _serve_once(srv, reqs)
    stats = slo.aggregate(recs)
    dev_rows = [k for k in stats if k.startswith("device:")]
    assert set(dev_rows) == {"device:0", "device:1"}
    assert sum(stats[k]["problems"] for k in dev_rows) == 12
    verdicts = slo.evaluate(stats, {
        "device:0": {"latency_p99_ms": 1e9},
        "device:1": {"problems": 1},
    })
    assert all(v["ok"] for v in verdicts)
    bad = slo.evaluate(stats, {"device:0": {"latency_p99_ms": 1e-9}})
    assert not bad[0]["ok"]


# ---------------------------------------------------- online retuning


def _bimodal_reqs(rng, count, k=2):
    """Sizes 40/96: the geometric ladder buckets them at 64/128; the
    fitted ladder serves 96 at a 96 rung — padded area drops ~30%."""
    out = []
    for i in range(count):
        n = 40 if i % 2 == 0 else 96
        out.append(("solve", *_mk_solve(rng, n, k)))
    return out


def test_online_retune_hot_swap_drill():
    """The retune acceptance drill: a bimodal size stream triggers
    EXACTLY one ladder hot-swap; subsequent flushes bucket on the
    fitted ladder and padding waste drops."""
    rng = _rng()
    cfg = serve.AdmissionConfig(retune_interval_s=1e9,  # tick off: direct
                                retune_min_samples=16,
                                retune_margin=0.02)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    with obs.recording() as recs:
        pre = _bimodal_reqs(rng, 16)
        for (op, a, b), res in zip(pre, _serve_once(srv, pre)):
            _check_solve(a, b, res)
        pre_batches = _batch_events(recs)
        assert all(e["ladder"] == "geometric" for e in pre_batches)
        assert {tuple(e["bucket"]) for e in pre_batches} == \
            {(64, 2), (128, 2)}

        info = srv.retune_now("float32")
        assert info is not None
        assert info["new"] == [64, 96]
        assert info["waste_fitted"] < info["waste_live"]
        # a second retune without fresh evidence is a no-op: the
        # histogram reset and the margin hold — EXACTLY one swap
        assert srv.retune_now("float32") is None

        post = _bimodal_reqs(rng, 16)
        for (op, a, b), res in zip(post, _serve_once(srv, post)):
            _check_solve(a, b, res)
    retunes = [e for e in recs if e.get("kind") == "serve_retune"]
    assert len(retunes) == 1
    post_batches = _batch_events(recs)[len(pre_batches):]
    assert all(e["ladder"] == "retuned" for e in post_batches)
    assert {tuple(e["bucket"]) for e in post_batches} == \
        {(64, 2), (96, 2)}

    def waste(evs):
        return np.mean([e["padding_waste"] for e in evs])

    assert waste(post_batches) < waste(pre_batches)


def test_background_retune_tick_swaps_once():
    """The background loop's retune tick performs the swap off-thread:
    in-flight tickets settle on the old plan, later flushes use the
    fitted ladder, and exactly one serve_retune record is emitted."""
    rng = _rng()
    cfg = serve.AdmissionConfig(flush_occupancy=4, max_batch_delay_ms=5.0,
                                retune_interval_s=0.05,
                                retune_min_samples=16,
                                retune_margin=0.02)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    srv.start()
    try:
        with obs.recording() as recs:
            reqs = _bimodal_reqs(rng, 24)
            tickets = [(a, b, srv.submit(op, a, b)) for op, a, b in reqs]
            for a, b, t in tickets:
                _check_solve(a, b, t.result(timeout=120.0))
            deadline = time.perf_counter() + 30.0
            while (srv.health_info()["retunes"] < 1
                   and time.perf_counter() < deadline):
                time.sleep(0.02)
            assert srv.health_info()["retunes"] == 1
            reqs2 = _bimodal_reqs(rng, 8)
            tickets = [(a, b, srv.submit(op, a, b)) for op, a, b in reqs2]
            for a, b, t in tickets:
                _check_solve(a, b, t.result(timeout=120.0))
    finally:
        srv.shutdown()
    retunes = [e for e in recs if e.get("kind") == "serve_retune"]
    assert len(retunes) == 1
    assert _batch_events(recs)[-1]["ladder"] == "retuned"


def test_cli_serving_table_renders_pool_columns(tmp_path, capsys):
    """The metrics CLI smoke test: a pooled stream with a failover and a
    retune renders the serving table with dev / failovers / retunes
    columns populated (retunes on their own ladder/<dtype> row)."""
    rng = _rng()
    cfg = serve.AdmissionConfig(retune_interval_s=1e9,
                                retune_min_samples=16,
                                retune_margin=0.02)
    devs = [jax.local_devices()[0]] * 2
    pool = serve.DevicePool(devs, serve.PoolConfig(strike_limit=1))
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg,
                       pool=pool)
    kill = faults.FaultPlan("serve_device_fail", kind="inf",
                            transient=True, device=0)
    with obs.recording() as recs:
        with faults.inject(kill):
            reqs = _bimodal_reqs(rng, 16)
            for (op, a, b), res in zip(reqs, _serve_once(srv, reqs)):
                _check_solve(a, b, res)
        assert srv.retune_now("float32") is not None
    path = tmp_path / "events.jsonl"
    path.write_text("".join(json.dumps(e) + "\n" for e in recs))

    table = obs.summarize([str(path)])["serve"]
    row = table["solve/float32"]
    assert row["dev"] >= 1 and row["failovers"] == 1
    assert table["ladder/float32"]["retunes"] == 1
    assert obs_cli.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "dev" in out and "failovers" in out and "retunes" in out
    assert "ladder/float32" in out


def test_retune_respects_margin_hysteresis():
    """A stream the live ladder already serves well never swaps."""
    rng = _rng()
    cfg = serve.AdmissionConfig(retune_interval_s=1e9,
                                retune_min_samples=8, retune_margin=0.05)
    srv = serve.Server(cache=serve.ExecutableCache(), admission=cfg)
    reqs = [("solve", *_mk_solve(rng, 32)) for _ in range(8)]
    _serve_once(srv, reqs)          # n=32 sits exactly on a rung
    assert srv.retune_now("float32") is None
    assert srv.health_info()["retunes"] == 0
