"""Failure-domain hardening (PR 4): partial results on node death,
per-peer circuit breakers, end-to-end deadlines, and the
never-cache-partials contract.  In-process "kills" (NodeQueryServer.stop
-> connection refused) give the same socket-level failure signature as a
SIGKILL without subprocess cost; the chaos drill (`python -m bench.drills
chaos`) covers the real-SIGKILL macro run."""
import socket
import threading
import time

import numpy as np
import pytest

from filodb_tpu.core.memstore import TimeSeriesMemStore
from filodb_tpu.ingest.generator import counter_batch, gauge_batch
from filodb_tpu.parallel.breaker import breakers
from filodb_tpu.parallel.shardmapper import SpreadProvider
from filodb_tpu.parallel.testcluster import make_two_node_cluster
from filodb_tpu.query.engine import QueryEngine
from filodb_tpu.query.rangevector import PlannerParams

START = 1_600_000_020_000
S = START // 1000
Q = 'sum by (_ns_)(rate(request_total[5m]))'


@pytest.fixture(autouse=True)
def _fresh_breakers():
    breakers.reset()
    breakers.configure(failure_threshold=3, open_base_s=0.2,
                       open_max_s=1.0, jitter=0.0)
    yield
    breakers.configure()
    breakers.reset()


@pytest.fixture()
def cluster():
    c = make_two_node_cluster(
        [counter_batch(40, 360, start_ms=START),
         gauge_batch(30, 360, start_ms=START)], with_truth=True)
    truth_eng = QueryEngine("prometheus", c.truth, c.mapper,
                            SpreadProvider(default_spread=1))
    yield c, truth_eng
    c.stop()


# ------------------------------------------------------ partial results


def test_kill_node_mid_scatter_partial_flag_and_surviving_data(cluster):
    c, truth_eng = cluster
    pp = PlannerParams(allow_partial_results=True)
    # healthy first: full result, not partial
    healthy = c.engine.query_range(Q, S + 600, 60, S + 3600, pp)
    assert healthy.error is None and healthy.partial is False

    c.servers["nodeB"].stop()           # shards 2,3 now unreachable

    res = c.engine.query_range(Q, S + 600, 60, S + 3600, pp)
    assert res.error is None, res.error
    assert res.partial is True
    assert res.stats.partial is True
    assert any("shard dropped" in w for w in res.stats.warnings)

    # surviving data is CORRECT: exactly what the truth engine computes
    # over the surviving shards (0,1 — nodeA's)
    expect = truth_eng.query_range(
        Q, S + 600, 60, S + 3600, PlannerParams(shard_overrides=[0, 1]))
    assert expect.error is None
    got = {k: v for k, _, v in res.series()}
    want = {k: v for k, _, v in expect.series()}
    assert set(got) == set(want) and len(got) > 0
    for k in got:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-9,
                                   equal_nan=True)

    # the Prometheus envelope carries the flag + warnings, never silent
    payload = QueryEngine.to_prom_matrix(res)
    assert payload["partial"] is True
    assert payload["warnings"]
    # and ?stats=true exposes them too
    d = res.stats.to_dict()
    assert d["partial"] is True and d["warnings"]


def test_without_gate_node_death_fails_with_typed_error(cluster):
    c, _ = cluster
    c.servers["nodeB"].stop()
    res = c.engine.query_range(Q, S + 600, 60, S + 3600)
    assert res.error is not None
    assert res.error.startswith("shard_unavailable")
    assert res.partial is False


def test_raw_selector_partial_keeps_per_series_values(cluster):
    """Raw (unaggregated) partials: the surviving series' VALUES are
    bit-identical to the full-truth result — a dropped shard may only
    remove series, never corrupt survivors."""
    c, truth_eng = cluster
    pp = PlannerParams(allow_partial_results=True)
    c.servers["nodeB"].stop()
    res = c.engine.query_range('heap_usage', S + 600, 60, S + 3600, pp)
    assert res.error is None and res.partial is True
    full = truth_eng.query_range('heap_usage', S + 600, 60, S + 3600)
    got = {k: v for k, _, v in res.series()}
    want = {k: v for k, _, v in full.series()}
    assert 0 < len(got) < len(want)     # strictly partial
    for k, v in got.items():
        np.testing.assert_allclose(v, want[k], rtol=1e-9, equal_nan=True)


# ----------------------------------------------------- circuit breakers


def _mk_leaf(shard=0):
    from filodb_tpu.core.index import Equals
    from filodb_tpu.query.exec import (AggregateMapReduce,
                                       MultiSchemaPartitionsExec,
                                       PeriodicSamplesMapper)
    from filodb_tpu.query.rangevector import QueryContext
    plan = MultiSchemaPartitionsExec(
        QueryContext(query_id="qb"), "prometheus", shard,
        [Equals("_metric_", "request_total")], START, START + 3_600_000)
    plan.add_transformer(PeriodicSamplesMapper(
        START + 600_000, 60_000, START + 3_600_000, 300_000, "rate", ()))
    plan.add_transformer(AggregateMapReduce("sum", (), (), ()))
    return plan


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_breaker_opens_fails_fast_half_opens_and_recovers():
    from filodb_tpu.parallel.transport import (NodeQueryServer,
                                               RemoteNodeDispatcher)
    from filodb_tpu.query.execbase import QueryError

    port = _free_port()                 # nothing listening: refused
    # generous ask timeout: the revived server pays a cold XLA compile
    # on the probe dispatch; a timeout would (correctly) re-open via
    # on_abort, which is not what this test is probing
    disp = RemoteNodeDispatcher("127.0.0.1", port, timeout_s=30.0)
    peer = f"127.0.0.1:{port}"

    # threshold consecutive connect failures -> open
    for _ in range(3):
        with pytest.raises(QueryError) as ei:
            disp.dispatch(_mk_leaf(), None)
        assert ei.value.code == "shard_unavailable"
    br = breakers.get(peer)
    assert br.state == "open"

    # open: fail-fast in microseconds, no socket touched
    t0 = time.perf_counter()
    with pytest.raises(QueryError) as ei:
        disp.dispatch(_mk_leaf(), None)
    assert time.perf_counter() - t0 < 0.05
    assert "circuit open" in str(ei.value)
    assert ei.value.code == "shard_unavailable"
    assert br.fail_fast >= 1

    # half-open probe against the still-dead peer -> re-open, doubled
    time.sleep(0.25)
    with pytest.raises(QueryError):
        disp.dispatch(_mk_leaf(), None)     # the admitted probe
    assert br.state == "open"
    assert br.snapshot()["backoffSeconds"] == pytest.approx(0.4)

    # peer comes back on the SAME address: probe succeeds -> closed
    ms = TimeSeriesMemStore()
    ms.setup("prometheus", 0).ingest(counter_batch(8, 360, start_ms=START))
    srv = NodeQueryServer(ms, port=port).start()
    try:
        time.sleep(0.45)
        data, stats = disp.dispatch(_mk_leaf(), None)
        assert stats.samples_scanned > 0
        assert br.state == "closed"
        assert br.consecutive_failures == 0
    finally:
        srv.stop()


def test_breaker_probe_timeout_releases_slot_never_wedges():
    """Regression (found by the chaos stage): a half-open probe whose
    dispatch ends in a TIMEOUT — no liveness verdict — must release the
    probe slot via on_abort (re-opening, doubled backoff).  Before the
    fix the slot leaked and the breaker stayed half-open forever,
    failing fast on a recovered peer."""
    from filodb_tpu.parallel.breaker import CircuitBreaker
    br = CircuitBreaker("peer:1", failure_threshold=1, open_base_s=0.05,
                        open_max_s=1.0, jitter=0.0)
    br.on_failure()
    assert br.state == "open"
    time.sleep(0.07)
    assert br.allow() is True           # the half-open probe
    assert br.allow() is False          # slot held while it runs
    br.on_abort()                       # probe timed out
    assert br.state == "open"
    assert br.snapshot()["backoffSeconds"] == pytest.approx(0.1)
    time.sleep(0.12)
    assert br.allow() is True           # a NEW probe is admitted
    br.on_success()
    assert br.state == "closed"
    # on_abort on a CLOSED breaker is a no-op (plain dispatch timeout)
    br.on_abort()
    assert br.state == "closed"


def test_breaker_fail_fast_engages_partial_path(cluster):
    """With nodeB's breaker already open, a gated query degrades to a
    partial WITHOUT paying any socket work for the dead peer."""
    c, _ = cluster
    c.servers["nodeB"].stop()
    pp = PlannerParams(allow_partial_results=True)
    # first query: opens the breaker via real connect failures (threshold
    # 3; the engine's initial attempt + partial re-execution provide them)
    for _ in range(3):
        c.engine.query_range(Q, S + 600, 60, S + 3600, pp)
    dead_peer = "%s:%d" % c.servers["nodeB"].address
    assert breakers.get(dead_peer).state == "open"
    t0 = time.perf_counter()
    res = c.engine.query_range(Q, S + 600, 60, S + 3600, pp)
    dur = time.perf_counter() - t0
    assert res.error is None and res.partial is True
    assert breakers.get(dead_peer).fail_fast > 0
    assert dur < 2.0                    # no connect-timeout serialization


# ----------------------------------------------------------- deadlines


def test_expired_deadline_returns_structured_error_with_stats():
    ms = TimeSeriesMemStore()
    ms.setup("prometheus", 0).ingest(counter_batch(8, 360, start_ms=START))
    eng = QueryEngine("prometheus", ms)
    pp = PlannerParams(deadline_unix_s=time.time() - 1.0)
    res = eng.query_range(Q, S + 600, 60, S + 3600, pp)
    assert res.error is not None
    assert res.error.startswith("query_timeout")
    # the structured envelope: errorType timeout + per-phase stats
    payload = QueryEngine.to_prom_matrix(res)
    assert payload["status"] == "error"
    assert payload["errorType"] == "timeout"
    assert "phases" in res.stats.to_dict()


def test_deadline_expiry_in_scheduler_queue_attributes_queue_wait():
    from filodb_tpu.config import FilodbSettings
    from filodb_tpu.query.frontend import QueryFrontend

    ms = TimeSeriesMemStore()
    ms.setup("prometheus", 0).ingest(counter_batch(8, 360, start_ms=START))
    eng = QueryEngine("prometheus", ms)
    cfg = FilodbSettings()
    cfg.query.max_concurrent_queries = 1
    fe = QueryFrontend(eng, config=cfg)
    # hog the single execution slot so the query dies IN THE QUEUE
    # (the qos scheduler replaced the semaphore; an admit under another
    # tenant's name holds the one global capacity slot the same way)
    assert fe.scheduler.admit("hog", 1.0).acquired
    try:
        t0 = time.perf_counter()
        res = fe.query_range(Q, S + 600, 60, S + 3600,
                             PlannerParams(timeout_s=0.3))
        waited = time.perf_counter() - t0
    finally:
        fe.scheduler.release("hog")
    assert res.error is not None and res.error.startswith("query_timeout")
    assert "queue" in res.error
    # queue wait is attributed in the stats the error ships with
    assert res.stats.queue_wait_s == pytest.approx(waited, abs=0.15)
    assert res.stats.queue_wait_s >= 0.25


def test_remote_dispatch_timeout_bounded_by_remaining_budget():
    """A peer that ACCEPTS the plan but never replies: the socket wait is
    bounded by the query's remaining budget, and its expiry is the
    structured query_timeout (not a 120 s ask-timeout hang)."""
    from filodb_tpu.parallel.transport import RemoteNodeDispatcher
    from filodb_tpu.query.execbase import QueryError

    # a listener that accepts and then stays silent
    lsock = socket.socket()
    lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    accepted = []
    t = threading.Thread(
        target=lambda: accepted.append(lsock.accept()), daemon=True)
    t.start()
    try:
        disp = RemoteNodeDispatcher(*lsock.getsockname(), timeout_s=30.0)
        plan = _mk_leaf()
        plan.ctx.deadline_unix_s = time.time() + 0.4
        t0 = time.perf_counter()
        with pytest.raises(QueryError) as ei:
            disp.dispatch(plan, None)
        dur = time.perf_counter() - t0
        assert ei.value.code == "query_timeout"
        assert 0.2 < dur < 5.0          # budget-bounded, not ask-bounded
    finally:
        lsock.close()
        for conn, _ in accepted:
            conn.close()


def test_wedged_peer_deadline_share_yields_droppable_dispatch_timeout():
    """A wedged peer (accepts, never replies) under an ample deadline
    with partial results ALLOWED: the hop's socket wait is capped at the
    deadline SHARE (query.peer_deadline_share, default 0.5) of the
    remaining budget, so it expires as the droppable dispatch_timeout
    with budget left for the survivors — NOT as the non-droppable
    query_timeout after consuming the whole budget.  And a share-bounded
    expiry teaches the breaker nothing (a slow peer is not a dead one)."""
    from filodb_tpu.parallel.transport import RemoteNodeDispatcher
    from filodb_tpu.query.execbase import QueryError

    lsock = socket.socket()
    lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(1)
    accepted = []
    t = threading.Thread(
        target=lambda: accepted.append(lsock.accept()), daemon=True)
    t.start()
    try:
        disp = RemoteNodeDispatcher(*lsock.getsockname(), timeout_s=30.0)
        plan = _mk_leaf()
        plan.ctx.planner_params = PlannerParams(allow_partial_results=True)
        dl = time.time() + 1.0
        plan.ctx.deadline_unix_s = dl
        t0 = time.perf_counter()
        with pytest.raises(QueryError) as ei:
            disp.dispatch(plan, None)
        dur = time.perf_counter() - t0
        assert ei.value.code == "dispatch_timeout"
        assert 0.3 < dur < 0.9          # the 0.5 share, not the full 1 s
        assert time.time() < dl         # survivors still have budget
        peer = "%s:%d" % lsock.getsockname()
        assert breakers.get(peer).consecutive_failures == 0
    finally:
        lsock.close()
        for conn, _ in accepted:
            conn.close()


def test_engine_caps_request_timeout_at_config_default(monkeypatch):
    """timeout_s above query.default_timeout_s is capped server-side."""
    from filodb_tpu import config as config_mod
    cfg = config_mod.FilodbSettings()
    cfg.query.default_timeout_s = 5.0
    monkeypatch.setattr(config_mod, "_SETTINGS", cfg)
    ms = TimeSeriesMemStore()
    ms.setup("prometheus", 0)
    eng = QueryEngine("prometheus", ms)
    ctx = eng._ctx(PlannerParams(timeout_s=600.0))
    assert ctx.deadline_unix_s <= time.time() + 5.5
    # and a request SHRINKING the budget is honored
    ctx2 = eng._ctx(PlannerParams(timeout_s=0.5))
    assert ctx2.deadline_unix_s <= time.time() + 1.0


def test_singleflight_follower_does_not_inherit_leader_timeout():
    """Budgets are per-request and repr-excluded from the dedup key: a
    short-timeout leader whose budget expires must not fail a follower
    whose own budget is ample — the follower re-runs solo."""
    import threading

    from filodb_tpu.config import FilodbSettings
    from filodb_tpu.query.frontend import QueryFrontend, _Flight
    from filodb_tpu.query.rangevector import QueryResult

    ms = TimeSeriesMemStore()
    ms.setup("prometheus", 0).ingest(counter_batch(8, 360, start_ms=START))
    fe = QueryFrontend(QueryEngine("prometheus", ms),
                       config=FilodbSettings())
    pp = fe._admit_params(PlannerParams(timeout_s=60.0))
    # simulate an in-flight leader whose own (shorter) budget expired
    flight = _Flight()
    flight.result = QueryResult(
        [], error="query_timeout: deadline exceeded at RootExec")
    flight.done.set()
    key = (Q, S + 600, 60, S + 3600, repr(pp))
    with fe._sf_lock:
        fe._inflight[key] = flight
    try:
        res, shared = fe._singleflight(
            key, lambda: fe._cached_query(Q, S + 600, 60, S + 3600, pp),
            pp)
    finally:
        with fe._sf_lock:
            fe._inflight.pop(key, None)
    assert shared is False
    assert res.error is None            # solo re-run under OWN budget


def test_remote_query_timeout_code_survives_the_wire():
    """A deadline that expires ON the remote node must surface at the
    coordinator as query_timeout (errorType "timeout"), not be
    flattened into remote_failure."""
    from filodb_tpu.parallel.transport import (NodeQueryServer,
                                               RemoteNodeDispatcher)
    from filodb_tpu.query.execbase import QueryError
    from filodb_tpu.utils.faults import faults

    ms = TimeSeriesMemStore()
    ms.setup("prometheus", 0).ingest(counter_batch(8, 360, start_ms=START))
    srv = NodeQueryServer(ms).start()
    try:
        disp = RemoteNodeDispatcher(*srv.address, timeout_s=10.0)
        plan = _mk_leaf()
        disp.dispatch(plan, None)               # warm node-side compiles
        plan2 = _mk_leaf()
        plan2.ctx.deadline_unix_s = time.time() + 0.25
        # delay the SEND past the deadline: the coordinator's pre-check
        # passes, the REMOTE's exec-boundary check fires
        with faults.plan("transport.send", "delay", first_k=1,
                         delay_s=0.4):
            with pytest.raises(QueryError) as ei:
                disp.dispatch(plan2, None)
        assert ei.value.code == "query_timeout"
        assert "via node" in str(ei.value)
    finally:
        faults.disarm()
        srv.stop()


def test_timeout_variants_share_serving_keys():
    """timeout_s / deadline / partial_now are repr-excluded: requests
    differing only in their budget must dedup in singleflight, the
    coalescer, and the result cache."""
    a = repr(PlannerParams())
    b = repr(PlannerParams(timeout_s=30.0, deadline_unix_s=123.0,
                           partial_now=True))
    assert a == b


def test_metadata_query_degrades_to_partial(cluster):
    from filodb_tpu.query import logical as lp
    c, truth_eng = cluster
    c.servers["nodeB"].stop()
    plan = lp.LabelValues(("_ns_",), (), 0, 1 << 62)
    # without the gate: typed error
    res = c.engine.exec_logical_plan(plan)
    assert res.error is not None and \
        res.error.startswith("shard_unavailable")
    # with the gate: survivors' label values, no hard error — and the
    # degradation is FLAGGED (a silently shortened label dropdown is
    # exactly the silent partial the contract forbids)
    res = c.engine.exec_logical_plan(
        plan, PlannerParams(allow_partial_results=True))
    assert res.error is None
    assert res.data and res.data["_ns_"]
    assert res.partial is True
    assert any("shard dropped" in w for w in res.stats.warnings)


def test_metadata_http_payload_flags_partial(cluster):
    """GET /api/v1/label/<name>/values with partial_response=true and a
    dead node: 200 with the survivors' values, plus the partial flag +
    warnings in the payload (the per-request param must reach the
    metadata path)."""
    from filodb_tpu.http.routes import PromHttpApi
    c, _ = cluster
    api = PromHttpApi({"prometheus": c.engine})
    c.servers["nodeB"].stop()
    # without the opt-in: hard 400 with the typed error
    status, payload = api.handle(
        "GET", "/api/v1/label/_ns_/values", {})
    assert status == 400
    assert payload["error"].startswith("shard_unavailable")
    # with it: flagged partial from the survivors
    status, payload = api.handle(
        "GET", "/api/v1/label/_ns_/values", {"partial_response": "true"})
    assert status == 200, payload
    assert payload["data"]
    assert payload["partial"] is True
    assert payload["warnings"]


# ----------------------------------------------------- cache exclusion


def test_result_cache_never_stores_partials():
    from filodb_tpu.query.rangevector import QueryResult, QueryStats
    from filodb_tpu.query.resultcache import ResultCache

    cache = ResultCache()
    calls = []

    def run_partial(s0, e0):
        calls.append((s0, e0))
        r = QueryResult([], QueryStats())
        r.partial = True
        r.stats.partial = True
        return r

    state = (((1, 1, 0),), 10 ** 15)    # (token, horizon_ms): cacheable
    res = cache.query_range(run_partial, "up", 1000, 10, 1300, "pp", state)
    assert res.partial is True
    assert len(cache) == 0              # never stored
    # a re-poll runs again — there is no poisoned entry to serve
    cache.query_range(run_partial, "up", 1000, 10, 1300, "pp", state)
    assert len(calls) == 2 and len(cache) == 0


@pytest.mark.chaos
def test_chaos_sigkill_gates():
    """The ISSUE-11 acceptance run (gate FLIPPED from the PR-4 stance):
    SIGKILL one of three RF-2 data nodes mid ingest+query traffic.
    Queries stay FULL through the kill via replica failover
    (availability 1.0 with ZERO partials — the partial path engages
    only when every owner of a shard is dead), no acked slab is lost
    (the surviving owner held it; WAL-segment catch-up repairs the
    respawn), and no result ever claims to be full while missing a
    shard's group.  Excluded from tier-1 (chaos implies slow); also
    runnable standalone: `python -m bench.drills chaos`."""
    import json as _json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "bench.drills", "chaos", "--quick"],
        capture_output=True, text=True, timeout=600, cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.strip().splitlines()
            if l.startswith("{")][-1]
    r = _json.loads(line)
    assert r["chaos_queries"]["fault"] > 0
    assert r["chaos_availability"] == 1.0, r
    assert r["chaos_partial_rate"] == 0.0, r
    assert r["chaos_acked_lost"] == 0, r
    assert r["chaos_wrong_full_results"] == 0, r
    assert r["chaos_p99_during_fault_s"] <= 2 * r["healthy_p99_s"], r
    # the respawned node was repaired through WAL-segment catch-up and
    # full results kept flowing
    assert r["chaos_recovered_full_results"] > 0, r


def test_drills_entry_lists_four_drills():
    """`python -m bench.drills` is the one entry of the correctness
    drills, and its help names exactly the four it runs."""
    import os
    import re
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "bench.drills", "--help"],
        capture_output=True, text=True, timeout=120, cwd=repo)
    assert proc.returncode == 0, proc.stderr[-2000:]
    listed = re.search(r"\{([a-z,]+)\}", proc.stdout).group(1).split(",")
    assert sorted(listed) == ["chaos", "federation", "objectstore",
                              "replication"]


def test_result_cache_partial_tail_drops_entry_and_reruns():
    """A cached healthy prefix whose TAIL run comes back partial must not
    merge: the entry drops and the poll is served by one full run."""
    from filodb_tpu.ops.timewindow import make_window_ends
    from filodb_tpu.query.rangevector import (QueryResult, QueryStats,
                                              RangeVectorKey, ResultBlock)
    from filodb_tpu.query.resultcache import ResultCache

    cache = ResultCache()
    key = RangeVectorKey.make({"inst": "a"})
    partial_mode = {"on": False}
    full_runs = []

    def run(s0, e0):
        wends = make_window_ends(s0 * 1000, e0 * 1000, 10_000)
        r = QueryResult([ResultBlock([key], wends,
                                     np.ones((1, wends.size)))],
                        QueryStats())
        if partial_mode["on"]:
            r.partial = True
            r.stats.partial = True
        else:
            full_runs.append((s0, e0))
        return r

    state = (((1, 1, 0),), 1_200_000)   # horizon: windows <= 1200s final
    r1 = cache.query_range(run, "up", 1000, 10, 1200, "pp", state)
    assert r1.partial is False and len(cache) == 1
    # now the tail degrades: shards died — the poll must return ONE full
    # (partial-flagged) run and the poisoned-merge entry must be gone
    partial_mode["on"] = True
    r2 = cache.query_range(run, "up", 1000, 10, 1290, "pp", state)
    assert len(cache) == 0
    assert r2.partial is True
