"""Fault-injection layer (utils/faults.py): arming, determinism, seeded
plans, and the production fault points actually firing where they claim
to.  The chaos drill (`python -m bench.drills chaos`) is the macro counterpart;
these are the fast deterministic guarantees the tier-1 gate holds."""
import json
import socket
import time

import numpy as np
import pytest

from filodb_tpu.core.memstore import TimeSeriesMemStore
from filodb_tpu.ingest.generator import counter_batch
from filodb_tpu.utils.faults import (FaultRegistry, InjectedFault, faults)

START = 1_600_000_020_000


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.disarm()
    yield
    faults.disarm()


# ------------------------------------------------------------ registry unit


def test_unknown_point_and_kind_rejected():
    r = FaultRegistry(env={})
    with pytest.raises(ValueError, match="unknown fault point"):
        r.arm("no.such.point", "error")
    with pytest.raises(ValueError, match="unknown fault kind"):
        r.arm("ingest.batch", "explode")


def test_first_k_fires_exactly_first_k_calls():
    r = FaultRegistry(env={})
    r.arm("ingest.batch", "error", first_k=3)
    fired = 0
    for _ in range(10):
        try:
            r.fire("ingest.batch")
        except InjectedFault:
            fired += 1
    assert fired == 3
    snap = r.snapshot()[0]
    assert snap["calls"] == 10 and snap["fired"] == 3


def test_probability_schedule_is_seed_deterministic():
    def sequence(seed):
        r = FaultRegistry(env={})
        r.arm("ingest.batch", "error", probability=0.3, seed=seed)
        out = []
        for _ in range(200):
            try:
                r.fire("ingest.batch")
                out.append(False)
            except InjectedFault:
                out.append(True)
        return out

    a, b = sequence(7), sequence(7)
    assert a == b                       # same seed -> same schedule
    assert any(a) and not all(a)        # p=0.3 over 200 calls: mixed
    assert sequence(8) != a             # a different seed moves it


def test_kinds_error_drop_delay_corrupt():
    r = FaultRegistry(env={})
    r.arm("transport.send", "error", first_k=1, message="boom")
    with pytest.raises(InjectedFault, match="boom"):
        r.fire("transport.send")

    r.arm("transport.send", "drop", first_k=1)
    with pytest.raises(socket.timeout):
        r.fire("transport.send")

    r.arm("transport.send", "delay", first_k=1, delay_s=0.05)
    t0 = time.perf_counter()
    assert r.fire("transport.send", b"abc") == b"abc"
    assert time.perf_counter() - t0 >= 0.045

    r.arm("transport.recv", "corrupt", first_k=1, seed=3)
    payload = bytes(range(64))
    out = r.fire("transport.recv", payload)
    assert out != payload and len(out) == len(payload)
    # deterministic: the same seed corrupts the same positions
    r2 = FaultRegistry(env={})
    r2.arm("transport.recv", "corrupt", first_k=1, seed=3)
    assert r2.fire("transport.recv", payload) == out


def test_disabled_fast_path_passthrough():
    r = FaultRegistry(env={})
    assert r.fire("transport.send", b"x") == b"x"
    # armed on a DIFFERENT point: untouched too
    r.arm("ingest.batch", "error", first_k=1)
    assert r.fire("transport.send", b"x") == b"x"


def test_env_arming():
    spec = json.dumps([{"point": "flush.persist", "kind": "error",
                        "first_k": 2}])
    r = FaultRegistry(env={"FILODB_TPU_FAULTS": spec})
    with pytest.raises(InjectedFault):
        r.fire("flush.persist")


def test_plan_context_manager_disarms_on_exit():
    r_before = faults.snapshot()
    assert r_before == []
    with faults.plan("ingest.batch", "error", first_k=1):
        assert len(faults.snapshot()) == 1
    assert faults.snapshot() == []


# ------------------------------------------------- production fault points


def test_ingest_batch_point_fires_in_shard():
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", 0)
    batch = counter_batch(4, 10, start_ms=START)
    with faults.plan("ingest.batch", "error", first_k=1):
        with pytest.raises(InjectedFault):
            sh.ingest(batch)
        assert sh.ingest(batch) > 0     # first_k exhausted: recovers


def test_flush_persist_point_fires_in_flush():
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", 0)
    sh.ingest(counter_batch(4, 50, start_ms=START))
    groups = {sh.partitions[p].group for p in range(sh.num_partitions)}
    with faults.plan("flush.persist", "error", first_k=100):
        with pytest.raises(InjectedFault):
            for g in sorted(groups):
                sh.flush_group(g)
    # disarmed: the same flush succeeds
    assert sum(sh.flush_group(g) for g in sorted(groups)) >= 0


def test_transport_points_fire_on_dispatch_path():
    from filodb_tpu.core.index import Equals
    from filodb_tpu.parallel.breaker import breakers
    from filodb_tpu.parallel.transport import (NodeQueryServer,
                                               RemoteNodeDispatcher)
    from filodb_tpu.query.exec import (AggregateMapReduce,
                                       MultiSchemaPartitionsExec,
                                       PeriodicSamplesMapper)
    from filodb_tpu.query.execbase import QueryError
    from filodb_tpu.query.rangevector import QueryContext

    breakers.reset()
    ms = TimeSeriesMemStore()
    ms.setup("prometheus", 0).ingest(counter_batch(8, 360, start_ms=START))
    srv = NodeQueryServer(ms).start()
    try:
        disp = RemoteNodeDispatcher(*srv.address, timeout_s=10.0)

        def mk_plan():
            plan = MultiSchemaPartitionsExec(
                QueryContext(query_id="qf"), "prometheus", 0,
                [Equals("_metric_", "request_total")],
                START, START + 3_600_000)
            plan.add_transformer(PeriodicSamplesMapper(
                START + 600_000, 60_000, START + 3_600_000, 300_000,
                "rate", ()))
            plan.add_transformer(AggregateMapReduce("sum", (), (), ()))
            return plan

        # baseline: healthy dispatch
        data, stats = disp.dispatch(mk_plan(), None)
        assert stats.samples_scanned > 0

        # ONE send fault on a pooled socket: the stale-pool one-retry
        # path absorbs it (counted + visible), the dispatch succeeds
        from filodb_tpu.utils.metrics import registry
        retries0 = registry.counter("transport_stale_socket_retries").value
        with faults.plan("transport.send", "error", first_k=1):
            data1, stats1 = disp.dispatch(mk_plan(), None)
            assert stats1.samples_scanned > 0
        assert registry.counter(
            "transport_stale_socket_retries").value == retries0 + 1

        # TWO send faults: the retry fails too -> peer-death taxonomy
        with faults.plan("transport.send", "error", first_k=2):
            with pytest.raises(QueryError) as ei:
                disp.dispatch(mk_plan(), None)
            assert ei.value.code == "shard_unavailable"

        # corrupt reply -> loud remote_failure, never a mis-parse
        with faults.plan("transport.recv", "corrupt", first_k=1):
            with pytest.raises(QueryError) as ei:
                disp.dispatch(mk_plan(), None)
            assert ei.value.code == "remote_failure"
            # streamed replies report a per-frame CRC mismatch, legacy
            # single-frame replies a corrupt reply — both are the
            # typed remote_failure
            assert "corrupt" in str(ei.value)

        # dropped frame -> the timeout handling path, deterministically
        with faults.plan("transport.recv", "drop", first_k=1):
            with pytest.raises(QueryError) as ei:
                disp.dispatch(mk_plan(), None)
            assert ei.value.code == "dispatch_timeout"

        # after every fault the pooled connection recovers
        data2, stats2 = disp.dispatch(mk_plan(), None)
        assert stats2.samples_scanned == stats.samples_scanned
    finally:
        srv.stop()
        breakers.reset()


def test_flush_scheduler_backs_off_and_recovers():
    from filodb_tpu.core.flush import FlushScheduler
    from filodb_tpu.utils.metrics import registry

    ms = TimeSeriesMemStore()
    sh = ms.setup("chaos_flush", 0)
    sh.ingest(counter_batch(8, 80, start_ms=START))
    sched = FlushScheduler(ms, "chaos_flush", interval_s=0.5,
                           headroom=False)
    errs0 = registry.counter("flush_errors", dataset="chaos_flush",
                             shard="0").value
    try:
        with faults.plan("flush.persist", "error", first_k=10_000):
            sched.start()
            deadline = time.time() + 5.0
            while time.time() < deadline and not sched._backoff_until:
                time.sleep(0.02)
        # errors were counted per shard AND the shard entered backoff
        assert sched.errors > 0
        assert registry.counter("flush_errors", dataset="chaos_flush",
                                shard="0").value > errs0
        assert 0 in sched._backoff_until
        assert registry.gauge("flush_backoff_active",
                              dataset="chaos_flush").value == 1
        # disarmed: the next successful flush resets streak + gauge
        deadline = time.time() + 5.0
        while time.time() < deadline and sched._err_streak:
            time.sleep(0.02)
        assert not sched._err_streak
        assert registry.gauge("flush_backoff_active",
                              dataset="chaos_flush").value == 0
    finally:
        sched.stop(final_flush=False)
