"""Replication layer tests (filodb_tpu/replication; doc/replication.md):
placement math, ingest fan-out + lag journal edges, WAL-segment
catch-up, query-time replica failover + gather dedup, the live-handoff
state machine, health/admin surfaces.

Fast in-process tests run in tier-1; traffic-under-chaos drills carry
the `replication` marker (implies slow) and run via -m replication or
`python -m bench.drills replication`.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

from filodb_tpu.core.memstore import TimeSeriesMemStore
from filodb_tpu.core.partkey import PartKey
from filodb_tpu.core.schemas import PROM_COUNTER
from filodb_tpu.parallel.shardmanager import (DatasetResourceSpec,
                                              ShardManager)
from filodb_tpu.parallel.shardmapper import (ShardEvent, ShardMapper,
                                             ShardStatus)
from filodb_tpu.parallel.testcluster import make_replicated_cluster
from filodb_tpu.query.rangevector import PlannerParams
from filodb_tpu.utils.events import journal
from filodb_tpu.utils.jobs import jobs

DS = "prometheus"
START = 1_600_000_000_000


def _keys(n, ns="n"):
    return [PartKey.make("repl_total",
                         {"_ws_": "w", "_ns_": ns, "i": str(i)})
            for i in range(n)]


def _grid(n_series, n_samples, base_idx=0):
    ts = (np.arange(n_samples, dtype=np.int64)[None, :]
          + base_idx) * 10_000 + START
    ts = np.repeat(ts, n_series, axis=0)
    vals = (np.arange(n_samples, dtype=np.float64)[None, :] + base_idx) \
        * 5.0 + np.arange(n_series, dtype=np.float64)[:, None]
    return ts, vals


# ------------------------------------------------------------- placement


def test_mapper_ordered_owners_and_promotion():
    m = ShardMapper(4, replication_factor=2)
    m.update_from_event(ShardEvent("IngestionStarted", DS, 0, "A"))
    m.register_replica(0, "B", status=ShardStatus.ACTIVE)
    assert m.owners(0) == ["A", "B"]
    assert m.live_owners(0) == ["A", "B"]
    # registering the primary as replica is a no-op
    m.register_replica(0, "A")
    assert m.owners(0) == ["A", "B"]
    old = m.promote_replica(0, "B", demote_old=True)
    assert old == "A"
    assert m.owners(0) == ["B", "A"]
    assert m.node_for_shard(0) == "B"
    # promoted replica carried its ACTIVE status into the primary column
    assert m.statuses[0] == ShardStatus.ACTIVE
    m.unassign_replica(0, "A")
    assert m.owners(0) == ["B"]
    with pytest.raises(ValueError):
        m.promote_replica(0, "Z")


def test_mapper_replica_events_never_touch_primary():
    m = ShardMapper(2)
    m.update_from_event(ShardEvent("IngestionStarted", DS, 0, "A"))
    m.update_from_event(ShardEvent("ReplicaAssigned", DS, 0, "B"))
    assert m.owner_status(0, "B") == ShardStatus.ASSIGNED
    m.update_from_event(ShardEvent("ReplicaActive", DS, 0, "B"))
    assert m.owner_status(0, "B") == ShardStatus.ACTIVE
    assert m.statuses[0] == ShardStatus.ACTIVE      # primary untouched
    # a ShardDown addressed to the REPLICA node removes only the replica
    m.update_from_event(ShardEvent("ShardDown", DS, 0, "B"))
    assert m.owners(0) == ["A"]
    assert m.node_for_shard(0) == "A"
    assert m.statuses[0] == ShardStatus.ACTIVE
    # ReplicaPromoted event = the atomic cutover
    m.update_from_event(ShardEvent("ReplicaAssigned", DS, 0, "C"))
    m.update_from_event(ShardEvent("ReplicaPromoted", DS, 0, "C"))
    assert m.node_for_shard(0) == "C"
    assert "A" not in m.owners(0)


def test_manager_rf2_never_colocates():
    sm = ShardManager(replication_factor=2)
    for n in ("a", "b", "c"):
        sm.add_member(n)
    mapper = sm.setup_dataset(DS, DatasetResourceSpec(8, 3))
    for s in range(8):
        owners = mapper.owners(s)
        assert len(owners) == 2, f"shard {s}: {owners}"
        assert len(set(owners)) == 2, f"shard {s} co-located: {owners}"


def test_manager_promotes_replica_on_primary_death():
    sm = ShardManager(replication_factor=2)
    for n in ("a", "b", "c"):
        sm.add_member(n)
    mapper = sm.setup_dataset(DS, DatasetResourceSpec(8, 3))
    # all copies live
    for s in range(8):
        sm.on_shard_event(ShardEvent("IngestionStarted", DS, s,
                                     mapper.node_for_shard(s)))
        for n in list(mapper.replicas[s]):
            sm.on_shard_event(ShardEvent("ReplicaActive", DS, s, n))
    victim = mapper.node_for_shard(0)
    owned = mapper.shards_for_node(victim)
    sm.remove_member(victim)
    for s in owned:
        # never Down: the live replica was promoted in place
        assert mapper.statuses[s] == ShardStatus.ACTIVE, \
            f"shard {s} went {mapper.statuses[s]} instead of promoting"
        assert mapper.node_for_shard(s) != victim
    # the dead node is gone from every assignment list
    assert not mapper.replica_shards_for_node(victim)
    # replicas refilled on surviving capacity (2 nodes left -> every
    # shard can still hold 2 distinct owners)
    for s in range(8):
        assert len(set(mapper.owners(s))) == 2


def test_mapper_replication_off_unchanged():
    m = ShardMapper(4)
    assert m.replication_factor == 1
    assert m.replicas == [[], [], [], []]
    m.update_from_event(ShardEvent("IngestionStarted", DS, 1, "A"))
    assert m.owners(1) == ["A"]


# -------------------------------------------- satellite: mapper edge math


def test_mapper_rejects_non_power_of_two():
    with pytest.raises(AssertionError):
        ShardMapper(6)
    with pytest.raises(AssertionError):
        ShardMapper(0)


def test_shard_down_clears_node_assignment():
    m = ShardMapper(4)
    m.update_from_event(ShardEvent("IngestionStarted", DS, 2, "A"))
    assert m.node_for_shard(2) == "A"
    m.update_from_event(ShardEvent("ShardDown", DS, 2, "A"))
    assert m.node_for_shard(2) is None
    assert m.statuses[2] == ShardStatus.DOWN
    assert not m.active_shards([2])


def test_query_shards_run_boundaries():
    """queryShards returns the full 2^spread-wide aligned run the shard
    key can land on — and clamps spread past log2(numShards)."""
    m = ShardMapper(8)
    h = 0b10110  # arbitrary shard-key hash
    assert m.query_shards(h, 0) == [h & 7]
    run = m.query_shards(h, 2)
    assert len(run) == 4
    base = run[0]
    assert base % 4 == 0                     # aligned to the run width
    assert run == [base, base + 1, base + 2, base + 3]
    # ingestion_shard always lands inside the query run
    for ph in range(64):
        assert m.ingestion_shard(h, ph, 2) in run
    # spread beyond log2(numShards) clamps to all shards
    assert m.query_shards(h, 10) == list(range(8))


# ---------------------------------------------------------- ingest fan-out


def test_fanout_quorum_ack_and_lag_journal_edges():
    cluster = make_replicated_cluster(num_shards=2)
    try:
        keys = _keys(8)
        ts, vals = _grid(8, 16)
        res = cluster.ingest_grid(0, PROM_COUNTER.name, keys, ts,
                                  {"count": vals})
        owners = cluster.mapper.owners(0)
        assert sorted(res.acked) == sorted(owners)
        for n in owners:
            sh = cluster.stores[n].get_shard(DS, 0)
            assert sh.num_partitions == 8
        # kill one replica owner -> fan-out marks it lagging (journal
        # edge fires once), primary ack keeps ingest available
        replica = cluster.mapper.replicas[0][0]
        seq0 = journal.next_seq
        cluster.kill(replica)
        for b in range(3):
            ts2, vals2 = _grid(8, 4, base_idx=16 + b * 4)
            res2 = cluster.ingest_grid(0, PROM_COUNTER.name, keys, ts2,
                                       {"count": vals2})
            assert cluster.mapper.node_for_shard(0) in res2.acked
            assert replica not in res2.acked
        lag_events = [e for e in journal.since(seq0 - 1)
                      if e["kind"] == "replica_lagging"
                      and e.get("peer") == replica]
        assert len(lag_events) == 1, "lagging edge must fire exactly once"
        snap = cluster.manager.snapshot()
        lagging = [p for p in snap if p["peer"] == replica]
        assert lagging and lagging[0]["lagging"]
    finally:
        cluster.stop()


def test_fanout_requires_some_owner():
    from filodb_tpu.replication.replicator import ReplicationSendError
    cluster = make_replicated_cluster(num_shards=2)
    try:
        for n in list(cluster.mapper.owners(1)):
            cluster.kill(n)
        keys = _keys(4)
        ts, vals = _grid(4, 4)
        with pytest.raises(ReplicationSendError):
            cluster.manager.replicate(1, PROM_COUNTER.name, keys, ts,
                                      {"count": vals},
                                      require_primary=True)
    finally:
        cluster.stop()


# ------------------------------------------------------- WAL catch-up


def test_catchup_streams_segments_and_registers_job(tmp_path):
    from filodb_tpu.replication import (ReplicaClient, ReplicationServer,
                                        catchup_shards)
    from filodb_tpu.wal import WalManager
    ms_primary = TimeSeriesMemStore()
    ms_primary.setup(DS, 0)
    ms_primary.setup(DS, 1)
    wal = WalManager(str(tmp_path), DS)
    keys = _keys(6)
    for shard in (0, 1):
        for b in range(4):
            ts, vals = _grid(6, 8, base_idx=b * 8)
            seq = wal.append_grid(shard, PROM_COUNTER.name, keys, ts,
                                  {"count": vals})
            ms_primary.get_shard(DS, shard).ingest_columns(
                PROM_COUNTER.name, keys, ts, {"count": vals}, offset=seq)
    srv = ReplicationServer(ms_primary, node="P", wals={DS: wal}).start()
    try:
        cli = ReplicaClient(*srv.address)
        replica = TimeSeriesMemStore()
        stats = catchup_shards(cli, DS, replica, shards=[1], node="R")
        assert stats.records == 4
        assert stats.samples == 4 * 6 * 8
        # only the filtered shard materialized
        assert replica.get_shard(DS, 0) is None
        sh = replica.get_shard(DS, 1)
        assert sh.num_partitions == 6
        # replayed data answers identically to the primary's copy
        a = ms_primary.get_shard(DS, 1).stores[PROM_COUNTER.name]
        b = sh.stores[PROM_COUNTER.name]
        assert a.num_series == b.num_series
        # resume point: nothing replays twice
        stats2 = catchup_shards(cli, DS, replica, shards=[1],
                                since={1: stats.last_seq}, node="R")
        assert stats2.records == 0
        # the PR 10 job registry saw the runs
        h = jobs.get("replication_catchup", dataset=DS)
        assert h is not None and h.runs >= 2 and h.consecutive_errors == 0
        caught = [e for e in journal.since(0)
                  if e["kind"] == "replica_caught_up"
                  and e.get("node") == "R"]
        assert caught
    finally:
        srv.stop()
        wal.close()


def test_wal_snapshot_segments_safe_bytes(tmp_path):
    """The active segment's snapshot byte range decodes completely —
    whole frames only, no torn tail inside safe_bytes."""
    from filodb_tpu.wal.segment import WalRecord, read_records
    from filodb_tpu.wal.writer import WalWriter
    w = WalWriter(str(tmp_path), dataset=DS)
    keys = _keys(4)
    for b in range(5):
        ts, vals = _grid(4, 8, base_idx=b * 8)
        w.append(WalRecord(0, 0, PROM_COUNTER.name, keys, ts,
                           {"count": vals}))
    segs, committed = w.snapshot_segments()
    assert committed == 4
    assert segs, "active segment must appear in the snapshot"
    first, last, path, safe = segs[-1]
    assert last == 4
    data = open(path, "rb").read(safe)
    clone = str(tmp_path / "clone.seg")
    with open(clone, "wb") as f:
        f.write(data)
    tables = {}
    seqs = [WalRecord.decode(body, tables).seq
            for body in read_records(clone)]
    assert seqs == [0, 1, 2, 3, 4]
    w.close()


# ------------------------------------------------- query-time failover


def _fill_cluster(cluster, n_series=32, n_samples=64):
    keys = _keys(n_series)
    ts, vals = _grid(n_series, n_samples)
    for s in range(cluster.mapper.num_shards):
        skeys = [PartKey.make("repl_total",
                             {"_ws_": "w", "_ns_": f"s{s}",
                              "i": str(i)}) for i in range(n_series)]
        cluster.ingest_grid(s, PROM_COUNTER.name, skeys, ts,
                            {"count": vals})
    return keys, ts, vals


QUERY = 'sum by (_ns_)(rate(repl_total[5m]))'
QS = START // 1000 + 600
QE = START // 1000 + 630


def _payload(res):
    from filodb_tpu.query.engine import QueryEngine
    p = QueryEngine.to_prom_matrix(res)
    p.pop("traceID", None)
    return json.dumps(p, sort_keys=True)


def test_failover_serves_full_results_through_node_kill():
    from filodb_tpu.parallel.breaker import breakers
    from filodb_tpu.utils.metrics import registry
    breakers.reset()
    cluster = make_replicated_cluster(num_shards=2, with_truth=True)
    try:
        _fill_cluster(cluster)
        pp = PlannerParams(allow_partial_results=True)
        baseline = cluster.engine.query_range(QUERY, QS, 30, QE, pp)
        assert baseline.error is None and not baseline.partial
        groups = {k.labels_dict.get("_ns_")
                  for k, _, _ in baseline.series()}
        assert groups == {"s0", "s1"}
        # kill one node: every query stays FULL via replica failover
        victim = cluster.mapper.node_for_shard(0)
        fo0 = registry.counter("query_replica_failovers",
                               peer=cluster.mapper.replicas[0][0]).value
        cluster.kill(victim)
        for _ in range(4):
            res = cluster.engine.query_range(QUERY, QS, 30, QE, pp)
            assert res.error is None, res.error
            assert not res.partial, "failover must beat the partial path"
            got = {k.labels_dict.get("_ns_") for k, _, _ in res.series()}
            assert got == {"s0", "s1"}, f"missing groups: {got}"
            assert _payload(res) == _payload(baseline)
        fo1 = registry.counter("query_replica_failovers",
                               peer=cluster.mapper.replicas[0][0]).value
        assert fo1 > fo0, "failover counter must prove the replica served"
    finally:
        cluster.stop()
        breakers.reset()


def test_partials_only_when_all_owners_dead():
    from filodb_tpu.parallel.breaker import breakers
    breakers.reset()
    cluster = make_replicated_cluster(num_shards=2)
    try:
        _fill_cluster(cluster)
        # kill EVERY owner of shard 0; shard 1 keeps at least one owner
        dead = set(cluster.mapper.owners(0))
        survivors = [n for n in cluster.mapper.owners(1)
                     if n not in dead]
        assert survivors, "fixture must leave shard 1 an owner"
        for n in dead:
            cluster.kill(n)
        pp = PlannerParams(allow_partial_results=True)
        res = cluster.engine.query_range(QUERY, QS, 30, QE, pp)
        assert res.error is None, res.error
        assert res.partial, "all owners dead -> flagged partial"
        got = {k.labels_dict.get("_ns_") for k, _, _ in res.series()}
        assert "s0" not in got
    finally:
        cluster.stop()
        breakers.reset()


# ------------------------------------------------------- gather dedup


def test_gather_dedups_duplicate_shard_children():
    """Both owners of a shard materialized (handoff window): the shard
    contributes exactly once to concat AND aggregation."""
    from filodb_tpu.core.index import Equals
    from filodb_tpu.query.exec import (AggregateMapReduce,
                                       AggregatePresenter,
                                       LocalPartitionDistConcatExec,
                                       MultiSchemaPartitionsExec,
                                       PeriodicSamplesMapper,
                                       ReduceAggregateExec)
    from filodb_tpu.query.rangevector import QueryContext
    from filodb_tpu.utils.metrics import registry
    ms = TimeSeriesMemStore()
    ms.setup(DS, 0)
    keys = _keys(8)
    ts, vals = _grid(8, 64)
    ms.get_shard(DS, 0).ingest_columns(PROM_COUNTER.name, keys, ts,
                                       {"count": vals})

    def leaf():
        lf = MultiSchemaPartitionsExec(
            QueryContext(), DS, 0, [Equals("_metric_", "repl_total")],
            START, START + 64 * 10_000)
        lf.add_transformer(PeriodicSamplesMapper(
            QS * 1000, 30_000, QE * 1000, 300_000, "rate", ()))
        lf.add_transformer(AggregateMapReduce("sum", (), ("_ns_",), ()))
        return lf

    single = ReduceAggregateExec(QueryContext(), [leaf()], "sum")
    single.add_transformer(AggregatePresenter("sum", ()))
    want = single.execute(ms)
    assert want.error is None

    before = registry.counter("query_shard_dedup").value
    dup = ReduceAggregateExec(QueryContext(), [leaf(), leaf()], "sum")
    dup.add_transformer(AggregatePresenter("sum", ()))
    got = dup.execute(ms)
    assert got.error is None
    assert registry.counter("query_shard_dedup").value > before
    np.testing.assert_allclose(np.asarray(got.blocks[0].values),
                               np.asarray(want.blocks[0].values))

    # concat path too: series count must not double
    def leaf_raw():
        lf = MultiSchemaPartitionsExec(
            QueryContext(), DS, 0, [Equals("_metric_", "repl_total")],
            START, START + 64 * 10_000)
        lf.add_transformer(PeriodicSamplesMapper(
            QS * 1000, 30_000, QE * 1000, 300_000, "rate", ()))
        return lf

    single_cat = LocalPartitionDistConcatExec(QueryContext(),
                                              [leaf_raw()])
    want_cat = single_cat.execute(ms)
    cat = LocalPartitionDistConcatExec(QueryContext(),
                                       [leaf_raw(), leaf_raw()])
    res = cat.execute(ms)
    assert res.error is None
    assert len(res.blocks[0].keys) == len(want_cat.blocks[0].keys)


def test_gather_never_dedups_different_selectors_on_one_shard():
    """Regression: a ShardKeyRegexPlanner fan-out legitimately puts two
    same-shard leaves with DIFFERENT selectors under one concat — the
    dedup key must include the selector, or one combo's data silently
    vanishes from a FULL result."""
    from filodb_tpu.core.index import Equals
    from filodb_tpu.query.exec import (LocalPartitionDistConcatExec,
                                       MultiSchemaPartitionsExec,
                                       PeriodicSamplesMapper)
    from filodb_tpu.query.rangevector import QueryContext
    ms = TimeSeriesMemStore()
    ms.setup(DS, 0)
    ts, vals = _grid(4, 64)
    for ns in ("a", "b"):
        keys = [PartKey.make("repl_total",
                             {"_ws_": "w", "_ns_": ns, "i": str(i)})
                for i in range(4)]
        ms.get_shard(DS, 0).ingest_columns(PROM_COUNTER.name, keys, ts,
                                           {"count": vals})

    def leaf(ns):
        lf = MultiSchemaPartitionsExec(
            QueryContext(), DS, 0,
            [Equals("_metric_", "repl_total"), Equals("_ns_", ns)],
            START, START + 64 * 10_000)
        lf.add_transformer(PeriodicSamplesMapper(
            QS * 1000, 30_000, QE * 1000, 300_000, "rate", ()))
        return lf

    cat = LocalPartitionDistConcatExec(QueryContext(),
                                       [leaf("a"), leaf("b")])
    res = cat.execute(ms)
    assert res.error is None
    got_ns = {k.labels_dict.get("_ns_") for k in res.blocks[0].keys}
    assert got_ns == {"a", "b"}, \
        f"a shard-key combo was wrongly deduped away: {got_ns}"


def test_gather_twin_absorbs_shard_unavailable():
    """First-listed owner dead, duplicate twin healthy: the twin answers
    — no partial flag, no error (the handoff-window contract)."""
    from filodb_tpu.core.index import Equals
    from filodb_tpu.query.exec import (AggregateMapReduce,
                                       AggregatePresenter,
                                       MultiSchemaPartitionsExec,
                                       PeriodicSamplesMapper,
                                       QueryError,
                                       ReduceAggregateExec)
    from filodb_tpu.query.execbase import PlanDispatcher
    from filodb_tpu.query.rangevector import QueryContext
    ms = TimeSeriesMemStore()
    ms.setup(DS, 0)
    keys = _keys(4)
    ts, vals = _grid(4, 64)
    ms.get_shard(DS, 0).ingest_columns(PROM_COUNTER.name, keys, ts,
                                       {"count": vals})

    class _DeadDispatcher(PlanDispatcher):
        def dispatch(self, plan, source):
            raise QueryError("shard_unavailable", "owner SIGKILLed")

    def leaf(dead=False):
        lf = MultiSchemaPartitionsExec(
            QueryContext(), DS, 0, [Equals("_metric_", "repl_total")],
            START, START + 64 * 10_000)
        lf.add_transformer(PeriodicSamplesMapper(
            QS * 1000, 30_000, QE * 1000, 300_000, "rate", ()))
        lf.add_transformer(AggregateMapReduce("sum", (), ("_ns_",), ()))
        if dead:
            lf.dispatcher = _DeadDispatcher()
        return lf

    want = ReduceAggregateExec(QueryContext(), [leaf()], "sum")
    want.add_transformer(AggregatePresenter("sum", ()))
    base = want.execute(ms)

    plan = ReduceAggregateExec(QueryContext(),
                               [leaf(dead=True), leaf()], "sum")
    plan.add_transformer(AggregatePresenter("sum", ()))
    res = plan.execute(ms)
    assert res.error is None, res.error
    assert not res.partial
    np.testing.assert_allclose(np.asarray(res.blocks[0].values),
                               np.asarray(base.blocks[0].values))


# ------------------------------------------------------------- handoff


def test_handoff_state_machine_and_journal():
    cluster = make_replicated_cluster(nodes=("A", "B", "C"),
                                      num_shards=2, with_truth=True)
    try:
        _fill_cluster(cluster)
        pp = PlannerParams()
        baseline = cluster.engine.query_range(QUERY, QS, 30, QE, pp)
        assert baseline.error is None
        shard = 0
        from_node = cluster.mapper.node_for_shard(shard)
        owners = set(cluster.mapper.owners(shard))
        target = next(n for n in ("A", "B", "C") if n not in owners)
        from filodb_tpu.replication import HandoffCoordinator
        coord = HandoffCoordinator(DS, cluster.mapper,
                                   lambda n: cluster.repl_clients[n])
        seq0 = journal.next_seq
        summary = coord.handoff(shard, target)
        assert summary["states"][-1] == "done"
        assert cluster.mapper.node_for_shard(shard) == target
        assert from_node not in cluster.mapper.owners(shard)
        # the old owner's copy was tombstoned
        assert cluster.stores[from_node].get_shard(DS, shard) is None
        # the new owner answers; results byte-identical to pre-handoff
        res = cluster.engine.query_range(QUERY, QS, 30, QE, pp)
        assert res.error is None and not res.partial
        assert _payload(res) == _payload(baseline)
        kinds = [e["kind"] for e in journal.since(seq0 - 1)]
        assert "shard_handoff_started" in kinds
        assert "shard_handoff_done" in kinds
        states = [e["state"] for e in journal.since(seq0 - 1)
                  if e["kind"] == "shard_handoff"]
        assert states == ["register", "stream_snapshot",
                          "stream_wal_tail", "cutover", "tombstone",
                          "done"]
    finally:
        cluster.stop()


def test_handoff_failure_journals_and_rolls_back():
    from filodb_tpu.replication import (HandoffCoordinator, HandoffError,
                                        ReplicaClient)
    cluster = make_replicated_cluster(nodes=("A", "B", "C"),
                                      num_shards=2)
    try:
        _fill_cluster(cluster)
        shard = 0
        owners_before = list(cluster.mapper.owners(shard))
        target = next(n for n in ("A", "B", "C")
                      if n not in owners_before)
        # target's replication door is dead -> the snapshot stream fails
        cluster.repl_servers[target].stop()
        dead_client = ReplicaClient(*cluster.repl_servers[target].address,
                                    timeout_s=1.0)

        def client_for(n):
            return dead_client if n == target \
                else cluster.repl_clients[n]

        coord = HandoffCoordinator(DS, cluster.mapper, client_for)
        seq0 = journal.next_seq
        with pytest.raises(HandoffError):
            coord.handoff(shard, target)
        fails = [e for e in journal.since(seq0 - 1)
                 if e["kind"] == "shard_handoff_failed"]
        assert fails and fails[0]["state"] in ("register",
                                               "stream_snapshot")
        # rollback: the half-registered target left the assignment list
        assert cluster.mapper.owners(shard) == owners_before
    finally:
        cluster.stop()


# ------------------------------------------------- health + admin surface


def test_health_degrades_on_zero_live_replicas():
    from filodb_tpu.utils.health import (DEGRADED, FAILED, OK,
                                         HealthEvaluator, SERVING)
    ev = HealthEvaluator(phase=SERVING)
    m = ShardMapper(2, replication_factor=2)
    ev.shard_mappers = {DS: m}
    for s in (0, 1):
        m.update_from_event(ShardEvent("IngestionStarted", DS, s, "A"))
        m.register_replica(s, "B", status=ShardStatus.ACTIVE)
    assert ev._shards_verdict()["status"] == OK
    # replica of shard 0 dies: primary serves, but one failure from
    # partials -> degraded
    m.unassign_replica(0, "B")
    sv = ev._shards_verdict()
    assert sv["status"] == DEGRADED
    assert sv["datasets"][DS]["underReplicated"] == 1
    # every owner of shard 0 dead -> failed
    m.update_from_event(ShardEvent("ShardDown", DS, 0, "A"))
    sv = ev._shards_verdict()
    assert sv["status"] == FAILED
    assert sv["datasets"][DS]["noLiveOwners"] == 1


def test_ready_503_while_draining():
    from filodb_tpu.utils.health import HealthEvaluator, SERVING
    ev = HealthEvaluator(phase=SERVING)
    ok, _ = ev.ready()
    assert ok
    ev.draining = "drained 4 shard(s) off A"
    ok, reason = ev.ready()
    assert not ok and "draining" in reason


def test_admin_shards_route_and_cli_shape():
    from filodb_tpu.http.routes import PromHttpApi
    api = PromHttpApi({})
    m = ShardMapper(2, replication_factor=2)
    m.update_from_event(ShardEvent("IngestionStarted", DS, 0, "A"))
    m.register_replica(0, "B", status=ShardStatus.ACTIVE)
    api.shard_mappers[DS] = m
    st, payload = api.handle("GET", "/admin/shards", {})
    assert st == 200
    ent = payload["data"]["datasets"][DS]
    assert ent["replicationFactor"] == 2
    row = ent["shards"][0]
    assert row["primary"] == "A"
    assert row["replicas"] == [{"node": "B", "status": "Active"}]
    assert row["liveOwners"] == 2
    st, _ = api.handle("GET", "/admin/shards", {"dataset": "nope"})
    assert st == 404
    # handoff route without a coordinator is a clean 400
    st, payload = api.handle("POST", "/admin/shards/0/handoff",
                             {"to": "B"}, b"")
    assert st == 400


def test_admin_shards_handoff_route_drives_coordinator():
    cluster = make_replicated_cluster(nodes=("A", "B", "C"),
                                      num_shards=2)
    try:
        _fill_cluster(cluster)
        from filodb_tpu.http.routes import PromHttpApi
        from filodb_tpu.replication import HandoffCoordinator
        api = PromHttpApi({})
        api.default_dataset = DS
        api.shard_mappers[DS] = cluster.mapper
        api.handoffs[DS] = HandoffCoordinator(
            DS, cluster.mapper, lambda n: cluster.repl_clients[n])
        shard = 0
        owners = set(cluster.mapper.owners(shard))
        target = next(n for n in ("A", "B", "C") if n not in owners)
        st, payload = api.handle(
            "POST", f"/admin/shards/{shard}/handoff",
            {"drain": "true"},
            json.dumps({"to": target}).encode())
        assert st == 200, payload
        assert payload["data"]["to"] == target
        assert cluster.mapper.node_for_shard(shard) == target
        # drain=true flipped readiness
        ok, reason = api.health.ready()
        assert not ok and "handed off" in reason
        # a bad target is a structured 409, not a 500
        st, payload = api.handle(
            "POST", f"/admin/shards/{shard}/handoff", {},
            json.dumps({"to": target}).encode())
        assert st == 409
    finally:
        cluster.stop()


# ----------------------------------- chaos-style: traffic through handoff


@pytest.mark.replication
def test_live_handoff_under_traffic_zero_failed_queries():
    """The acceptance drill: ingest+query traffic runs while a shard
    hands off — zero failed queries, zero partials, and the final
    query_range is byte-identical to an undisturbed truth store."""
    cluster = make_replicated_cluster(nodes=("A", "B", "C"),
                                      num_shards=2, with_truth=True)
    try:
        n_series, n_samples = 16, 64
        skeys = {s: [PartKey.make("repl_total",
                                  {"_ws_": "w", "_ns_": f"s{s}",
                                   "i": str(i)})
                     for i in range(n_series)]
                 for s in range(2)}
        ts, vals = _grid(n_series, n_samples)
        for s in range(2):
            cluster.ingest_grid(s, PROM_COUNTER.name, skeys[s], ts,
                                {"count": vals})
        stop = threading.Event()
        qerrs, qpartials, qok = [], [], [0]
        tick = [n_samples]

        def query_loop():
            pp = PlannerParams(allow_partial_results=True)
            while not stop.is_set():
                res = cluster.engine.query_range(QUERY, QS, 30, QE, pp)
                if res.error is not None:
                    qerrs.append(res.error)
                elif res.partial:
                    qpartials.append(True)
                else:
                    qok[0] += 1
                time.sleep(0.02)

        def ingest_loop():
            while not stop.is_set():
                b = tick[0]
                tick[0] += 1
                for s in range(2):
                    ts2, vals2 = _grid(n_series, 1, base_idx=b)
                    cluster.ingest_grid(s, PROM_COUNTER.name, skeys[s],
                                        ts2, {"count": vals2})
                time.sleep(0.02)

        threads = [threading.Thread(target=query_loop, daemon=True),
                   threading.Thread(target=ingest_loop, daemon=True)]
        for t in threads:
            t.start()
        time.sleep(1.0)
        from filodb_tpu.replication import HandoffCoordinator
        shard = 0
        owners = set(cluster.mapper.owners(shard))
        target = next(n for n in ("A", "B", "C") if n not in owners)
        coord = HandoffCoordinator(DS, cluster.mapper,
                                   lambda n: cluster.repl_clients[n])
        summary = coord.handoff(shard, target)
        assert summary["states"][-1] == "done"
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not qerrs, f"queries failed during handoff: {qerrs[:3]}"
        assert not qpartials, "no partials during a handoff"
        # CPU XLA recompiles per fresh-shape poll make the loop slow;
        # the gates above cover every query that DID run
        assert qok[0] >= 1
        # quiesce: final answer identical to the undisturbed truth store
        res = cluster.engine.query_range(QUERY, QS, 30, QE,
                                         PlannerParams())
        from filodb_tpu.query.engine import QueryEngine
        tmapper = ShardMapper(2)
        for s in range(2):
            tmapper.update_from_event(
                ShardEvent("IngestionStarted", DS, s, "local"))
        truth_engine = QueryEngine(DS, cluster.truth, tmapper)
        want = truth_engine.query_range(QUERY, QS, 30, QE,
                                        PlannerParams())
        assert res.error is None and want.error is None
        got = {k.labels_dict["_ns_"]: np.asarray(v)
               for k, _, v in res.series()}
        exp = {k.labels_dict["_ns_"]: np.asarray(v)
               for k, _, v in want.series()}
        assert set(got) == {"s0", "s1"}
        for g in got:
            np.testing.assert_allclose(got[g], exp[g])
    finally:
        cluster.stop()
