"""Correctness drills: multi-process failure and recovery scenarios that end
in hard gates, not timings.

    python -m bench.drills {chaos,replication,objectstore,federation}
                           [--quick] [--series N] [--report PATH]

Each drill runs in this process pinned to the CPU (it exercises
degradation machinery, not kernels), spawns whatever node processes it
needs from this package (chaosnode.py, coldnode.py), prints ONE JSON line
and exits 0 only when its `<drill>_gate_ok` holds:

  chaos        three RF-2 data nodes, one SIGKILLed and respawned
               mid-traffic: availability 1.0, no partial, no acked loss
  replication  RF-2 fan-out, failover and live shard handoff under load
  objectstore  disk loss rebuilt from the shared object store, stateless
               query nodes, a dead store
  federation   two clusters: pushed partials bit-identical, a dead
               cluster flagged, recovery

The `chaos`-marked pytest (tests/test_partial_results.py) runs
`chaos --quick` and asserts the same gates.
"""
import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_chaos(quick=False, series=None, report=None):
    """Failure-domain chaos stage — REPLICATED (ISSUE 11, flipping the
    PR 4 gate): three real data-node processes each own copies of
    shards at RF=2 (primary + replica, never co-located); this process
    is the distributor (replication/replicator.py fan-out with quorum
    acks) AND the query coordinator (ReplicaFailoverDispatcher per
    shard).  Mid-traffic one node is SIGKILLed, later respawned on the
    same address and repaired by WAL-segment catch-up.  Gates:

      chaos_availability        == 1.0 — every fault-phase query
                                  answers in budget, served FULL via
                                  replica failover
      chaos_partial_rate        == 0.0 — the partial path never engages
                                  while any owner of a shard lives
      chaos_acked_lost          == 0  — every slab acked during the
                                  fault is queryable afterwards (the
                                  surviving owner held it; catch-up
                                  repaired the respawn)
      chaos_wrong_full_results  == 0  — a FULL result always carries
                                  every shard's group

    Full phase detail is written to `report` when a path is given."""
    import signal
    import socket as _socket
    import tempfile

    import numpy as np

    import jax
    jax.config.update("jax_platforms", "cpu")

    from bench.chaosnode import chaos_column
    from filodb_tpu.config import ReplicationConfig
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import PartKey
    from filodb_tpu.core.schemas import PROM_COUNTER
    from filodb_tpu.parallel.breaker import breakers
    from filodb_tpu.parallel.shardmapper import (ShardEvent, ShardMapper,
                                                 ShardStatus,
                                                 SpreadProvider)
    from filodb_tpu.parallel.transport import RemoteNodeDispatcher
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.query.planner import SingleClusterPlanner
    from filodb_tpu.query.rangevector import PlannerParams
    from filodb_tpu.replication import (ReplicaClient, ReplicationManager,
                                        failover_dispatcher_factory)
    from filodb_tpu.replication.catchup import relay_wal

    S_NODE = series or (512 if quick else 4_096)
    T = 420                              # 70 min of 10s scrapes
    START = 1_600_000_000_000
    STEP = 10_000
    BUDGET_S = 5.0
    phase_s = 4.0 if quick else 10.0
    dataset = "chaos"
    NODES = ("A", "B", "C")
    NUM_SHARDS = 4
    # RF-2 placement, replicas never co-located: shard s -> primary
    # NODES[s % 3], replica NODES[(s + 1) % 3]
    owners = {s: (NODES[s % 3], NODES[(s + 1) % 3])
              for s in range(NUM_SHARDS)}
    shards_of = {n: sorted(s for s, (p, r) in owners.items()
                           if n in (p, r)) for n in NODES}
    worker = os.path.join(REPO_DIR, "bench", "chaosnode.py")
    wal_root = tempfile.mkdtemp(prefix="filodb-chaos-wal-")

    def free_port():
        with _socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    env = {k: v for k, v in os.environ.items()}
    env["PYTHONPATH"] = REPO_DIR
    env["JAX_PLATFORMS"] = "cpu"
    logs = {n: open(os.path.join(REPO_DIR, f".chaos_node{n}.log"), "w")
            for n in NODES}

    def spawn(name):
        proc = subprocess.Popen(
            [sys.executable, worker, "--name", name,
             "--port", str(qports[name]),
             "--repl-port", str(rports[name]),
             "--shards", ",".join(str(s) for s in shards_of[name]),
             "--dataset", dataset,
             "--series", str(S_NODE), "--samples", str(T),
             "--start-ms", str(START),
             "--wal-dir", os.path.join(wal_root, name),
             "--platform", "cpu"],
            stdout=subprocess.PIPE, stderr=logs[name], text=True,
            env=env, cwd=REPO_DIR)
        line = proc.stdout.readline()
        ready = json.loads(line) if line.strip().startswith("{") else {}
        if not ready.get("ready"):
            raise RuntimeError(f"chaos node {name} failed to start: "
                               f"{line!r}")
        return proc

    qports = {n: free_port() for n in NODES}
    rports = {n: free_port() for n in NODES}
    procs = {n: spawn(n) for n in NODES}

    # coordinator state: replica-aware mapper, failover dispatchers,
    # quorum fan-out manager — no local data
    mapper = ShardMapper(NUM_SHARDS, replication_factor=2)
    for s, (p, r) in owners.items():
        mapper.update_from_event(
            ShardEvent("IngestionStarted", dataset, s, p))
        mapper.register_replica(s, r, status=ShardStatus.ACTIVE)
    dispatchers = {n: RemoteNodeDispatcher("127.0.0.1", qports[n],
                                           timeout_s=30.0)
                   for n in NODES}
    repl_clients = {n: ReplicaClient("127.0.0.1", rports[n],
                                     timeout_s=5.0) for n in NODES}
    planner = SingleClusterPlanner(
        dataset, mapper, SpreadProvider(default_spread=1),
        dispatcher_factory=failover_dispatcher_factory(
            mapper, lambda n: dispatchers[n]))
    engine = QueryEngine(dataset, TimeSeriesMemStore(), mapper,
                         planner=planner)
    manager = ReplicationManager(
        dataset, mapper, lambda n: repl_clients[n],
        config=ReplicationConfig(enabled=True, factor=2,
                                 ack_mode="quorum"))
    breakers.reset()
    breakers.configure(failure_threshold=3, open_base_s=0.3,
                       open_max_s=2.0, jitter=0.1)
    pp = PlannerParams(allow_partial_results=True, timeout_s=BUDGET_S,
                      sample_limit=2_000_000_000,
                      scan_limit=2_000_000_000)
    Q = 'sum by (_ns_)(rate(chaos_total[5m]))'
    qs, qe = START // 1000 + 600, START // 1000 + (T - 1) * 10
    ALL_GROUPS = sorted(f"s{s}" for s in range(NUM_SHARDS))

    skeys = {s: [PartKey.make("chaos_total",
                              {"_ws_": "chaos", "_ns_": f"s{s}",
                               "instance": f"s{s}-{i}"})
                 for i in range(S_NODE)] for s in range(NUM_SHARDS)}
    tick = {"n": T}
    acked = {s: -1 for s in range(NUM_SHARDS)}   # last acked tick
    seq = {"n": 0}

    def ingest_tick():
        """One fresh scrape column per shard through the quorum
        fan-out; on a primary-owner death the coordinator promotes the
        replica (the ClusterCoordinator deathwatch path, exercised
        in-process by tests) and keeps acking on the survivor."""
        t_idx = tick["n"]
        tick["n"] += 1
        for s in range(NUM_SHARDS):
            col_ts, col_v = chaos_column(s, S_NODE, t_idx, START, STEP)
            res = manager.replicate(s, PROM_COUNTER.name, skeys[s],
                                    col_ts, {"count": col_v},
                                    seq=seq["n"], require_primary=False)
            seq["n"] += 1
            primary = mapper.node_for_shard(s)
            if primary not in res.acked:
                live = [n for n in mapper.replicas[s]
                        if n in res.acked]
                if live:
                    # demote_old=False — the dead primary must NOT
                    # re-enter the owner list as a query-ready replica
                    # (same stance as ShardManager.remove_member); the
                    # respawn re-registers it after catch-up
                    mapper.promote_replica(s, live[0], demote_old=False)
            if res.acked:
                acked[s] = t_idx

    def drive(phase_name, dur_s):
        """Mixed ingest+query loop for one phase."""
        recs = []
        t_end = time.perf_counter() + dur_s
        last_ingest = 0.0
        while time.perf_counter() < t_end:
            if time.perf_counter() - last_ingest >= 1.0:
                ingest_tick()
                last_ingest = time.perf_counter()
            t0 = time.perf_counter()
            res = engine.query_range(Q, qs, 60, qe, pp)
            lat = time.perf_counter() - t0
            groups = {k.labels_dict.get("_ns_") for k, _, _ in
                      res.series()} if res.error is None else set()
            recs.append({"lat_s": lat, "error": res.error,
                         "partial": bool(res.partial),
                         "groups": sorted(g for g in groups if g)})
        return recs

    def p99(recs):
        if not recs:
            return 0.0
        lats = sorted(r["lat_s"] for r in recs)
        return lats[min(int(len(lats) * 0.99), len(lats) - 1)]

    # warmup WITHOUT the deadline: first-hit XLA compiles (coordinator
    # merge + node-side leaf kernels) must not eat the chaos budget
    warm_pp = PlannerParams(allow_partial_results=True,
                            sample_limit=2_000_000_000,
                            scan_limit=2_000_000_000)
    warm = engine.query_range(Q, qs, 60, qe, warm_pp)
    if warm.error:
        raise RuntimeError(f"chaos warmup failed: {warm.error}")

    # phase 1: healthy baseline (replicated ingest + full queries)
    healthy = drive("healthy", phase_s)

    # phase 2: SIGKILL node B mid-traffic.  B is primary for some
    # shards and replica for others — queries must stay FULL (failover)
    # and ingest must keep acking (promotion + surviving owner)
    victim = "B"
    os.kill(procs[victim].pid, signal.SIGKILL)
    procs[victim].wait()
    fault = drive("fault", phase_s)

    # phase 3: B respawns on the same address: replays its own WAL,
    # then the coordinator repairs the gap by relaying the current
    # primaries' WAL segments through B's door, and only THEN lists B
    # as a query-ready replica again
    procs[victim] = spawn(victim)
    repl_clients[victim].reset()
    dispatchers[victim]._reset()
    caught_up = 0
    by_src = {}
    for s in shards_of[victim]:
        src = mapper.node_for_shard(s)
        if src != victim and src is not None:
            by_src.setdefault(src, []).append(s)
    for src, shards in by_src.items():
        # one relay per SOURCE (not per shard — each relay streams the
        # source's whole log); restore windows buffer live fan-out
        # probes reaching B mid-relay so a fresh tick can never
        # OOO-drop the relayed gap
        for s in shards:
            repl_clients[victim].begin_restore(dataset, s)
        caught_up += relay_wal(repl_clients[src], repl_clients[victim],
                               dataset, shards=shards)
        for s in shards:
            repl_clients[victim].end_restore(dataset, s)
    if by_src:
        manager.mark_repaired(victim)
    for s in shards_of[victim]:
        if mapper.node_for_shard(s) != victim \
                and victim not in mapper.replicas[s]:
            mapper.register_replica(s, victim,
                                    status=ShardStatus.ACTIVE)
    recovery = drive("recovery", phase_s)

    # zero acked-ingest loss: for every shard, the latest ACKED tick's
    # column must be queryable now (value = 5*tick + row; max over the
    # shard's series at the acked tick's timestamp = 5*tick + S-1)
    acked_lost = 0
    loss_detail = {}
    for s in range(NUM_SHARDS):
        t_idx = acked[s]
        if t_idx < 0:
            continue
        t_s = (START + t_idx * STEP) // 1000
        res = engine.query_range(
            f'max(chaos_total{{_ns_="s{s}"}})', t_s, 1, t_s, warm_pp)
        want = 5.0 * t_idx + (S_NODE - 1)
        got = None
        if res.error is None:
            for _k, _w, vals in res.series():
                v = np.asarray(vals)
                if v.size and not np.isnan(v[-1]):
                    got = float(v[-1])
        if got is None or abs(got - want) > 1e-6:
            acked_lost += 1
            loss_detail[s] = {"want": want, "got": got,
                              "acked_tick": t_idx}

    for name, proc in procs.items():
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    for f in logs.values():
        f.close()

    def ok_within_budget(r):
        return r["error"] is None and r["lat_s"] <= BUDGET_S

    wrong_full = [r for r in fault
                  if r["error"] is None and not r["partial"]
                  and r["groups"] != ALL_GROUPS]
    avail = (sum(ok_within_budget(r) for r in fault) / len(fault)
             if fault else 0.0)
    partial_rate = (sum(r["partial"] for r in fault) / len(fault)
                    if fault else 0.0)
    healthy_p99 = p99(healthy)
    fault_p99 = p99(fault)
    recovered_full = sum(1 for r in recovery
                         if r["error"] is None and not r["partial"]
                         and r["groups"] == ALL_GROUPS)
    result = {
        "metric": "chaos_availability", "unit": "fraction",
        "value": round(avail, 4),
        "chaos_availability": round(avail, 4),
        "chaos_partial_rate": round(partial_rate, 4),
        "chaos_acked_lost": acked_lost,
        "chaos_p99_during_fault_s": round(fault_p99, 4),
        "healthy_p99_s": round(healthy_p99, 4),
        "chaos_p99_ratio": round(fault_p99 / max(healthy_p99, 1e-9), 2),
        "chaos_wrong_full_results": len(wrong_full),
        "chaos_queries": {"healthy": len(healthy), "fault": len(fault),
                          "recovery": len(recovery)},
        "chaos_recovered_full_results": recovered_full,
        "chaos_catchup_records": caught_up,
        "chaos_rf": 2, "chaos_nodes": len(NODES),
        "chaos_gate_ok": bool(avail == 1.0 and partial_rate == 0.0
                              and acked_lost == 0
                              and not wrong_full),
        "breakers": breakers.snapshot(),
        "replica_lag": manager.snapshot(),
        "series_per_shard": S_NODE, "budget_s": BUDGET_S,
        "platform": "cpu",
    }
    if loss_detail:
        result["chaos_acked_loss_detail"] = loss_detail
    artifact = {
        "run": "chaos", "quick": quick, "result": result,
        "owners": {str(s): list(o) for s, o in owners.items()},
        "phases": {"healthy": healthy, "fault": fault,
                   "recovery": recovery},
    }
    if report:
        with open(report, "w") as f:
            json.dump(artifact, f, indent=1)
    manager.stop()
    breakers.configure()
    breakers.reset()
    import shutil as _shutil
    _shutil.rmtree(wal_root, ignore_errors=True)
    return result


def run_replication(quick=False, series=None):
    """Replication stage (ISSUE 11): in-process RF-2 cluster on the real
    transports.  Three measurements + gates:

      replication_rf2_vs_rf1_pct   — quorum-acked RF-2 fan-out ingest
                                     throughput vs RF-1 (gate >= 50%:
                                     the durability copy may not halve
                                     the front door twice over)
      replication_catchup_samples_per_sec — WAL-segment catch-up drain
                                     rate into a fresh replica
      replication_handoff_*        — live handoff of a shard during
                                     mixed ingest+query traffic: zero
                                     failed queries, zero partials, and
                                     the final query_range byte-
                                     identical to an undisturbed
                                     single-store truth run
    """
    import tempfile
    import threading

    import numpy as np

    import jax
    jax.config.update("jax_platforms", "cpu")

    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import PartKey
    from filodb_tpu.core.schemas import PROM_COUNTER
    from filodb_tpu.parallel.shardmapper import ShardEvent, ShardMapper
    from filodb_tpu.parallel.testcluster import make_replicated_cluster
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.query.rangevector import PlannerParams
    from filodb_tpu.replication import HandoffCoordinator

    S = series or (256 if quick else 2_048)
    K = 8                                # samples per slab column
    T = 64                               # base samples per series
    START = 1_600_000_000_000
    STEP = 10_000
    dataset = "prometheus"
    pump_s = 1.5 if quick else 4.0

    def skeys_for(shard, n):
        return [PartKey.make("repl_total",
                             {"_ws_": "w", "_ns_": f"s{shard}",
                              "i": str(i)}) for i in range(n)]

    def grid(n_series, n_samples, base_idx=0):
        ts = (np.arange(n_samples, dtype=np.int64)[None, :]
              + base_idx) * STEP + START
        ts = np.repeat(ts, n_series, axis=0)
        vals = (np.arange(n_samples, dtype=np.float64)[None, :]
                + base_idx) * 5.0 \
            + np.arange(n_series, dtype=np.float64)[:, None]
        return ts, vals

    # ---------------------------------------- RF-1 vs RF-2 throughput
    def pump(cluster, dur_s):
        keys = {s: skeys_for(s, S) for s in range(2)}
        n = 0
        b = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < dur_s:
            for s in range(2):
                ts, vals = grid(S, K, base_idx=b * K)
                cluster.manager.replicate(s, PROM_COUNTER.name, keys[s],
                                          ts, {"count": vals},
                                          require_primary=True)
                n += S * K
            b += 1
        return n / (time.perf_counter() - t0)

    rates = {}
    for rf in (1, 2):
        cluster = make_replicated_cluster(num_shards=2,
                                          replication_factor=rf)
        try:
            pump(cluster, 0.3)           # warm sockets + key memos
            rates[rf] = pump(cluster, pump_s)
        finally:
            cluster.stop()
    rf2_pct = 100.0 * rates[2] / max(rates[1], 1e-9)

    # ------------------------------------------------ catch-up drain
    from filodb_tpu.replication import (ReplicaClient, ReplicationServer,
                                        catchup_shards)
    from filodb_tpu.wal import WalManager
    wal_root = tempfile.mkdtemp(prefix="filodb-replbench-")
    primary = TimeSeriesMemStore()
    primary.setup(dataset, 0)
    wal = WalManager(wal_root, dataset)
    keys0 = skeys_for(0, S)
    n_grids = 20 if quick else 60
    for b in range(n_grids):
        ts, vals = grid(S, K, base_idx=b * K)
        seq = wal.append_grid(0, PROM_COUNTER.name, keys0, ts,
                              {"count": vals})
        primary.get_shard(dataset, 0).ingest_columns(
            PROM_COUNTER.name, keys0, ts, {"count": vals}, offset=seq)
    srv = ReplicationServer(primary, node="P",
                            wals={dataset: wal}).start()
    try:
        replica = TimeSeriesMemStore()
        stats = catchup_shards(ReplicaClient(*srv.address), dataset,
                               replica, shards=[0], node="bench")
        catchup_sps = stats.samples_per_sec
        catchup_ok = stats.records == n_grids
    finally:
        srv.stop()
        wal.close()
        import shutil as _shutil
        _shutil.rmtree(wal_root, ignore_errors=True)

    # ------------------------------- live handoff under mixed traffic
    Q = 'sum by (_ns_)(rate(repl_total[5m]))'
    qs, qe = START // 1000 + 600, START // 1000 + 630
    cluster = make_replicated_cluster(nodes=("A", "B", "C"),
                                      num_shards=2, with_truth=True)
    handoff_summary = {}
    try:
        skeys = {s: skeys_for(s, S) for s in range(2)}
        ts, vals = grid(S, T)
        for s in range(2):
            cluster.ingest_grid(s, PROM_COUNTER.name, skeys[s], ts,
                                {"count": vals})
        pp = PlannerParams(allow_partial_results=True)
        warm = cluster.engine.query_range(Q, qs, 30, qe, pp)
        if warm.error:
            raise RuntimeError(f"replication warmup failed: "
                               f"{warm.error}")
        stop = threading.Event()
        qerrs, qpartials, qok = [], [], [0]
        tick = [T]

        def query_loop():
            while not stop.is_set():
                res = cluster.engine.query_range(Q, qs, 30, qe, pp)
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
                    ts2, vals2 = grid(S, 1, base_idx=b)
                    cluster.ingest_grid(s, PROM_COUNTER.name, skeys[s],
                                        ts2, {"count": vals2})
                time.sleep(0.05)

        threads = [threading.Thread(target=query_loop, daemon=True),
                   threading.Thread(target=ingest_loop, daemon=True)]
        for t in threads:
            t.start()
        time.sleep(1.0)
        shard = 0
        owners = set(cluster.mapper.owners(shard))
        target = next(n for n in ("A", "B", "C") if n not in owners)
        coord = HandoffCoordinator(dataset, cluster.mapper,
                                   lambda n: cluster.repl_clients[n])
        t0 = time.perf_counter()
        handoff_summary = coord.handoff(shard, target)
        handoff_s = time.perf_counter() - t0
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        # quiesced comparison vs the undisturbed truth store
        res = cluster.engine.query_range(Q, qs, 30, qe, PlannerParams())
        tmapper = ShardMapper(2)
        for s in range(2):
            tmapper.update_from_event(
                ShardEvent("IngestionStarted", dataset, s, "local"))
        truth_engine = QueryEngine(dataset, cluster.truth, tmapper)
        want = truth_engine.query_range(Q, qs, 30, qe, PlannerParams())

        def payload(r):
            p = QueryEngine.to_prom_matrix(r)
            p.pop("traceID", None)
            return json.dumps(p, sort_keys=True)

        handoff_identical = (res.error is None and want.error is None
                             and payload(res) == payload(want))
        handoff_failed_queries = len(qerrs)
        handoff_partials = len(qpartials)
        handoff_queries_ok = qok[0]
    finally:
        cluster.stop()

    gate_ok = bool(rf2_pct >= 50.0 and catchup_ok
                   and handoff_failed_queries == 0
                   and handoff_partials == 0 and handoff_identical)
    return {
        "metric": "replication_rf2_vs_rf1_pct", "unit": "%",
        "value": round(rf2_pct, 1),
        "replication_rf1_samples_per_sec": round(rates[1]),
        "replication_rf2_samples_per_sec": round(rates[2]),
        "replication_rf2_vs_rf1_pct": round(rf2_pct, 1),
        "replication_catchup_samples_per_sec": round(catchup_sps),
        "replication_handoff_failed_queries": handoff_failed_queries,
        "replication_handoff_partials": handoff_partials,
        "replication_handoff_identical": handoff_identical,
        "replication_handoff_seconds": round(handoff_s, 3),
        "replication_handoff_queries_ok": handoff_queries_ok,
        "replication_handoff_states": handoff_summary.get("states", []),
        "replication_gate_ok": gate_ok,
        "series_per_shard": S, "platform": "cpu",
    }


def run_objectstore(quick=False, series=None):
    """Disaggregated cold-tier stage (ISSUE 19): the disk-loss +
    elastic-read drills over persist/objectstore.py.  Three parts,
    each gated:

      (a) disk-kill drill — a FiloServer compacts + uploads two windows
          to a shared object store, takes a WAL-riding remote_write
          tail, then loses its ENTIRE store root (chunks.log, segments,
          meta).  While it is down, a stateless cold-read cluster over
          the same shared store keeps answering the historical range
          (objectstore_drill_availability == 1.0).  A reboot on the
          empty disk restores segments from the manifests, replays the
          WAL tail, and must answer the full-range query_range
          byte-identical to the pre-kill baseline (traceID stripped).
      (b) elastic-read gate — a cold-only 4-shard dataset in the shared
          store, served by real query-node OS processes
          (bench/coldnode.py: zero owned shards, manifest mount only).
          1 node vs 1 data + 2 query-only under the same concurrent
          client load: objectstore_elastic_qps_ratio >= 1.8 (on hosts
          with >= 3 cores; no-collapse + identity on smaller hosts) and
          results bit-identical.
      (c) dead-store degrade — every objectstore.get errors (fault
          point + breaker): a partial-tolerant query returns a FLAGGED
          partial in bounded wall time; a strict query surfaces the
          typed error.  Never a hang, never a silent full.
    """
    import shutil
    import signal
    import socket as _socket
    import tempfile
    import threading

    import numpy as np

    import jax
    jax.config.update("jax_platforms", "cpu")

    from filodb_tpu.config import FilodbSettings
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import PartKey
    from filodb_tpu.http import remotepb
    from filodb_tpu.parallel.breaker import breakers
    from filodb_tpu.parallel.shardmapper import (ShardEvent, ShardMapper,
                                                 SpreadProvider)
    from filodb_tpu.parallel.testcluster import make_cold_read_cluster
    from filodb_tpu.parallel.transport import RemoteNodeDispatcher
    from filodb_tpu.persist.compactor import SegmentCompactor
    from filodb_tpu.persist.localstore import (LocalDiskColumnStore,
                                               LocalDiskMetaStore)
    from filodb_tpu.persist.objectstore import (LocalObjectStore,
                                                SegmentUploader,
                                                make_query_tier)
    from filodb_tpu.persist.segments import SegmentStore
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.query.planners import PersistedClusterPlanner
    from filodb_tpu.query.rangevector import PlannerParams
    from filodb_tpu.replication.failover import cold_dispatcher_factory
    from filodb_tpu.standalone import DatasetConfig, FiloServer
    from filodb_tpu.utils import snappy as fsnappy
    from filodb_tpu.utils.faults import faults

    WINDOW = 3600 * 1000
    INTERVAL = 60_000
    root = tempfile.mkdtemp(prefix="filodb-objbench-")
    procs = []
    try:
        # ------------------------------- (a) disk-kill drill (FiloServer)
        S_a = 128 if quick else 512
        now_ms = int(time.time() * 1000)
        t0 = (now_ms - 5 * WINDOW) - ((now_ms - 5 * WINDOW) % WINDOW)
        na = 2 * WINDOW // INTERVAL
        grid_a = t0 + np.arange(na, dtype=np.int64) * INTERVAL
        vals_a = (np.arange(S_a)[:, None] * 7.0
                  + (np.arange(na) % 13)[None, :])
        pks_a = [PartKey("m", (("inst", f"i{i}"), ("_ws_", "w"),
                               ("_ns_", "drill"))) for i in range(S_a)]
        tail_batches, tail_k = 4, 8
        tail_start = int(grid_a[-1]) + INTERVAL

        def tail_payload(b):
            srs = []
            for i in range(S_a):
                labels = [("__name__", "m"), ("_ws_", "w"),
                          ("_ns_", "drill"), ("inst", f"i{i}")]
                samples = [(float(i + j + b),
                            tail_start + (b * tail_k + j) * INTERVAL)
                           for j in range(tail_k)]
                srs.append(remotepb.PromTimeSeries(labels, samples))
            return fsnappy.compress(remotepb.encode_write_request(srs))

        cfg = FilodbSettings()
        cfg.store.segment_window_ms = WINDOW
        cfg.store.segment_closed_lag_ms = WINDOW
        cfg.store.segment_retain_raw_ms = 1
        cfg.objectstore.root = os.path.join(root, "shared-a")
        cfg.objectstore.retry_base_s = 0.001
        cfg.objectstore.retry_max_s = 0.01
        cfg.wal.enabled = True
        cfg.wal.dir = os.path.join(root, "wal-a")
        store_root = os.path.join(root, "node-a")
        tail_end = tail_start + tail_batches * tail_k * INTERVAL
        # grid chosen so no instant lands inside the raw/cold seam band
        # [earliest_raw, earliest_raw + lookback): instants there route
        # to the cold tier, whose coverage legitimately ends before the
        # WAL tail — the same conservative split FiloDB's raw/downsample
        # boundary makes.  step 600s > lookback 300s and a +300s phase
        # puts the grid at seam±300s exactly, where both tiers agree.
        q_full = {"query": "sum(m)", "start": str(t0 // 1000 + 300),
                  "end": str(tail_end // 1000), "step": "600"}

        def filo_query(server, query):
            st, pay = server.api.handle("GET", "/api/v1/query_range",
                                        dict(query), b"")
            assert st == 200, pay
            pay.pop("traceID", None)
            return pay

        srv = FiloServer([DatasetConfig("prometheus", num_shards=1)],
                         column_store=LocalDiskColumnStore(store_root),
                         meta_store=LocalDiskMetaStore(store_root),
                         config=cfg)
        try:
            shard = srv.memstore.get_shard("prometheus", 0)
            shard.ingest_columns("gauge", pks_a,
                                 np.broadcast_to(grid_a, (S_a, na)),
                                 {"value": vals_a})
            shard.flush_all_groups()
            # compact -> upload -> retention (upload ack gates the prune)
            srv.compaction_schedulers["prometheus"].run_once()
            uploaded = srv.uploaders["prometheus"].uploads
            tail_acked = 0
            for b in range(tail_batches):        # WAL-riding tail
                st, _ = srv.api.handle("POST", "/api/v1/write", {},
                                       tail_payload(b))
                assert st == 204, f"remote_write got {st}"
                tail_acked += 1
            baseline = filo_query(srv, q_full)
            assert baseline["data"]["result"], "drill baseline empty"
        finally:
            srv.shutdown()

        # the disk dies — WAL and shared store survive, nothing else
        shutil.rmtree(store_root)

        # while the node is down, stateless readers over the shared tier
        # keep the historical range answerable: that IS the availability
        shared_a = LocalObjectStore(cfg.objectstore.root, name="avail")
        cold = make_cold_read_cluster(shared_a, num_shards=1,
                                      dataset="prometheus",
                                      data_nodes=("b0",),
                                      query_nodes=("qb",))
        avail_ok = avail_n = 0
        try:
            qs_a = t0 // 1000 + 600
            qe_a = int(grid_a[-1]) // 1000
            for _ in range(20):
                avail_n += 1
                r = cold.engine.query_range("sum(m)", qs_a, 300, qe_a)
                if r.error is None and not r.partial and \
                        list(r.series()):
                    avail_ok += 1
        finally:
            cold.stop()
        availability = avail_ok / max(avail_n, 1)

        # reboot on the empty disk: manifests restore the segments, the
        # WAL replays the tail, the answer must not have changed a byte
        srv2 = FiloServer([DatasetConfig("prometheus", num_shards=1)],
                          column_store=LocalDiskColumnStore(store_root),
                          meta_store=LocalDiskMetaStore(store_root),
                          config=cfg)
        try:
            restored = len(SegmentStore(store_root).list("prometheus", 0))
            mount_ok = srv2.health.pending_manifest_mounts() == []
            rebuilt = filo_query(srv2, q_full)
            drill_identical = (json.dumps(rebuilt, sort_keys=True)
                               == json.dumps(baseline, sort_keys=True))
        finally:
            srv2.shutdown()

        # -------------------------- (b) elastic read: real node processes
        DSB = "coldbench"
        NSH = 4
        S_b = series or (512 if quick else 2_048)
        T0B = 1_600_000_000_000 - (1_600_000_000_000 % WINDOW)
        nb = 2 * WINDOW // INTERVAL
        grid_b = T0B + np.arange(nb, dtype=np.int64) * INTERVAL
        broot = os.path.join(root, "shared-b")
        disk_b = os.path.join(root, "disk-b")
        cs_b = LocalDiskColumnStore(disk_b)
        ms_b = TimeSeriesMemStore(column_store=cs_b,
                                  meta_store=LocalDiskMetaStore(disk_b))
        for s in range(NSH):
            sh = ms_b.setup(DSB, s)
            keys = [PartKey("m", (("inst", f"i{i}"), ("_ws_", "w"),
                                  ("_ns_", f"s{s}")))
                    for i in range(S_b)]
            vals = (np.arange(S_b)[:, None] * 3.0 + s
                    + (np.arange(nb) % 17)[None, :])
            sh.ingest_columns("gauge", keys,
                              np.broadcast_to(grid_b, (S_b, nb)),
                              {"value": vals})
            sh.flush_all_groups()
        seg_b = SegmentStore(disk_b)
        comp_b = SegmentCompactor(cs_b, seg_b, DSB, NSH,
                                  window_ms=WINDOW, closed_lag_ms=0)
        n_segs = comp_b.compact_all(now_ms=int(grid_b[-1]) + 10 * WINDOW)
        store_b = LocalObjectStore(broot, name="bench-up")
        up_b = SegmentUploader(store_b, seg_b, DSB, NSH,
                               retry_base_s=0.001, retry_max_s=0.01)
        up_b.mount()
        n_up = up_b.run_once()

        def free_port():
            with _socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]

        env = dict(os.environ)
        env["PYTHONPATH"] = REPO_DIR
        env["JAX_PLATFORMS"] = "cpu"
        worker = os.path.join(REPO_DIR, "bench", "coldnode.py")
        ports = {}

        def spawn_cold(name):
            port = free_port()
            p = subprocess.Popen(
                [sys.executable, worker, "--name", name,
                 "--port", str(port), "--objstore", broot,
                 "--dataset", DSB, "--num-shards", str(NSH)],
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, env=env, cwd=REPO_DIR)
            procs.append(p)
            ready = json.loads(p.stdout.readline())
            assert ready.get("ready"), f"cold node {name}: {ready}"
            ports[name] = ready["port"]

        def make_engine(query_nodes=()):
            mapper = ShardMapper(NSH)
            for s in range(NSH):
                mapper.update_from_event(
                    ShardEvent("IngestionStarted", DSB, s, "data0"))
            for qn in query_nodes:
                mapper.register_query_node(qn)
            dispatchers = {}

            def dispatcher_for(node):
                d = dispatchers.get(node)
                if d is None:
                    dispatchers[node] = d = RemoteNodeDispatcher(
                        "127.0.0.1", ports[node])
                return d

            tier, _remote = make_query_tier(store_b, DSB, NSH)
            planner = PersistedClusterPlanner(
                DSB, mapper, tier,
                spread_provider=SpreadProvider(default_spread=1),
                dispatcher_factory=cold_dispatcher_factory(
                    mapper, dispatcher_for))
            return QueryEngine(DSB, TimeSeriesMemStore(), mapper,
                               planner=planner)

        qs_b = T0B // 1000 + 600
        qe_b = int(grid_b[-1]) // 1000
        Q_b = "sum by (_ns_)(m)"

        def payload(res):
            p = QueryEngine.to_prom_matrix(res)
            p.pop("traceID", None)
            return json.dumps(p, sort_keys=True)

        def measure_qps(engine, dur_s, threads=8):
            for _ in range(3):                   # warm every node's leaves
                warm = engine.query_range(Q_b, qs_b, 300, qe_b)
                assert warm.error is None, warm.error
            stop = time.perf_counter() + dur_s
            counts = [0] * threads
            errs = []

            def loop(i):
                while time.perf_counter() < stop:
                    r = engine.query_range(Q_b, qs_b, 300, qe_b)
                    if r.error is not None or r.partial:
                        errs.append(r.error or "partial")
                        return
                    counts[i] += 1

            ths = [threading.Thread(target=loop, args=(i,))
                   for i in range(threads)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            assert not errs, f"elastic load errors: {errs[:3]}"
            return sum(counts) / dur_s

        dur = 2.0 if quick else 5.0
        spawn_cold("data0")
        eng1 = make_engine()
        ref1 = payload(eng1.query_range(Q_b, qs_b, 300, qe_b))
        qps1 = measure_qps(eng1, dur)
        spawn_cold("q1")
        spawn_cold("q2")
        eng3 = make_engine(query_nodes=("q1", "q2"))
        ref3 = payload(eng3.query_range(Q_b, qs_b, 300, qe_b))
        qps3 = measure_qps(eng3, dur)
        elastic_identical = ref1 == ref3
        ratio = qps3 / max(qps1, 1e-9)
        # the 1.8x scale-out gate needs real parallel hardware: three
        # node processes on a 1-core host share that core, so there the
        # stage gates on no-collapse + bit-identity instead (the spread
        # machinery is still exercised end-to-end)
        cores = len(os.sched_getaffinity(0)) if hasattr(
            os, "sched_getaffinity") else (os.cpu_count() or 1)
        if cores >= 3:
            elastic_gate = "qps_ratio>=1.8"
            elastic_ok = ratio >= 1.8 and elastic_identical
        else:
            elastic_gate = f"no-collapse ({cores} core host)"
            elastic_ok = ratio >= 0.5 and elastic_identical
        for p in procs:
            p.send_signal(signal.SIGKILL)
        for p in procs:
            p.wait(timeout=30)
        procs.clear()

        # ------------------------------------- (c) dead-store degrade
        def make_local_engine():
            mapper = ShardMapper(NSH)
            for s in range(NSH):
                mapper.update_from_event(
                    ShardEvent("IngestionStarted", DSB, s, "local"))
            # fresh tier + cache each time: nothing pre-paged, so the
            # dead-store query MUST touch objectstore.get
            tier, _remote = make_query_tier(store_b, DSB, NSH,
                                            ttl_s=1_000.0)
            planner = PersistedClusterPlanner(
                DSB, mapper, tier,
                spread_provider=SpreadProvider(default_spread=1))
            return QueryEngine(DSB, TimeSeriesMemStore(), mapper,
                               planner=planner)

        healthy = make_local_engine().query_range(Q_b, qs_b, 300, qe_b)
        assert healthy.error is None and not healthy.partial
        eng_part, eng_strict = make_local_engine(), make_local_engine()
        breakers.configure(failure_threshold=2, open_base_s=0.05,
                           open_max_s=0.1, jitter=0.0)
        try:
            t_dead = time.perf_counter()
            with faults.plan("objectstore.get", "error",
                             first_k=1_000_000):
                res_p = eng_part.query_range(
                    Q_b, qs_b, 300, qe_b,
                    PlannerParams(allow_partial_results=True))
            dead_s = time.perf_counter() - t_dead
            partial_flagged = res_p.error is None and bool(res_p.partial)
            with faults.plan("objectstore.get", "error",
                             first_k=1_000_000):
                res_s = eng_strict.query_range(Q_b, qs_b, 300, qe_b)
            strict_error = res_s.error is not None
        finally:
            faults.disarm()
            breakers.configure()
            breakers.reset()
        bounded = dead_s < 10.0

        gate_ok = bool(drill_identical and mount_ok
                       and availability == 1.0
                       and restored == 2 and uploaded == 2
                       and n_segs == n_up == NSH * 2
                       and elastic_ok
                       and partial_flagged and strict_error and bounded)
        return {
            "metric": "objectstore_elastic_qps_ratio", "unit": "x",
            "value": round(ratio, 2),
            "objectstore_drill_identical": drill_identical,
            "objectstore_drill_availability": round(availability, 3),
            "objectstore_drill_restored_segments": restored,
            "objectstore_drill_uploaded_segments": uploaded,
            "objectstore_drill_wal_tail_batches": tail_acked,
            "objectstore_elastic_qps_1node": round(qps1, 1),
            "objectstore_elastic_qps_3node": round(qps3, 1),
            "objectstore_elastic_qps_ratio": round(ratio, 2),
            "objectstore_elastic_identical": elastic_identical,
            "objectstore_elastic_cores": cores,
            "objectstore_elastic_gate": elastic_gate,
            "objectstore_deadstore_partial_flagged": partial_flagged,
            "objectstore_deadstore_strict_error": strict_error,
            "objectstore_deadstore_seconds": round(dead_s, 3),
            "objectstore_gate_ok": gate_ok,
            "series_per_shard": S_b, "platform": "cpu",
        }
    finally:
        for p in procs:
            try:
                p.send_signal(signal.SIGKILL)
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001 — already dead
                pass
        shutil.rmtree(root, ignore_errors=True)


def run_federation(quick=False, series=None):
    """Cross-cluster federation stage (ISSUE 20): the two-cluster
    testbench over parallel/testcluster.make_federated_pair.  Gated:

      (a) bit-identity — a federated exactly-mergeable `sum by` (west
          replies one [G, W] cluster partial over the door) and a
          non-mergeable per-series shape (series shipping) must be
          bit-identical to a single-cluster truth engine holding every
          series; a cross-cluster binary join likewise.
      (b) dead-cluster degrade — west's door dies with the SIGKILL
          signature mid-bench: a partial-tolerant query must return a
          FLAGGED partial NAMING cluster:west in bounded wall time
          (never a hang, never silent short data), and after the door
          revives the half-open breaker must recover to full
          bit-identical answers.
      (c) wire ratio — the same `sum by` against a push_partials=False
          strawman pair (every remote series ships raw): the pushed
          wire bytes must be at least federation_wire_ratio_x smaller,
          the O(groups)-vs-O(series) win federation exists for.
    """
    import numpy as np

    import jax
    jax.config.update("jax_platforms", "cpu")

    from filodb_tpu.parallel.breaker import breakers
    from filodb_tpu.parallel.testcluster import make_federated_pair
    from filodb_tpu.query.rangevector import PlannerParams

    S_f = int(series) if series else (8 if quick else 32)
    n_samples = 60 if quick else 240
    s0 = 1_600_000_020
    q_sum = "sum by (_ns_) (fed_gauge)"
    q_series = "avg_over_time(fed_gauge[2m])"
    q_join = ('sum by (_ns_) (fed_gauge{region="west"}) '
              '+ sum by (_ns_) (fed_gauge{region="east"})')
    args = (s0 + 180, 60, s0 + (n_samples - 2) * 10)
    pp = PlannerParams(allow_partial_results=True, timeout_s=30.0)

    def identical(res, truth):
        if res.error is not None or truth.error is not None:
            return False
        got = {str(k): np.asarray(v) for k, _, v in res.series()}
        want = {str(k): np.asarray(v) for k, _, v in truth.series()}
        return set(got) == set(want) and all(
            np.array_equal(got[k], want[k], equal_nan=True) for k in want)

    breakers.configure(failure_threshold=3, open_base_s=0.2,
                       open_max_s=0.5, jitter=0.0)
    breakers.reset()
    pair = make_federated_pair(num_series=S_f, num_samples=n_samples,
                               start=False)
    try:
        # --------------------------------------------- (a) bit-identity
        res_sum = pair.engine.query_range(q_sum, *args)
        ident = (identical(res_sum, pair.truth.query_range(q_sum, *args))
                 and res_sum.stats.pushdown_pushed >= 1
                 and identical(pair.engine.query_range(q_series, *args),
                               pair.truth.query_range(q_series, *args)))
        join_ident = identical(pair.engine.query_range(q_join, *args),
                               pair.truth.query_range(q_join, *args))
        pushed_bytes = res_sum.stats.wire_bytes

        # --------------------------------------- (b) dead-cluster drill
        pair.kill_west()
        t0 = time.perf_counter()
        dead = pair.engine.query_range(q_sum, *args, planner_params=pp)
        dead_s = time.perf_counter() - t0
        partial_flagged = (dead.error is None and dead.partial
                          and dead_s < 30.0)
        names_cluster = any("cluster:west" in w
                            for w in dead.stats.warnings)
        pair.revive_west()
        recovered = False
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            res = pair.engine.query_range(q_sum, *args, planner_params=pp)
            if res.error is None and not res.partial:
                recovered = identical(res, pair.truth.query_range(q_sum,
                                                                  *args))
                break
            time.sleep(0.2)
    finally:
        pair.stop()
        breakers.reset()

    # ------------------------------------------------- (c) wire ratio
    straw = make_federated_pair(num_series=S_f, num_samples=n_samples,
                                push_partials=False, start=False)
    try:
        res = straw.engine.query_range(q_sum, *args)
        shipped_ok = identical(res, straw.truth.query_range(q_sum, *args))
        shipped_bytes = res.stats.wire_bytes
    finally:
        straw.stop()
        breakers.configure()
        breakers.reset()
    ratio = (shipped_bytes / pushed_bytes) if pushed_bytes else 0.0

    gate_ok = bool(ident and join_ident and partial_flagged
                   and names_cluster and recovered and shipped_ok
                   and ratio >= 1.2)
    return {
        "metric": "federation_wire_ratio_x",
        "value": round(ratio, 2), "unit": "x",
        "federation_identical": 1.0 if ident else 0.0,
        "federation_join_identical": 1.0 if join_ident else 0.0,
        "federation_partial_on_dead_cluster":
            1.0 if partial_flagged else 0.0,
        "federation_dead_names_cluster": 1.0 if names_cluster else 0.0,
        "federation_dead_seconds": round(dead_s, 3),
        "federation_recovered_full": 1.0 if recovered else 0.0,
        "federation_wire_ratio_x": round(ratio, 2),
        "federation_pushed_wire_bytes": pushed_bytes,
        "federation_shipped_wire_bytes": shipped_bytes,
        "federation_gate_ok": gate_ok,
        "series_per_region": S_f, "platform": "cpu",
    }


# drill -> (function, headline metric, its unit): the metric and unit name
# the one-line JSON of a drill that raised
DRILLS = {
    "chaos": (run_chaos, "chaos_availability", "fraction"),
    "replication": (run_replication, "replication_rf2_vs_rf1_pct", "%"),
    "objectstore": (run_objectstore, "objectstore_elastic_qps_ratio", "x"),
    "federation": (run_federation, "federation_wire_ratio_x", "x"),
}


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m bench.drills", description=__doc__.split("\n\n")[0])
    ap.add_argument("drill", choices=list(DRILLS))
    ap.add_argument("--quick", action="store_true",
                    help="small sizes and short phases")
    ap.add_argument("--series", type=int, default=0,
                    help="series per shard or node (0: the drill's own)")
    ap.add_argument("--report", default=None,
                    help="chaos only: write the full phase detail here")
    args = ap.parse_args(argv)
    if args.report and args.drill != "chaos":
        ap.error("--report: only the chaos drill writes one")
    run, metric, unit = DRILLS[args.drill]
    kw = {"report": args.report} if args.report else {}
    try:
        r = run(quick=args.quick, series=args.series or None, **kw)
    except Exception as e:  # noqa: BLE001 — loud one-line fail
        print(json.dumps({
            "metric": metric, "unit": unit,
            f"{args.drill}_error": f"{type(e).__name__}: {e}"[:300]}))
        sys.exit(1)
    print(json.dumps(r))
    sys.exit(0 if r.get(f"{args.drill}_gate_ok") else 1)


if __name__ == "__main__":
    main()
