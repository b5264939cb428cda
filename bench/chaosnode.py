"""Chaos-bench data node: one OS process owning COPIES of shards.

Spawned (and SIGKILLed, and respawned) by `python -m bench.drills chaos`: for
every shard in --shards it builds the same deterministic counter
dataset any other owner of that shard builds (series are tagged
`_ns_=s<shard>` — shard-keyed, so primary and replica copies are
byte-identical by construction), replays its own WAL if one survives a
kill, then serves two doors:

  * the cross-node query transport (NodeQueryServer) — the coordinator
    scatter-gathers here, failing over between owners;
  * the replication door (ReplicationServer) — the coordinator's
    ReplicationManager fans live ingest slabs here (appended to this
    node's WAL before the ack), and a respawned peer catches up by
    streaming this node's WAL segments back out.

The node never self-ingests: all post-boot data arrives through the
replication door, which is exactly what makes "zero acked-ingest loss
through a SIGKILL" a provable property of the REPLICATION layer rather
than of scripted local writes.

Run: python bench/chaosnode.py --name A --port 7071 --repl-port 7171 \
         --shards 0,3 --wal-dir /tmp/chaosA [--platform cpu]
Prints one JSON line {"ready": true, ...} once serving.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# REPLACE the script-dir path entry (bench/) with the repo root: bench/
# contains a platform.py that would shadow the stdlib module jax needs
sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402


def build_shard_batch(shard: int, series: int, samples: int,
                      start_ms: int, step_ms: int):
    """The shard's deterministic base dataset — every owner builds the
    identical copy.  value = 5.0 * sample index + row."""
    from filodb_tpu.core.partkey import PartKey
    from filodb_tpu.core.records import RecordBatch
    from filodb_tpu.core.schemas import PROM_COUNTER
    keys = [PartKey.make("chaos_total",
                         {"_ws_": "chaos", "_ns_": f"s{shard}",
                          "instance": f"s{shard}-{i}"})
            for i in range(series)]
    part_idx = np.repeat(np.arange(series, dtype=np.int32), samples)
    ts = np.tile(start_ms
                 + np.arange(samples, dtype=np.int64) * step_ms, series)
    vals = (np.arange(samples, dtype=np.float64)[None, :] * 5.0
            + np.arange(series, dtype=np.float64)[:, None])
    return RecordBatch(PROM_COUNTER, keys, part_idx, ts,
                       {"count": vals.ravel()}), keys


def chaos_column(shard: int, series: int, tick: int, start_ms: int,
                 step_ms: int):
    """One fresh scrape column for a shard at `tick` — the coordinator
    fans these through the replication door."""
    col_ts = np.full((series, 1), start_ms + tick * step_ms, np.int64)
    col_v = (np.full((series, 1), tick * 5.0)
             + np.arange(series, dtype=np.float64)[:, None])
    return col_ts, col_v


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--name", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--repl-port", type=int, required=True)
    ap.add_argument("--shards", required=True,
                    help="comma-separated shard numbers this node owns "
                         "a copy of (primary or replica)")
    ap.add_argument("--dataset", default="chaos")
    ap.add_argument("--series", type=int, default=2048)
    ap.add_argument("--samples", type=int, default=420)
    ap.add_argument("--start-ms", type=int, default=1_600_000_000_000)
    ap.add_argument("--step-ms", type=int, default=10_000)
    ap.add_argument("--wal-dir", default="",
                    help="WAL root for this node ('' disables): appends "
                         "through the replication door become durable, "
                         "and a SIGKILL'd node replays them on respawn")
    ap.add_argument("--platform", default="cpu",
                    help="pin jax platform ('' keeps the default)")
    args = ap.parse_args(argv)

    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.parallel.transport import NodeQueryServer
    from filodb_tpu.replication import ReplicationServer
    from filodb_tpu.utils import metrics as _metrics

    _metrics.NODE_NAME = args.name
    shards = [int(s) for s in args.shards.split(",") if s != ""]
    S, T, step = args.series, args.samples, args.step_ms
    ms = TimeSeriesMemStore()
    warm_keys = {}
    for shard in shards:
        sh = ms.setup(args.dataset, shard)
        batch, keys = build_shard_batch(shard, S, T, args.start_ms, step)
        sh.ingest(batch)
        warm_keys[shard] = keys
    wals = {}
    replayed = 0
    if args.wal_dir:
        from filodb_tpu.wal import WalManager
        wal = WalManager(args.wal_dir, args.dataset)
        # a respawn after SIGKILL recovers everything the door acked
        # before the kill (the base dataset is deterministic; only door
        # appends live in the log)
        stats = wal.replay(ms)
        replayed = stats.records
        wals[args.dataset] = wal

    # warm the leaf query path BEFORE reporting ready: a restarted
    # node's first dispatched plan must answer within the probing
    # query's remaining deadline budget, not pay cold XLA compiles on
    # it (production nodes warm at boot via standalone warmup_shapes).
    from filodb_tpu.core.index import Equals
    from filodb_tpu.query.exec import (AggregateMapReduce,
                                       MultiSchemaPartitionsExec,
                                       PeriodicSamplesMapper)
    from filodb_tpu.query.rangevector import QueryContext
    q_start = (args.start_ms // 1000 + 600) * 1000
    q_end = args.start_ms + (T - 1) * step
    for shard in shards:
        warm = MultiSchemaPartitionsExec(
            QueryContext(), args.dataset, shard,
            [Equals("_metric_", "chaos_total")], args.start_ms, q_end)
        warm.add_transformer(PeriodicSamplesMapper(
            q_start, 60_000, q_end, 300_000, "rate", ()))
        warm.add_transformer(AggregateMapReduce("sum", (), ("_ns_",), ()))
        warm.execute_internal(ms)
    srv = NodeQueryServer(ms, port=args.port).start()
    rsrv = ReplicationServer(ms, node=args.name, wals=wals,
                             port=args.repl_port).start()
    print(json.dumps({"ready": True, "name": args.name,
                      "port": srv.address[1],
                      "repl_port": rsrv.address[1],
                      "shards": shards, "series": S, "samples": T,
                      "wal_replayed_records": replayed}), flush=True)
    # serve-only: every post-boot sample arrives through the
    # replication door until we are killed
    while True:
        time.sleep(1.0)


if __name__ == "__main__":
    main()
