"""Elastic-read bench node: one STATELESS query-only OS process.

Spawned by `python -m bench.drills objectstore`: it owns NO shards and holds
NO local data — its entire serving state is a mounted manifest snapshot
over the shared object store (persist/objectstore.py make_query_tier)
plus a cold cache.  The coordinator scatter-gathers cold leaves here
via the ordinary cross-node transport; decoded leaves rebind to the
object-store tier through the per-process query-tier registry, so
adding one of these processes adds cold read capacity with zero data
movement — the elastic-read property the stage gates on.

Run: python bench/coldnode.py --name q1 --port 7071 \
         --objstore /tmp/shared --dataset coldbench --num-shards 4
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


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--name", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--objstore", required=True,
                    help="shared object-store root (LocalObjectStore)")
    ap.add_argument("--dataset", default="coldbench")
    ap.add_argument("--num-shards", type=int, default=4)
    ap.add_argument("--platform", default="cpu",
                    help="pin jax platform ('' keeps the default)")
    args = ap.parse_args(argv)

    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.parallel.transport import NodeQueryServer
    from filodb_tpu.persist.objectstore import (LocalObjectStore,
                                                make_query_tier)
    from filodb_tpu.utils import metrics as _metrics

    _metrics.NODE_NAME = args.name
    store = LocalObjectStore(args.objstore, name=args.name)
    # mounts the manifests and registers the tier for the dataset: every
    # cold leaf dispatched here pages the SHARED tier, nothing local
    tier, remote = make_query_tier(store, args.dataset, args.num_shards)
    ms = TimeSeriesMemStore()            # empty: query-only by contract
    srv = NodeQueryServer(ms, port=args.port).start()
    print(json.dumps({"ready": True, "name": args.name,
                      "port": srv.address[1],
                      "manifest_entries":
                          sum(len(remote.list(args.dataset, s))
                              for s in range(args.num_shards))}),
          flush=True)
    # serve-only until the bench kills us
    while True:
        time.sleep(1.0)


if __name__ == "__main__":
    main()
