"""Loader `hist_grid`: as `grid` (one shared timestamp row, the gateway's
routing, the same spans), for a native-histogram schema: one `[n, T, B]` f64
bucket column `h` with `sum` and `count` beside it, ingested through
`shard.ingest_columns(..., bucket_les=...)`.  `control == "bf16"` stores the
buckets rounded to bfloat16; the reference keeps the unrounded ones.

Before it generates anything the loader asks the program where it would send
this deployment's leaves (`query/leafexec.leaf_route`: estimated samples,
values per sample, the cap -> "host" | "device").  A leaf of `series / shards`
rows at the configuration's own size (`leaf_series_at_size`: a rehearsal cuts
`series`, not the question) over the traffic's span must gather from the
device mirror: a program that sends it to the host, or has no such function,
is not running this deployment, and the run ends here instead of serving
multi-second requests.
"""
import concurrent.futures
import time

import numpy as np

GEN_CHUNK = 1024            # series generated and ingested at once (377 MB)
REF_BLOCK = 32              # ... and handed to the reference at once
REF_THREADS = 6             # ... by this many threads (13 cores a one-chip machine)


def leaf_samples(cfg, plan):
    """Samples one shard leaf of a request scans: its rows over the span
    and the first window's range."""
    per_series = (plan.span_s + plan.range_s) * 1000 // cfg["scrape_ms"] + 1
    return cfg["leaf_series_at_size"] * per_series


def require_device_route(cfg, plan):
    from filodb_tpu.config import settings
    try:
        from filodb_tpu.query.leafexec import leaf_route
    except ImportError as e:
        raise RuntimeError(
            "this program has no query/leafexec.leaf_route: it routes a "
            "leaf by samples, not values, and sends this deployment's "
            f"{cfg['buckets']}-bucket leaves to the host") from e
    cap = settings().query.host_route_max_samples
    est = leaf_samples(cfg, plan)
    route = leaf_route(est, cfg["buckets"], cap)
    if route != "device":
        raise RuntimeError(
            f"leaf_route({est}, {cfg['buckets']}, {cap}) says {route!r}: "
            "this deployment's leaves must gather from the device mirror")


def load(server, cfg, plan, seed, control, spans, find):
    """Generate, reference-evaluate and ingest the configuration's series.
    Returns (Reference, series per shard)."""
    require_device_route(cfg, plan)
    from filodb_tpu.core.partkey import PartKey
    grid = find("loaders", "grid")
    Reference = grid.reference_module(cfg, find).Reference
    gen = find("generators", cfg["generator"])
    ds, S, T, B = cfg["dataset"], cfg["series"], cfg["samples"], cfg["buckets"]
    scheme = cfg["bucket_scheme"]
    les = scheme["first_le"] * scheme["factor"] ** np.arange(B)
    ns_mod = cfg["labels"]["_ns_"]["mod"]
    mapper, spread = server.mappers[ds], server.spreads[ds]
    shards = server.memstore.shards_for(ds)
    ts_row = cfg["start_ms"] + np.arange(T, dtype=np.int64) * cfg["scrape_ms"]
    num_base = plan.num_base()
    ref = Reference(ts_row, plan.window_ends_s() * 1000,
                    plan.range_s * 1000, plan.panels, num_base, les)
    per_shard = np.zeros(len(shards), np.int64)
    hbuf = np.empty((min(GEN_CHUNK, S), T, B))
    for c, lo in enumerate(range(0, S, GEN_CHUNK)):
        hi = min(lo + GEN_CHUNK, S)
        n = hi - lo
        t0 = time.perf_counter()
        keys = [PartKey.make(cfg["metric"], {
            lab: grid.label_value(spec, i)
            for lab, spec in cfg["labels"].items()}) for i in range(lo, hi)]
        shard_of = np.fromiter(
            (mapper.ingestion_shard(pk.shard_key_hash(), pk.partition_hash(),
                                    spread.spread_for(pk.shard_key()))
             for pk in keys), np.int64, n)
        t1 = time.perf_counter()
        ids = np.arange(lo, hi)
        h = gen.chunk(np.random.default_rng([seed, c]), hbuf[:n],
                      ids % ns_mod)
        total, count = gen.sum_and_count(h, les)
        t2 = time.perf_counter()
        # the blocks side by side (NumPy releases the interpreter lock),
        # added in their own order: the same sums from the same seed
        with concurrent.futures.ThreadPoolExecutor(REF_THREADS) as pool:
            for part in pool.map(
                    lambda b: ref.increase_by_base(
                        h[b:b + REF_BLOCK], ids[b:b + REF_BLOCK] % num_base),
                    range(0, n, REF_BLOCK)):
                ref.accumulate(part)
        t3 = time.perf_counter()
        stored = grid.to_bf16(h) if control == "bf16" else h
        for sh in shards:
            idx = np.flatnonzero(shard_of == sh.shard_num)
            if idx.size:
                got = sh.ingest_columns(
                    cfg["schema"], [keys[i] for i in idx],
                    np.broadcast_to(ts_row, (idx.size, T)),
                    {"sum": total[idx], "count": count[idx],
                     cfg["column"]: stored[idx]},
                    offset=c, bucket_les=les)
                if got != idx.size * T:
                    raise RuntimeError(f"ingested {got} of {idx.size * T}")
                per_shard[sh.shard_num] += idx.size
        t4 = time.perf_counter()
        spans["keys_and_routing"] += t1 - t0
        spans["generate"] += t2 - t1
        spans["reference"] += t3 - t2
        spans["ingest_columns"] += t4 - t3
    return ref, per_shard.tolist()
