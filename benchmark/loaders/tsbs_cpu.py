"""Loader `tsbs_cpu`: TSBS devops, use case cpu-only: every host reports the
ten fields of `cpu` every interval, here as ten gauge metrics a host on one
shared timestamp row.  Generate -> reference -> ingest, a metric at a time
(`[hosts, T]` f64), through `shard.ingest_columns` routed to shards as the
gateway routes them, as `grid` does for one metric.

It asks the program ONE question first, as `grid_wide` does: a dense
`avg_over_time` leaf of the deployment's own shape, a row of `samples`
columns (4,736) under the query's 13 windows of an hour, at 1,024 groups (a
shard's hosts, a group each), through the program's own entry for one fused
leaf (`ops/pallas_fused.fused_rate_groupsum`).  A program whose kernel holds
that band whole declines it (five [4736, 128] matrices are 12.1 MB of the
chip's scoped vector memory) and serves the leaf from the general XLA path,
seconds a request: it is not running this deployment on the fused leaf, and
the run ends here, in seconds.  On the CPU (`--rehearse`) the kernel runs
interpreted, which lowers nothing and so turns nobody away.

A label's value is `prefix + str(i)`, `prefix + str(i mod m)` or
`values[i mod len(values)]` of the host's number i: a host's tags are fixed,
and the same in every run.
"""
import time

import numpy as np

ROWS = GROUPS = 1024        # a shard's share of the hosts, a group a host


def label_value(spec, i):
    if isinstance(spec, str):
        return spec
    if "values" in spec:
        return spec["values"][i % len(spec["values"])]
    return spec["prefix"] + str(i % spec["mod"] if "mod" in spec else i)


def require_fused_long_leaf(cfg, plan):
    import jax.numpy as jnp
    from filodb_tpu.ops import pallas_fused as pf
    interpret = pf.kernel_mode()
    if interpret is None:
        raise RuntimeError("this process may not run the fused kernel at all")
    T = cfg["samples"]
    ts_row = np.arange(T, dtype=np.int64) * cfg["scrape_ms"]
    end = ts_row[-1] - plan.phases[0] * 1000
    wends = end - np.arange(plan.n_windows, dtype=np.int64)[::-1] \
        * plan.step_s * 1000
    try:
        fused = pf.build_plan(ts_row, wends, plan.range_s * 1000)
        sums, _ = pf.fused_rate_groupsum(
            jnp.zeros((ROWS, T), jnp.float32), jnp.zeros((ROWS,), jnp.float32),
            np.arange(ROWS, dtype=np.int32) % GROUPS, fused, GROUPS,
            "avg_over_time", interpret=interpret)
        sums.block_until_ready()
    except Exception as e:      # noqa: BLE001 - whatever the program says
        raise RuntimeError(
            f"a dense avg_over_time leaf of {T} samples by {plan.n_windows} "
            f"windows at {GROUPS} groups is not fused by this program "
            f"({type(e).__name__}: {str(e)[:300]}): this deployment's "
            "queries must be answered by the fused leaf, not by the general "
            "XLA path") from e


def load(server, cfg, plan, seed, control, spans, find):
    """Returns (Reference, series per shard)."""
    require_fused_long_leaf(cfg, plan)
    from filodb_tpu.core.partkey import PartKey
    Reference = find("references", cfg["reference"]).Reference
    gen = find("generators", cfg["generator"])
    to_bf16 = find("loaders", "grid").to_bf16
    ds, T, metrics = cfg["dataset"], cfg["samples"], cfg["metrics"]
    hosts = cfg["series"] // len(metrics)
    mapper, spread = server.mappers[ds], server.spreads[ds]
    shards = server.memstore.shards_for(ds)
    ts_row = cfg["start_ms"] + np.arange(T, dtype=np.int64) * cfg["scrape_ms"]
    ref = Reference(ts_row, plan.window_ends_s() * 1000,
                    plan.range_s * 1000, plan.panels, hosts)
    per_shard = np.zeros(len(shards), np.int64)
    vbuf = np.empty((hosts, T))
    host_ids = np.arange(hosts)
    tags = [{lab: label_value(spec, i) for lab, spec in cfg["labels"].items()}
            for i in range(hosts)]
    for m, metric in enumerate(metrics):
        t0 = time.perf_counter()
        keys = [PartKey.make(metric, t) for t in tags]
        shard_of = np.fromiter(
            (mapper.ingestion_shard(pk.shard_key_hash(), pk.partition_hash(),
                                    spread.spread_for(pk.shard_key()))
             for pk in keys), np.int64, hosts)
        t1 = time.perf_counter()
        vals = gen.chunk(np.random.default_rng([seed, m]), vbuf)
        t2 = time.perf_counter()
        if ref.asks(metric):
            ref.add(metric, vals, host_ids)
        t3 = time.perf_counter()
        stored = to_bf16(vals) if control == "bf16" else vals
        for sh in shards:
            idx = np.flatnonzero(shard_of == sh.shard_num)
            if idx.size:
                got = sh.ingest_columns(
                    cfg["schema"], [keys[i] for i in idx],
                    np.broadcast_to(ts_row, (idx.size, T)),
                    {cfg["column"]: stored[idx]}, offset=m)
                if got != idx.size * T:
                    raise RuntimeError(f"ingested {got} of {idx.size * T}")
                per_shard[sh.shard_num] += idx.size
        t4 = time.perf_counter()
        spans["keys_and_routing"] += t1 - t0
        spans["generate"] += t2 - t1
        spans["reference"] += t3 - t2
        spans["ingest_columns"] += t4 - t3
    return ref, per_shard.tolist()
