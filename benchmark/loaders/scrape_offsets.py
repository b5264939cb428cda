"""Loader `scrape_offsets`: `grid`'s generate -> reference -> ingest for
series that Prometheus scraped: every target (here one a series) has an
offset of a whole number of milliseconds inside the scrape interval, and its
samples lie at `start_ms + offset + k x scrape_ms`.  No two rows need share a
timestamp row.

    load(server, cfg, plan, seed, control, spans, find) -> (ref, per_shard)

The offsets come from a stream of their own, `default_rng([seed, 1 << 20])`,
apart from the chunks' `[seed, c]`: the same for every run of a seed, and
the data of a seed are `grid`'s data of that seed.  The reference is the
configuration's (`references/scrape_offsets.py`), fed the offsets beside the
values; `control == "bf16"` as `grid`.

Before it generates anything the loader asks the program one question, as
`grid_on_mirror` asks one: whether a device mirror whose rows lie on one
scrape grid, each behind it by an offset of its own, is fusable
(`DeviceMirror.fused_eligible`).  A program that says no would answer every
leaf of this deployment on the general XLA path, seconds a request and half a
minute of compile a shard shape (ROADMAP B3), and is not serving this
deployment from the fused path: the run ends here, in seconds.
"""
import time

import numpy as np


# Series handed to the reference at once.  Its quantities are [series,
# window ends] f64 (1,431 ends: 11 KB a series a temporary), so `grid`'s 256
# rows leave the cache: 64 ran 2.1 times as fast (56 -> 26 s for 262,144
# series on the CPU, PR 37).
REF_BLOCK = 64


def scrape_offsets(seed, scrape_ms, series):
    """One whole-millisecond offset in [0, scrape_ms) a series, from the
    seed alone."""
    return np.random.default_rng([seed, 1 << 20]).integers(
        0, scrape_ms, series)


def require_phase_grid(cfg):
    """Raise unless the program fuses rows that differ by scrape offsets."""
    from filodb_tpu.core.blockstore import DenseSeriesStore
    from filodb_tpu.core.devicecache import DeviceMirror
    from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
    step = cfg["scrape_ms"]
    store = DenseSeriesStore(DEFAULT_SCHEMAS[cfg["schema"]])
    rows = np.array([store.new_row() for _ in range(4)], dtype=np.int64)
    off = np.array([0, 1, step // 2, step - 1])
    ts = cfg["start_ms"] + off[:, None] + np.arange(8)[None, :] * step
    store.append_grid(rows, ts, {cfg["column"]: np.cumsum(
        np.ones(ts.shape), axis=1)})
    mirror = DeviceMirror()
    if not mirror.ensure_fresh(store) \
            or mirror.fused_eligible(cfg["column"]) is None:
        raise RuntimeError(
            "a device mirror of four rows at scrape offsets "
            f"{off.tolist()} ms of {step} is not fusable "
            "(DeviceMirror.fused_eligible says None): this program answers "
            "per-target scrape offsets on the general path, not from the "
            "fused leaf, and does not serve this deployment")


def load(server, cfg, plan, seed, control, spans, find):
    """Generate, reference-evaluate and ingest the configuration's series,
    each at its own scrape offset.  Returns (Reference, series per shard)."""
    require_phase_grid(cfg)
    from filodb_tpu.core.partkey import PartKey
    grid = find("loaders", "grid")
    Reference = find("references", cfg["reference"]).Reference
    gen = find("generators", cfg["generator"])
    ds, S, T = cfg["dataset"], cfg["series"], cfg["samples"]
    mapper, spread = server.mappers[ds], server.spreads[ds]
    shards = server.memstore.shards_for(ds)
    ts_row = cfg["start_ms"] + np.arange(T, dtype=np.int64) * cfg["scrape_ms"]
    phase = scrape_offsets(seed, cfg["scrape_ms"], S)
    num_base = plan.num_base()
    ref = Reference(ts_row, plan.window_ends_s() * 1000,
                    plan.range_s * 1000, plan.panels, num_base)
    per_shard = np.zeros(len(shards), np.int64)
    vbuf = np.empty((min(grid.GEN_CHUNK, S), T))
    for c, lo in enumerate(range(0, S, grid.GEN_CHUNK)):
        hi = min(lo + grid.GEN_CHUNK, S)
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
        vals = gen.chunk(np.random.default_rng([seed, c]), vbuf[:n])
        t2 = time.perf_counter()
        for b in range(0, n, REF_BLOCK):
            e = min(b + REF_BLOCK, n)
            ref.add(vals[b:e], np.arange(lo + b, lo + e) % num_base,
                    phase[lo + b:lo + e])
        t3 = time.perf_counter()
        stored = grid.to_bf16(vals) if control == "bf16" else vals
        for sh in shards:
            idx = np.flatnonzero(shard_of == sh.shard_num)
            if idx.size:
                got = sh.ingest_columns(
                    cfg["schema"], [keys[i] for i in idx],
                    ts_row[None, :] + phase[lo + idx, None],
                    {cfg["column"]: stored[idx]}, offset=c)
                if got != idx.size * T:
                    raise RuntimeError(f"ingested {got} of {idx.size * T}")
                per_shard[sh.shard_num] += idx.size
        t4 = time.perf_counter()
        spans["keys_and_routing"] += t1 - t0
        spans["generate"] += t2 - t1
        spans["reference"] += t3 - t2
        spans["ingest_columns"] += t4 - t3
    return ref, per_shard.tolist()
