"""Loader `churned_scrapes`: `scrape_offsets`' generate -> reference -> ingest
for a fleet that churns.  Every series lies at `start_ms + offset + k x
scrape_ms` as there, but holds only the samples of its target's life: every
`churn.period_samples` scrapes `churn.percent` % of the live targets are
replaced (the old series ends, a new one with another `instance` and another
scrape offset starts), and in every period `outages.percent` % of the live
targets miss a few scrapes in a row.  No two rows need hold the same count
of samples.

    load(server, cfg, plan, seed, control, spans, find) -> (ref, per_shard)

All of it follows from the seed: the values are the `counter` generator's
full `[n, samples]` rows of every series that ever lives, chunk `c` from
`default_rng([seed, c])` (the first `cfg["series"]` rows are
`scrape_offsets`' rows of that seed), the offsets `scrape_offsets`' stream
drawn for all of them, the replacements `default_rng([seed, (1 << 20) + 1])`
and the outages `default_rng([seed, (1 << 20) + 2])` (`lives`).  A sample
that does not exist is not ingested: rows go through `shard.ingest_columns`
in rectangles, the runs of existing samples that start and end at the same
scrapes together, a row's runs in the order of time.  The reference is the
configuration's (`references/churned_scrapes.py`), fed the mask of existing
samples beside values and offsets; `control == "bf16"` as `grid`.

Before it generates anything the loader asks the program one question, as
`scrape_offsets` asks one: whether a device mirror of four rows on one scrape
grid, one starting three scrapes late, one ending early and one with a
two-scrape hole, is fusable (`DeviceMirror.fused_eligible`).  A program that
says no answers every leaf of this deployment on the general XLA path,
seconds a request and half a minute of compile a shard shape (ROADMAP B3),
and is not serving it from the fused leaf: the run ends here, in seconds.
"""
import time

import numpy as np

REF_BLOCK = 64              # as scrape_offsets: [series, window ends] f64


def lives(seed, cfg):
    """Which samples exist, from the seed alone.  -> (target [N], first [N],
    end [N], holes [(series [H], start [H], length [H])]): series i holds
    target `target[i]` (targets are 0..cfg["series"]-1; a series' `_ns_` and
    `dc` are its target's) from scrape `first[i]` up to `end[i]`, less the
    scrapes of its holes.  N = series + (periods - 1) x replaced a period."""
    S, T = cfg["series"], cfg["samples"]
    P = cfg["churn"]["period_samples"]
    if T % P:
        raise ValueError("samples must be whole periods")
    periods = T // P
    n_rep = int(S * cfg["churn"]["percent"] / 100)
    n_out = int(S * cfg["outages"]["percent"] / 100)
    N = S + (periods - 1) * n_rep
    target = np.concatenate([np.arange(S), np.zeros(N - S, np.int64)])
    first, end = np.zeros(N, np.int64), np.full(N, T, np.int64)
    holder = np.arange(S)           # the series that holds each target now
    rep = np.random.default_rng([seed, (1 << 20) + 1])
    out = np.random.default_rng([seed, (1 << 20) + 2])
    lo, hi = cfg["outages"]["min_scrapes"], cfg["outages"]["max_scrapes"]
    holes = []
    for p in range(periods):
        if p:
            hit = rep.choice(S, n_rep, replace=False)
            new = S + (p - 1) * n_rep + np.arange(n_rep)
            end[holder[hit]] = first[new] = p * P
            target[new] = hit
            holder[hit] = new
        down = out.choice(S, n_out, replace=False)
        length = out.integers(lo, hi + 1, n_out)
        holes.append((holder[down], p * P + out.integers(0, P - length + 1),
                      length))
    return target, first, end, tuple(np.concatenate(h) for h in zip(*holes))


def existing(first, end, holes, lo, hi, T):
    """[hi - lo, T] bool: the samples of series lo..hi-1 that exist."""
    pos = np.arange(T)[None, :]
    mask = (pos >= first[lo:hi, None]) & (pos < end[lo:hi, None])
    series, start, length = holes
    for i in np.flatnonzero((series >= lo) & (series < hi)):
        mask[series[i] - lo, start[i]:start[i] + length[i]] = False
    return mask


def runs(mask):
    """(row [R], start [R], end [R]): every run of True of every row, a
    row's runs in the order of time."""
    edge = np.diff(np.pad(mask, ((0, 0), (1, 1))).astype(np.int8), axis=1)
    row, start = np.nonzero(edge == 1)
    return row, start, np.nonzero(edge == -1)[1]


def require_slot_placement(cfg):
    """Raise unless the program fuses rows that hold other counts of
    samples than one another on one scrape grid."""
    from filodb_tpu.core.blockstore import DenseSeriesStore
    from filodb_tpu.core.devicecache import DeviceMirror
    from filodb_tpu.core.schemas import DEFAULT_SCHEMAS
    step = cfg["scrape_ms"]
    store = DenseSeriesStore(DEFAULT_SCHEMAS[cfg["schema"]])
    off = [0, 1, step // 2, step - 1]
    held = np.ones((4, 12), bool)
    held[1, :3] = held[2, 9:] = held[3, 5:7] = False
    for r in range(4):
        k = np.flatnonzero(held[r])
        store.append_grid(
            np.array([store.new_row()]),
            (cfg["start_ms"] + off[r] + k * step)[None, :],
            {cfg["column"]: np.cumsum(np.ones((1, k.size)), axis=1)})
    mirror = DeviceMirror()
    if not mirror.ensure_fresh(store) or mirror.fused_eligible(
            cfg["column"], allow_ragged=True) is None:
        raise RuntimeError(
            "a device mirror of four rows on one scrape grid, one starting "
            "three scrapes late, one ending early, one with a two-scrape "
            "hole, is not fusable (DeviceMirror.fused_eligible says None): "
            "this program answers a store whose rows differ in their count "
            "of samples on the general path, not from the fused leaf, and "
            "does not serve this deployment")


def load(server, cfg, plan, seed, control, spans, find):
    """Generate, reference-evaluate and ingest the configuration's series,
    each at its own scrape offset and with the samples of its own life.
    Returns (Reference, series per shard)."""
    require_slot_placement(cfg)
    from filodb_tpu.core.partkey import PartKey
    grid = find("loaders", "grid")
    offsets = find("loaders", "scrape_offsets").scrape_offsets
    Reference = find("references", cfg["reference"]).Reference
    gen = find("generators", cfg["generator"])
    ds, T = cfg["dataset"], cfg["samples"]
    mapper, spread = server.mappers[ds], server.spreads[ds]
    shards = server.memstore.shards_for(ds)
    ts_row = cfg["start_ms"] + np.arange(T, dtype=np.int64) * cfg["scrape_ms"]
    target, first, end, holes = lives(seed, cfg)
    N = len(target)
    phase = offsets(seed, cfg["scrape_ms"], N)
    num_base = plan.num_base()
    ref = Reference(ts_row, plan.window_ends_s() * 1000,
                    plan.range_s * 1000, plan.panels, num_base)
    per_shard = np.zeros(len(shards), np.int64)
    vbuf = np.empty((min(grid.GEN_CHUNK, N), T))
    for c, lo in enumerate(range(0, N, grid.GEN_CHUNK)):
        hi = min(lo + grid.GEN_CHUNK, N)
        n = hi - lo
        t0 = time.perf_counter()
        # a label that repeats (`mod`) is the target's, the others the series'
        keys = [PartKey.make(cfg["metric"], {
            lab: grid.label_value(spec, target[i] if isinstance(spec, dict)
                                  and "mod" in spec else i)
            for lab, spec in cfg["labels"].items()}) for i in range(lo, hi)]
        shard_of = np.fromiter(
            (mapper.ingestion_shard(pk.shard_key_hash(), pk.partition_hash(),
                                    spread.spread_for(pk.shard_key()))
             for pk in keys), np.int64, n)
        t1 = time.perf_counter()
        vals = gen.chunk(np.random.default_rng([seed, c]), vbuf[:n])
        mask = existing(first, end, holes, lo, hi, T)
        t2 = time.perf_counter()
        for b in range(0, n, REF_BLOCK):
            e = min(b + REF_BLOCK, n)
            ref.add(vals[b:e], target[lo + b:lo + e] % num_base,
                    phase[lo + b:lo + e], mask[b:e])
        t3 = time.perf_counter()
        stored = grid.to_bf16(vals) if control == "bf16" else vals
        # rectangles: the runs that start and end at the same scrapes, in
        # the order of their starts (a row's later run starts later)
        row, a, b = runs(mask)
        order = np.lexsort((row, b, a))
        row, a, b = row[order], a[order], b[order]
        cut = np.flatnonzero(np.diff(a, prepend=-1) | np.diff(b, prepend=-1))
        for sh in shards:
            per_shard[sh.shard_num] += int((shard_of == sh.shard_num).sum())
        for i, j in zip(cut, np.append(cut[1:], len(row))):
            rows, a0, b0 = row[i:j], int(a[i]), int(b[i])
            for sh in shards:
                idx = rows[shard_of[rows] == sh.shard_num]
                if not idx.size:
                    continue
                got = sh.ingest_columns(
                    cfg["schema"], [keys[r] for r in idx],
                    ts_row[None, a0:b0] + phase[lo + idx, None],
                    {cfg["column"]: stored[idx, a0:b0]}, offset=c)
                if got != idx.size * (b0 - a0):
                    raise RuntimeError(
                        f"ingested {got} of {idx.size * (b0 - a0)}")
        t4 = time.perf_counter()
        spans["keys_and_routing"] += t1 - t0
        spans["generate"] += t2 - t1
        spans["reference"] += t3 - t2
        spans["ingest_columns"] += t4 - t3
    return ref, per_shard.tolist()
