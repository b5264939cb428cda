"""Long-running stress/soak harnesses, assertion-checked.

Ports of the reference's stress apps (ref: stress/src/main/scala/
filodb.stress/ — IngestionStress.scala, InMemoryQueryStress.scala): keep
the system under continuous load for minutes, verify invariants the unit
suite can't (stable RSS under churn, no correctness drift under sustained
concurrent ingest+query+flush), and print one JSON line per harness.

Opt-in (not part of the driver's bench):
    python -m bench.stress ingest --minutes 10
    python -m bench.stress query  --minutes 10
    python -m bench.stress all    --minutes 5
"""
from __future__ import annotations

import argparse
import json
import os
import threading
import time
from typing import List


def _rss_mb() -> float:
    with open("/proc/self/statm") as f:
        pages = int(f.read().split()[1])
    return pages * os.sysconf("SC_PAGE_SIZE") / (1 << 20)


def _emit(harness: str, ok: bool, **extra):
    print(json.dumps({"stress": harness, "ok": ok, **extra}), flush=True)


def _overlap_flags(sh):
    """(eviction_in_progress, mirror_rebuild_in_progress) for latency
    attribution: every recorded query latency is tagged with these so a
    tail outlier (like the round-5 soak's 752 s p99) is attributable to its
    overlapping maintenance window from the artifact alone."""
    evicting = bool(getattr(sh, "eviction_in_progress", False))
    rebuilding = any(
        getattr(getattr(st, "device_mirror", None), "rebuild_in_progress",
                False)
        for st in sh.stores.values())
    return evicting, rebuilding


def _flag_breakdown(lat, flags):
    """Per-overlap-category counts and percentiles from parallel lists of
    latencies and (evict, rebuild) flag tuples."""
    import numpy as np
    cats = {"clean": [], "evict_overlap": [], "rebuild_overlap": []}
    for dt, (ev, rb) in zip(lat, flags):
        if rb:
            cats["rebuild_overlap"].append(dt)
        elif ev:
            cats["evict_overlap"].append(dt)
        else:
            cats["clean"].append(dt)
    out = {}
    for name, vals in cats.items():
        out[name] = {"n": len(vals)}
        if vals:
            arr = np.asarray(vals)
            out[name]["p50_s"] = round(float(np.percentile(arr, 50)), 4)
            out[name]["p99_s"] = round(float(np.percentile(arr, 99)), 4)
            out[name]["max_s"] = round(float(arr.max()), 4)
    return out


def ingestion_stress(minutes: float, series: int = 5_000) -> bool:
    """Continuous ingest + background flush + memory enforcement; asserts
    zero drops/errors and a stable RSS after warm-up (the
    IngestionStress.scala shape: heavy + quick streams, verified counts)."""
    import numpy as np
    from filodb_tpu.core.flush import FlushScheduler
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import counter_batch
    from filodb_tpu.persist.localstore import (LocalDiskColumnStore,
                                               LocalDiskMetaStore)
    import tempfile
    tmp = tempfile.mkdtemp(prefix="filodb_stress_")
    ms = TimeSeriesMemStore(column_store=LocalDiskColumnStore(tmp),
                            meta_store=LocalDiskMetaStore(tmp))
    sh = ms.setup("stress", 0)
    sh.config.store.shard_mem_size = 256 << 20
    # small resident budget so every tier reaches steady state within the
    # soak window — the point is proving the plateaus hold, not sizing
    sh.resident.budget_bytes = 64 << 20
    sched = FlushScheduler(ms, "stress", interval_s=5.0).start()
    START = 1_600_000_000_000
    deadline = time.time() + minutes * 60
    t_idx = 0
    total = 0
    # The dense tier saw-tooths by design (fill until the headroom task
    # truncates), so raw RSS samples mix cycle phases.  Leak detection
    # compares SAME-PHASE marks: RSS at each post-enforcement trough.
    troughs: List[float] = []
    last_evictions = 0
    base = counter_batch(series, 1, start_ms=START)
    try:
        while time.time() < deadline:
            # 20 new samples per series per iteration, strictly in-order,
            # through the columnar grid path (shard.ingest_columns) — the
            # scrape-cycle shape needs no flatten/re-sort round trip
            n = 20
            ts2d = np.broadcast_to(
                START + (t_idx + np.arange(n, dtype=np.int64)) * 10_000,
                (series, n))
            vals = (t_idx + np.arange(n, dtype=np.float64))[None, :] \
                * 5.0 + np.arange(series)[:, None]
            total += sh.ingest_columns("prom-counter", base.part_keys,
                                       ts2d, {"count": vals}, offset=t_idx)
            t_idx += n
            if sh.stats.evictions > last_evictions:
                last_evictions = sh.stats.evictions
                troughs.append(_rss_mb())
    finally:
        sched.stop(final_flush=True)
    dropped = sh.stats.rows_dropped
    # Stable = the troughs stop climbing once tiers filled: compare the
    # last trough against the median of the middle third.
    stable = True
    if minutes >= 2 and len(troughs) >= 6:
        third = len(troughs) // 3
        mid = float(np.median(troughs[third:2 * third]))
        stable = troughs[-1] / max(mid, 1.0) < 1.2
    ok = (dropped == 0 and sched.errors == 0 and stable
          and total == series * t_idx)
    _emit("ingestion", ok, samples=total, dropped=int(dropped),
          flush_errors=sched.errors, rss_mb=round(_rss_mb(), 1),
          rss_stable=stable, evictions=sh.stats.evictions,
          trough_rss_mb=[round(t, 1) for t in troughs[-6:]])
    return ok


def _setup_live_ingest(series: int):
    """Shared scaffold for the query-under-ingest harnesses: a memstore
    warmed with 30min of deterministic counters (+5 per 10s per series)
    plus an ingester loop extending them live.  Returns
    (engine, ingester_fn, stop_event, ingested_counter); both harnesses'
    rate bound checks depend on the +5/10s invariant — change it here,
    not in a copy."""
    import numpy as np
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.core.records import RecordBatch
    from filodb_tpu.ingest.generator import counter_batch
    from filodb_tpu.query.engine import QueryEngine
    START = 1_600_000_000_000
    ms = TimeSeriesMemStore()
    sh = ms.setup("stress", 0)
    base = counter_batch(series, 1, start_ms=START)
    warm = 180
    ts = np.tile(START + np.arange(warm, dtype=np.int64) * 10_000, series)
    idx = np.repeat(np.arange(series, dtype=np.int32), warm)
    vals = np.arange(warm, dtype=np.float64)[None, :] * 5.0 \
        + np.arange(series)[:, None]
    sh.ingest(RecordBatch(base.schema, base.part_keys, idx, ts,
                          {"count": vals.ravel()}))
    stop = threading.Event()
    ingested = [0]

    def ingester():
        t_idx = warm
        while not stop.is_set():
            n = 10
            its = np.broadcast_to(
                START + (t_idx + np.arange(n, dtype=np.int64)) * 10_000,
                (series, n))
            ivals = (t_idx + np.arange(n, dtype=np.float64))[None, :] \
                * 5.0 + np.arange(series)[:, None]
            sh.ingest_columns("prom-counter", base.part_keys, its,
                              {"count": ivals})
            t_idx += n
            ingested[0] += n * series
            time.sleep(0.01)

    return QueryEngine("stress", ms), ingester, stop, ingested


def query_stress(minutes: float, series: int = 2_000,
                 query_threads: int = 4) -> bool:
    """Concurrent PromQL queries against live ingest for the duration;
    asserts every query succeeds and rates stay in the generator's bounds
    (InMemoryQueryStress.scala: parallel queries, verified results)."""
    import numpy as np
    from filodb_tpu.query.rangevector import PlannerParams
    pp = PlannerParams(sample_limit=200_000_000)
    eng, ingester, stop, _ = _setup_live_ingest(series)
    s = 1_600_000_000_000 // 1000
    deadline = time.time() + minutes * 60
    counts = [0] * query_threads
    errors: List[str] = []

    def querier(i):
        while time.time() < deadline and not errors:
            res = eng.query_range('sum by (_ns_)(rate(request_total[5m]))',
                                  s + 600, 60, s + 1700, pp)
            if res.error is not None:
                errors.append(res.error)
                return
            for _, _, vs in res.series():
                arr = np.asarray(vs)
                finite = arr[np.isfinite(arr)]
                # each series gains +5 per 10s -> rate 0.5/s; per _ns_
                # group of series/10 members the sum is bounded
                if finite.size and ((finite < 0).any()
                                    or (finite > series * 2.0).any()):
                    errors.append(f"rate out of bounds: {finite.min()}"
                                  f"..{finite.max()}")
                    return
            counts[i] += 1

    ing = threading.Thread(target=ingester, daemon=True)
    ing.start()
    threads = [threading.Thread(target=querier, args=(i,))
               for i in range(query_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    ing.join(timeout=10)
    ok = not errors and sum(counts) > 0
    _emit("query", ok, queries=sum(counts),
          qps=round(sum(counts) / max(minutes * 60, 1e-9), 1),
          errors=errors[:3], rss_mb=round(_rss_mb(), 1))
    return ok


def batch_query_stress(minutes: float, series: int = 2_000,
                       batch_threads: int = 2,
                       coalesce_threads: int = 3) -> bool:
    """Dashboard-batch machinery under live ingest for the duration:
    rotating panel sets through engine.query_range_batch AND single
    panels through the server-side coalescer (query/coalesce.py), every
    result verified, RSS tracked — the leak check for the r4 batch
    caches (merged gid matrices, panel groupings, coalescer groups)
    whose entries pin device arrays."""
    import numpy as np
    from filodb_tpu.query.coalesce import QueryCoalescer
    from filodb_tpu.query.rangevector import PlannerParams
    had_interp = os.environ.get("FILODB_TPU_FUSED_INTERPRET")
    os.environ["FILODB_TPU_FUSED_INTERPRET"] = "1"
    pp = PlannerParams(sample_limit=200_000_000)
    eng, ingester, stop, ingested = _setup_live_ingest(series)
    co = QueryCoalescer(eng, window_s=0.02)
    s0 = 1_600_000_000_000 // 1000
    args = (s0 + 600, 60, s0 + 1700)
    panel_sets = [
        ['sum(rate(request_total[5m])) by (_ns_)',
         'avg(rate(request_total[5m])) by (dc)',
         'sum(rate(request_total[5m])) by (dc)'],
        ['sum(rate(request_total[5m])) by (_ns_, dc)',
         'count(rate(request_total[5m])) by (_ns_)',
         'min(rate(request_total[5m])) by (dc)'],
        ['sum(rate(request_total[5m]))',
         'max(rate(request_total[5m])) by (_ns_)'],
    ]
    deadline = time.time() + minutes * 60
    counts = [0] * (batch_threads + coalesce_threads)
    errors: List[str] = []

    nonempty = [0]

    def check(res, q):
        if res.error is not None:
            errors.append(f"{q}: {res.error}")
            return False
        n = 0
        for _, _, vs in res.series():
            n += 1
            arr = np.asarray(vs)
            finite = arr[np.isfinite(arr)]
            if finite.size and (finite < -1e-6).any():
                errors.append(f"{q}: negative rate {finite.min()}")
                return False
        nonempty[0] += n > 0
        return True

    def batcher(i):
        k = 0
        while time.time() < deadline and not errors:
            panels = panel_sets[k % len(panel_sets)]
            k += 1
            for q, res in zip(panels,
                              eng.query_range_batch(panels, *args, pp)):
                if not check(res, q):
                    return
            counts[i] += 1

    def coalescer(i):
        k = 0
        while time.time() < deadline and not errors:
            q = panel_sets[0][k % 3]
            k += 1
            if not check(co.query_range(q, *args, pp), q):
                return
            counts[i] += 1

    rss0 = _rss_mb()
    ing = threading.Thread(target=ingester, daemon=True)
    ing.start()
    threads = [threading.Thread(target=batcher, args=(i,))
               for i in range(batch_threads)]
    threads += [threading.Thread(target=coalescer,
                                 args=(batch_threads + i,))
                for i in range(coalesce_threads)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        stop.set()
        if had_interp is None:
            os.environ.pop("FILODB_TPU_FUSED_INTERPRET", None)
        else:
            os.environ["FILODB_TPU_FUSED_INTERPRET"] = had_interp
    ing.join(timeout=10)
    # "every result verified" must not hold vacuously: a regression
    # returning zero series everywhere is a failure, not a pass
    ok = not errors and sum(counts) > 0 and nonempty[0] > 0
    # rss grows with the live-ingested working set; report the ingested
    # volume alongside so cache leaks are distinguishable from data
    _emit("batch", ok, rounds=sum(counts), errors=errors[:3],
          ingested_samples=ingested[0],
          ingested_mb=round(ingested[0] * 16 / 1e6, 1),
          rss_start_mb=round(rss0, 1), rss_mb=round(_rss_mb(), 1))
    return ok


def north_star_soak(minutes: float, series: int = 1_048_576,
                    report_path: str = "",
                    target_ingest_per_s: float = 2_200_000.0) -> bool:
    """The full pipeline at the BASELINE.md north-star scale for the whole
    soak window: 1M-series ingest -> scheduled flush -> memory enforcement
    (evict to the compressed resident tier / disk, ODP-able) -> CONCURRENT
    PromQL sum-by(rate) queries, with RSS troughs and query p50/p99
    tracked and leak/correctness assertions at the end (ref:
    stress/.../MemStoreStress.scala; VERDICT r3 item 8 — prove the
    memstore story at target scale even with the chip absent)."""
    import tempfile

    import numpy as np

    from filodb_tpu.core.flush import FlushScheduler
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import counter_batch
    from filodb_tpu.persist.localstore import (LocalDiskColumnStore,
                                               LocalDiskMetaStore)
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.query.rangevector import PlannerParams

    import sys

    def _phase(msg: str) -> None:
        # progress to STDERR: the stdout one-JSON-line contract stays
        # intact, and a wedged soak shows WHERE it wedged
        print(f"[soak +{time.time() - _soak_t0:.0f}s] {msg}",
              file=sys.stderr, flush=True)

    _soak_t0 = time.time()
    START = 1_600_000_000_000
    tmp = tempfile.mkdtemp(prefix="filodb_soak_")
    ms = TimeSeriesMemStore(column_store=LocalDiskColumnStore(tmp),
                            meta_store=LocalDiskMetaStore(tmp))
    sh = ms.setup("stress", 0)
    # budgets sized so every tier CYCLES within the window — the dense
    # store must overflow into enforcement (seal + evict to the resident
    # tier/disk) during the soak, not just grow; the device mirror is off
    # (re-mirroring 1M series per ingest generation would measure the
    # mirror, not the memstore)
    sh.config.store.shard_mem_size = 1 << 30
    sh.config.store.device_mirror_enabled = False
    sh.resident.budget_bytes = 256 << 20
    t0_build = time.time()
    base = counter_batch(series, 1, start_ms=START)
    build_s = time.time() - t0_build
    eng = QueryEngine("stress", ms)
    # the north-star query legitimately scans ~60M samples (1M series x a
    # 10-minute window): lift the default per-query caps for the soak
    pp = PlannerParams(sample_limit=2_000_000_000,
                       scan_limit=2_000_000_000)
    sched = FlushScheduler(ms, "stress", interval_s=20.0).start()

    stop = threading.Event()
    state = {"t_idx": 0, "ingested": 0, "iters": 0}
    lat: List[float] = []
    lat_flags: List[tuple] = []
    errors: List[str] = []
    troughs: List[float] = []
    last_evictions = 0
    s = START // 1000
    step_ms = 10_000

    def ingest_once():
        # columnar grid ingest: the scrape-cycle shape goes straight to
        # the SoA store as rectangular slice writes (shard.ingest_columns)
        t_idx = state["t_idx"]
        ts2d = np.broadcast_to(
            START + (t_idx + np.arange(2, dtype=np.int64)) * step_ms,
            (series, 2))
        vals = ((t_idx + np.arange(2, dtype=np.float64))[None, :] * 5.0
                + np.arange(series)[:, None])
        state["ingested"] += sh.ingest_columns(
            "prom-counter", base.part_keys, ts2d, {"count": vals},
            offset=t_idx)
        state["t_idx"] += 2
        state["iters"] += 1

    # ---- idle-p50 pre-phase: preload >600s of stream so the idle
    # queries cover the SAME 600s span the live loop's queries will
    # (a shorter preload would clamp lo to s+600 and compare unequal
    # workloads), no concurrent ingest — the under-ingest degradation
    # is then measured in-artifact against the same process/box
    # (round-5 verdict item 3)
    _phase(f"partkeys built in {build_s:.0f}s; preloading")
    for _ in range(65):
        ingest_once()
    _phase("preload done; idle queries")
    idle_lat: List[float] = []
    for _ in range(5):
        hi = s + state["t_idx"] * 10
        lo = max(s + 600, hi - 600)
        t0 = time.perf_counter()
        res = eng.query_range(
            'sum by (_ns_)(rate(request_total[5m]))', lo, 60, hi, pp)
        if res.error is not None:
            errors.append(res.error)
            break
        idle_lat.append(time.perf_counter() - t0)
        _phase(f"idle query {len(idle_lat)}: {idle_lat[-1]:.1f}s")
    idle_p50 = float(np.median(idle_lat)) if idle_lat else float("nan")

    # ---- ingest-only capacity: unpaced, no queries — the sustained
    # rate the pipeline itself supports.  On this 1-core box the
    # STEADY-STATE rate below divides the core with the query thread
    # and the flush encoder (a scheduling identity, not a pipeline
    # limit), so the capacity number is measured separately.
    cap_t0 = time.time()
    cap_n0 = state["ingested"]
    while time.time() - cap_t0 < 30 and not errors:
        ingest_once()
    ingest_only_rate = (state["ingested"] - cap_n0) \
        / max(time.time() - cap_t0, 1e-9)
    _phase(f"ingest-only capacity: {ingest_only_rate / 1e6:.2f}M/s; "
           f"starting {minutes:.1f}min soak window")

    def querier():
        # rate over the freshest 10 minutes of the stream, group-summed —
        # the headline shape against live data (absent windows before the
        # stream reaches 10m are fine; correctness bound checked below)
        while not stop.is_set() and not errors:
            hi = s + state["t_idx"] * 10
            lo = max(s + 600, hi - 600)
            if hi <= lo:
                time.sleep(1.0)
                continue
            f0 = _overlap_flags(sh)
            t0 = time.perf_counter()
            res = eng.query_range(
                'sum by (_ns_)(rate(request_total[5m]))', lo, 60, hi, pp)
            dt = time.perf_counter() - t0
            f1 = _overlap_flags(sh)
            if res.error is not None:
                errors.append(res.error)
                return
            lat.append(dt)
            lat_flags.append((f0[0] or f1[0], f0[1] or f1[1]))
            for _, _, vs in res.series():
                arr = np.asarray(vs)
                finite = arr[np.isfinite(arr)]
                # every series gains +5/10s => rate 0.5/s; group sums are
                # bounded by series * 0.5 with headroom for extrapolation
                if finite.size and ((finite < 0).any()
                                    or (finite > series).any()):
                    errors.append(
                        f"rate bound: {finite.min()}..{finite.max()}")
                    return
            time.sleep(0.5)

    qt = threading.Thread(target=querier, daemon=True)
    qt.start()
    # the soak window starts AFTER the pre-phase — preload + idle
    # queries must not silently eat the reported minutes
    deadline = time.time() + minutes * 60
    ingest_t0 = time.time()
    ingested0 = state["ingested"]
    try:
        while time.time() < deadline and not errors:
            # 2 new samples per series per iteration, in-order; PACED to
            # the target sustained rate (a scrape pipeline delivers on a
            # cadence — unpaced max-rate ingest would just measure one
            # core timeslicing two saturated threads)
            ingest_once()
            if sh.stats.evictions > last_evictions:
                last_evictions = sh.stats.evictions
                troughs.append(_rss_mb())
            if target_ingest_per_s > 0:
                ahead = (state["ingested"] - ingested0) \
                    / target_ingest_per_s - (time.time() - ingest_t0)
                if ahead > 0:
                    time.sleep(min(ahead, 5.0))
    finally:
        stop.set()
        qt.join(timeout=120)
        _phase("soak window done; final flush")
        sched.stop(final_flush=True)
        _phase("final flush done")
    ingest_wall_s = max(time.time() - ingest_t0, 1e-9)

    stable = True
    if len(troughs) >= 6:
        third = len(troughs) // 3
        mid = float(np.median(troughs[third:2 * third]))
        stable = troughs[-1] / max(mid, 1.0) < 1.2
    larr = np.asarray(lat) if lat else np.asarray([float("nan")])
    ok = (not errors and sh.stats.rows_dropped == 0 and sched.errors == 0
          and stable and len(lat) > 0
          and state["ingested"] == series * state["t_idx"])
    p50_under = float(np.nanpercentile(larr, 50))
    report = {
        "stress": "north_star_soak", "ok": ok, "series": series,
        "minutes": round(minutes, 1),
        "samples_ingested": state["ingested"],
        "samples_per_sec_ingest": round(
            (state["ingested"] - ingested0) / ingest_wall_s, 1),
        "ingest_only_samples_per_sec": round(ingest_only_rate, 1),
        "target_ingest_per_s": target_ingest_per_s,
        "dropped": int(sh.stats.rows_dropped),
        "flush_errors": sched.errors, "evictions": sh.stats.evictions,
        "chunks_flushed": sh.stats.chunks_flushed
        if hasattr(sh.stats, "chunks_flushed") else None,
        "queries": len(lat),
        "query_p50_idle_s": round(idle_p50, 3),
        "query_p50_s": round(p50_under, 3),
        "query_p99_s": round(float(np.nanpercentile(larr, 99)), 3),
        # overlap-tagged breakdown: tail outliers are attributable to
        # their eviction / mirror-rebuild window from the artifact alone
        "query_overlap_breakdown": _flag_breakdown(lat, lat_flags),
        "under_ingest_vs_idle": round(p50_under / idle_p50, 2)
        if idle_p50 and np.isfinite(idle_p50) else None,
        "cpu_cores": os.cpu_count(),
        "errors": errors[:3],
        "rss_mb": round(_rss_mb(), 1), "rss_stable": stable,
        "trough_rss_mb": [round(t, 1) for t in troughs[-8:]],
        "partkey_build_s": round(build_s, 1),
    }
    print(json.dumps(report), flush=True)
    if report_path:
        with open(report_path, "w") as f:
            json.dump(report, f, indent=1)
    return ok


def eviction_window_soak(minutes: float = 2.0, series: int = 20_000,
                         report_path: str = "SOAK_PR2_EVICT.json") -> bool:
    """Eviction-window soak (PR 2 acceptance): continuous frontend queries
    while memory enforcement repeatedly shifts store rows (shift_version
    bumps -> full DeviceMirror rebuilds).  Every latency is tagged with
    overlap flags, and the harness asserts STRUCTURALLY that no query
    thread ever ran a post-eviction full `_refresh` — queries must ride
    the host-gather fallback while the rebuild happens in the background
    (the round-5 soak's 752 s p99 was one query paying that rebuild
    inline)."""
    import numpy as np

    from filodb_tpu.core.devicecache import DeviceMirror
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import counter_batch
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.query.frontend import QueryFrontend
    from filodb_tpu.query.rangevector import PlannerParams
    from filodb_tpu.utils.metrics import registry

    START = 1_600_000_000_000
    ms = TimeSeriesMemStore()
    sh = ms.setup("stress", 0)
    base = counter_batch(series, 1, start_ms=START)
    warm = 240
    row_base = np.arange(series, dtype=np.float64)[:, None]

    def ingest_slab(t_idx, n):
        ts2d = np.broadcast_to(
            START + (t_idx + np.arange(n, dtype=np.int64)) * 10_000,
            (series, n))
        vals = (t_idx + np.arange(n, dtype=np.float64))[None, :] * 5.0 \
            + row_base
        sh.ingest_columns("prom-counter", base.part_keys, ts2d,
                          {"count": vals}, offset=t_idx)

    for t0 in range(0, warm, 60):
        ingest_slab(t0, min(60, warm - t0))
    # budget sized so enforcement fires repeatedly as the stream grows;
    # each enforcement truncates to the active tail = a shift_version bump
    budget = int(sum(s.nbytes for s in sh.stores.values()) * 0.75)
    tail_rows = warm // 2

    eng = QueryEngine("stress", ms)
    fe = QueryFrontend(eng)
    pp = PlannerParams(sample_limit=2_000_000_000, scan_limit=2_000_000_000)
    s = START // 1000
    stop = threading.Event()
    state = {"t_idx": warm}
    errors: List[str] = []
    lat: List[float] = []
    flags: List[tuple] = []

    # structural instrumentation: record which THREAD runs every full
    # mirror upload and whether it was the post-eviction (shift moved)
    # case — those must only ever run on mirror-rebuild threads
    refresh_calls: List[dict] = []
    orig_refresh = DeviceMirror._refresh

    def traced_refresh(self, store):
        snap = self._snap
        refresh_calls.append({
            "thread": threading.current_thread().name,
            "shift_moved": bool(snap is not None and
                                snap.shift_version != store.shift_version)})
        return orig_refresh(self, store)

    DeviceMirror._refresh = traced_refresh

    def ingester():
        while not stop.is_set():
            ingest_slab(state["t_idx"], 5)
            state["t_idx"] += 5
            time.sleep(0.05)

    def evictor():
        while not stop.is_set():
            time.sleep(8.0)
            try:
                sh.enforce_memory(budget, tail_rows)
            except Exception as e:  # noqa: BLE001 — soak must report it
                errors.append(f"evictor: {type(e).__name__}: {e}")
                return

    def querier():
        q = 'sum by (_ns_)(rate(request_total[5m]))'
        while not stop.is_set() and not errors:
            # step-aligned poll grid (Grafana aligns start/end to the
            # step): sliding re-polls share a window grid, so the result
            # cache serves the frozen prefix and computes only the tail
            hi = s + (state["t_idx"] * 10 // 60) * 60
            lo = max(s + 600, hi - 600)
            f0 = _overlap_flags(sh)
            t0 = time.perf_counter()
            res = fe.query_range(q, lo, 60, hi, pp)
            dt = time.perf_counter() - t0
            f1 = _overlap_flags(sh)
            if res.error is not None:
                errors.append(res.error)
                return
            lat.append(dt)
            flags.append((f0[0] or f1[0], f0[1] or f1[1]))
            time.sleep(0.1)

    fe.query_range('sum by (_ns_)(rate(request_total[5m]))',
                   s + 600, 60, s + warm * 10, pp)       # warm the mirror
    bg0 = registry.counter("device_mirror_bg_rebuilds").value
    fb0 = registry.counter("device_mirror_query_fallbacks").value
    threads = [threading.Thread(target=fn, daemon=True)
               for fn in (ingester, evictor, querier)]
    try:
        for t in threads:
            t.start()
        time.sleep(minutes * 60)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        DeviceMirror._refresh = orig_refresh

    bg_rebuilds = int(
        registry.counter("device_mirror_bg_rebuilds").value - bg0)
    fallbacks = int(
        registry.counter("device_mirror_query_fallbacks").value - fb0)
    # the acceptance invariant: every post-eviction full upload ran on a
    # background rebuild thread, never on a query's critical path
    inline_shift_refreshes = [
        c for c in refresh_calls
        if c["shift_moved"] and not c["thread"].startswith("mirror-rebuild")]
    larr = np.asarray(lat) if lat else np.asarray([float("nan")])
    ok = (not errors and len(lat) > 10 and bg_rebuilds >= 1
          and fallbacks >= 1 and not inline_shift_refreshes)
    report = {
        "stress": "eviction_window_soak", "ok": ok, "series": series,
        "minutes": round(minutes, 1), "queries": len(lat),
        "errors": errors[:3],
        "query_p50_s": round(float(np.nanpercentile(larr, 50)), 4),
        "query_p99_s": round(float(np.nanpercentile(larr, 99)), 4),
        "query_max_s": round(float(np.nanmax(larr)), 4),
        "query_overlap_breakdown": _flag_breakdown(lat, flags),
        "mirror_bg_rebuilds": bg_rebuilds,
        "mirror_query_fallbacks": fallbacks,
        "full_refresh_calls": len(refresh_calls),
        "inline_shift_refreshes": inline_shift_refreshes,
        "result_cache_invalidations": int(registry.counter(
            "query_result_cache_invalidations").value),
        "result_cache_partial_hits": int(registry.counter(
            "query_result_cache_partial_hits").value),
        "evictions": sh.stats.evictions,
        "rss_mb": round(_rss_mb(), 1),
        # every latency, tagged (ms, evict_overlap, rebuild_overlap):
        # tail outliers are attributable from the artifact alone
        "query_latencies_tagged": [
            [round(dt * 1000, 1), int(ev), int(rb)]
            for dt, (ev, rb) in zip(lat, flags)],
    }
    print(json.dumps({k: v for k, v in report.items()
                      if k != "query_latencies_tagged"}), flush=True)
    if report_path:
        with open(report_path, "w") as f:
            json.dump(report, f, indent=1)
    return ok


def main(argv=None):
    ap = argparse.ArgumentParser(description="filodb-tpu stress harnesses")
    ap.add_argument("harness",
                    choices=["ingest", "query", "batch", "soak", "evict",
                             "all"])
    ap.add_argument("--minutes", type=float, default=10.0)
    ap.add_argument("--series", type=int, default=1_048_576)
    ap.add_argument("--report", default="")
    ap.add_argument("--target-rate", type=float, default=2_200_000.0,
                    help="paced ingest samples/s for the soak (0 = max)")
    from bench.platform import add_platform_arg, apply_platform
    add_platform_arg(ap)
    args = ap.parse_args(argv)
    apply_platform(args)
    ok = True
    if args.harness in ("ingest", "all"):
        ok &= ingestion_stress(args.minutes)
    if args.harness in ("query", "all"):
        ok &= query_stress(args.minutes)
    if args.harness in ("batch", "all"):
        ok &= batch_query_stress(args.minutes)
    if args.harness == "soak":
        ok &= north_star_soak(args.minutes, series=args.series,
                              report_path=args.report,
                              target_ingest_per_s=args.target_rate)
    if args.harness == "evict":
        ok &= eviction_window_soak(
            args.minutes,
            series=args.series if args.series != 1_048_576 else 20_000,
            report_path=args.report or "SOAK_PR2_EVICT.json")
    raise SystemExit(0 if ok else 1)


if __name__ == "__main__":
    main()
