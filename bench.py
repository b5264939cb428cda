"""Headline benchmark: PromQL samples/sec scanned on sum by (rate[5m]).

Mirrors the reference's QueryInMemoryBenchmark workload shape
(ref: jmh/src/main/scala/filodb.jmh/QueryInMemoryBenchmark.scala:31-35,
126-133 — Prom-schema counters, 720 samples @10s, 5m rate windows, sum
aggregation) at the BASELINE.json north-star scale: the headline config is
1,048,576 series x 720 samples (f32 values ~2.9 GB, chip-resident), with a
262,144-series stage first so an interrupted run still leaves evidence
behind.

Accounting is conservative: "samples scanned" counts every stored sample in
the queried span ONCE (S * samples_in_span), not once per overlapping window
the way the JVM SlidingWindowIterator would touch them — so the number is a
lower bound on iterator-equivalent throughput.

vs_baseline compares against the same algorithm implemented in vectorized
NumPy on host CPU (the strongest portable CPU stand-in we can run here; the
reference publishes no absolute numbers — see BASELINE.md). A second,
per-window loop baseline ("iterator") mimicking ChunkedWindowIterator's
per-window access pattern is reported as an extra field.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Robustness: backend init can fail or hang, and a run can die BETWEEN
stages.  Two defenses:
  - the default invocation runs as a SUPERVISOR that stays off jax and
    executes the measurement in a child process under a hard timeout,
    retrying once.  No chip, no number: without an accelerator it exits
    non-zero; a CPU run happens only on an explicit `--platform cpu`;
  - the worker persists EVERY completed stage incrementally to
    BENCH_PARTIAL.json (atomic rename), so a run that wedges midway
    still leaves evidence; the supervisor recovers those stages into
    the final line (`"partial": true`) when the worker dies.
"""
import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

REPO_DIR = os.path.dirname(os.path.abspath(__file__))
PARTIAL_PATH = os.environ.get(
    "FILODB_BENCH_PARTIAL", os.path.join(REPO_DIR, "BENCH_PARTIAL.json"))

# FLOP/byte model for the fused kernel (see doc/kernels.md): since round
# 5 the boundary selections are exact per-tile gathers (data movement, 0
# model FLOPs); the matmul work is the [Gp,BS]x[BS,Wp] group epilogue.
# The legacy matmul-selection path (FILODB_FUSED_GATHER=0) adds 2 (dense
# precorrected) selection matmuls.
_FUSED_MATMULS = (0 if os.environ.get("FILODB_FUSED_GATHER", "1") != "0"
                  else 2)


def make_counter_data(S, T, step_ms=10_000, seed=7):
    rng = np.random.default_rng(seed)
    ts_row = np.arange(T, dtype=np.int64) * step_ms
    vals = np.cumsum(rng.exponential(10.0, size=(S, T)).astype(np.float32),
                     axis=1)
    return ts_row, vals


def numpy_vectorized_baseline(ts_row, vals, gids, G, wends, range_ms):
    """Same algorithm as the device kernel, vectorized NumPy on host: window
    is samples in [wend-range+1, wend] and the rate uses full Prometheus
    extrapolation with the counter-zero clamp (semantics of ref:
    query/.../rangefn/RateFunctions.scala:37-76 extrapolatedRate), so in f64
    this doubles as the conformance oracle for the f32 device result."""
    lo = np.searchsorted(ts_row, wends - range_ms + 1, side="left")
    hi = np.searchsorted(ts_row, wends, side="right") - 1
    n = hi - lo + 1
    ok = n >= 2
    lo_c = np.minimum(lo, len(ts_row) - 1)
    t1 = ts_row[lo_c].astype(np.float64)
    t2 = ts_row[hi].astype(np.float64)                 # [W]
    v1 = vals[:, lo_c].astype(np.float64)
    v2 = vals[:, hi].astype(np.float64)                # [S, W]
    wstart = (wends - range_ms).astype(np.float64)
    wend = wends.astype(np.float64)
    dur_start = (t1 - wstart) / 1000.0
    dur_end = (wend - t2) / 1000.0
    sampled = (t2 - t1) / 1000.0
    avg = sampled / np.maximum(n - 1, 1)
    delta = v2 - v1
    with np.errstate(invalid="ignore", divide="ignore"):
        dur_zero = sampled * (v1 / delta)              # counter hit 0 here
        ds = np.where((delta > 0) & (v1 >= 0) & (dur_zero < dur_start),
                      dur_zero, dur_start)
        threshold = avg * 1.1
        extrap = (sampled + np.where(ds < threshold, ds, avg / 2)
                  + np.where(dur_end < threshold, dur_end, avg / 2))
        rate = delta * (extrap / sampled) / (wend - wstart) * 1000.0
    rate = np.where(ok & (sampled > 0), rate, np.nan)
    out = np.zeros((G, rate.shape[1]))
    np.add.at(out, gids, np.nan_to_num(rate))
    return out


def numpy_iterator_baseline(ts_row, vals, wends, range_ms):
    """Per-(series,window) loop mimicking ChunkedWindowIterator's access
    pattern (ref: query/.../exec/PeriodicSamplesMapper.scala:202-292)."""
    S = vals.shape[0]
    out = np.empty((S, len(wends)))
    for s in range(S):
        row_v = vals[s]
        for wi, wend in enumerate(wends):
            lo = np.searchsorted(ts_row, wend - range_ms, side="left")
            hi = np.searchsorted(ts_row, wend, side="right")
            if hi - lo < 2:
                out[s, wi] = np.nan
                continue
            t1, t2 = ts_row[lo], ts_row[hi - 1]
            out[s, wi] = ((row_v[hi - 1] - row_v[lo]) / (t2 - t1) * 1000.0
                          if t2 > t1 else np.nan)
    return out


class PartialWriter:
    """Atomic incremental persistence of completed bench stages."""

    def __init__(self, run_id, platform):
        self.doc = {"run_id": run_id, "platform": platform,
                    "started_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                 time.gmtime()),
                    "stages": {}, "done": False}
        self.flush()

    def stage(self, name, data):
        self.doc["stages"][name] = data
        self.flush()

    def finish(self):
        self.doc["done"] = True
        self.flush()

    def flush(self):
        self.doc["updated_utc"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                time.gmtime())
        tmp = PARTIAL_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.doc, f, indent=1)
        os.replace(tmp, PARTIAL_PATH)


def run_pallas_fused(ts_row, vals_dev, vbase32, gids, wends, range_ms, G,
                     xla_res, iters):
    """Time ops/pallas_fused for one config; cross-check against the XLA
    result when available.  Returns (p50_seconds, max_rel_err) where the
    error is inf when the NaN patterns disagree, and None when xla_res is
    None (conformance then comes from a smaller stage).  Values arrive
    host-precorrected + rebased (leaf-path parity), so the kernel runs
    with_drops=False — the same configuration the leaf exec uses."""
    from filodb_tpu.ops import pallas_fused as pf
    plan = pf.build_plan(ts_row, np.asarray(wends, np.int64), range_ms)
    prep = pf.pad_inputs(vals_dev, vbase32, gids, plan, G)

    def fused_query():
        sums, counts = pf.fused_rate_groupsum(
            None, None, None, plan, G, "rate", True, prepared=prep)
        return pf.present_sum(sums, counts)

    got = fused_query()                               # compile + warm
    if xla_res is None:
        err = None
    elif (np.isnan(got) != np.isnan(xla_res)).any():
        err = float("inf")
    else:
        err = float(np.nanmax(
            np.abs(got - xla_res) / np.maximum(np.abs(xla_res), 1e-6)))
    lat = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fused_query()
        lat.append(time.perf_counter() - t0)
    return float(np.median(np.asarray(lat))), err


CONFORMANCE_SERIES_CAP = 262_144


def cpu_f64_conformance(stage, xla_res, ts_row, vals, gids, G, wends,
                        range_ms):
    """Self-certify a CPU stage: cross-check the XLA f32 result against the
    same algorithm in f64 NumPy (round-3 verdict weak #3 — the artifact must
    carry an in-run correctness certificate even on the CPU fallback).
    Callers cap the series count (CONFORMANCE_SERIES_CAP) so the f64
    temporaries (~8 [S,W] arrays) can't OOM a smaller fallback host; vals
    stays f32 here — the oracle casts only the gathered [S,W] columns."""
    ref = numpy_vectorized_baseline(ts_row, vals, gids,
                                    G, wends.astype(np.int64), range_ms)
    got = np.nan_to_num(np.asarray(xla_res, np.float64))
    err = float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref), 1e-6)))
    stage["xla_max_rel_err_vs_f64"] = round(err, 9)
    if vals.shape[0] != stage["series"]:
        stage["conformance_series"] = vals.shape[0]
    return err < 1e-3


def measure_stage(S, T, iters, platform, do_fused, persist,
                  prior_conformance_ok=False):
    """One bench configuration end-to-end; returns the stage dict.
    `persist(partial_dict)` is called at every sub-milestone so a death
    mid-stage still leaves the finished sub-measurements behind."""
    import jax
    from filodb_tpu.ops.rangefns import evaluate_range_function
    from filodb_tpu.ops import agg as agg_ops
    from filodb_tpu.ops.timewindow import to_offsets, make_window_ends

    G = min(1000, S)
    range_ms, step_ms = 300_000, 60_000      # rate[5m], 1m steps
    stage = {"series": S, "samples_per_series": T, "groups": G}

    ts_row, vals = make_counter_data(S, T)
    # leaf-path parity (r4): counters are reset-corrected + rebased in f64
    # ON THE HOST once per working set — the DeviceMirror does exactly this
    # at upload (core/devicecache.py refresh; ops/counter.rebase_values),
    # so steady-state queries must NOT pay a per-query correction scan.
    # Round 2/3 benches ran precorrected=False and the scan was ~90% of
    # CPU query time (see doc/kernels.md, BENCH_TREND.json).
    t0 = time.perf_counter()
    # make_counter_data is monotone by construction, so the f64 reset
    # correction (ops/counter.host_counter_correct) is the identity —
    # only the f64 rebase matters for f32 delta exactness.  Chunked so
    # the 1M-series stage doesn't materialize ~30 GB of f64 temporaries
    # (the full rebase_values took 500s host-side at 1M x 720).
    vbase64 = vals[:, 0].astype(np.float64)
    vals32 = np.empty_like(vals, dtype=np.float32)
    for i in range(0, S, 65_536):
        j = min(i + 65_536, S)
        vals32[i:j] = (vals[i:j].astype(np.float64)
                       - vbase64[i:j, None]).astype(np.float32)
    vbase32 = vbase64.astype(np.float32)
    stage["host_prep_s"] = round(time.perf_counter() - t0, 2)
    # shared scrape grid: ship ONE [1, T] offset row and let it broadcast
    # (exact for every range fn — saves S*T*4 bytes of HBM at 1M series)
    ts_one = to_offsets(ts_row[None, :], np.full(1, T), 0)
    gids = (np.arange(S) % G).astype(np.int32)
    qstart = 600_000
    qend = int(ts_row[-1])
    wends = make_window_ends(qstart, qend, step_ms).astype(np.int32)
    stage["windows"] = W = len(wends)
    span_lo = np.searchsorted(ts_row, qstart - range_ms)
    span_hi = np.searchsorted(ts_row, qend, side="right")
    scanned = S * int(span_hi - span_lo)
    stage["samples_scanned_per_query"] = scanned
    value_bytes = S * T * 4

    dev_ts = jax.device_put(ts_one)
    dev_vals = jax.device_put(vals32)
    dev_vbase = jax.device_put(vbase32)
    dev_gids = jax.device_put(gids)
    dev_wends = jax.device_put(wends)

    @jax.jit
    def query(ts_off, v, vb, g, w):
        res = evaluate_range_function(ts_off, v, w, range_ms, "rate",
                                      shared_grid=True, vbase=vb,
                                      precorrected=True)
        return agg_ops.aggregate("sum", res, g, G)

    xla_res = None
    try:
        t0 = time.perf_counter()
        # np.asarray forces execution AND the result fetch
        xla_res = np.asarray(query(dev_ts, dev_vals, dev_vbase, dev_gids,
                                   dev_wends))
        stage["xla_compile_s"] = round(time.perf_counter() - t0, 2)
        lat = []
        for _ in range(iters):
            t0 = time.perf_counter()
            np.asarray(query(dev_ts, dev_vals, dev_vbase, dev_gids,
                             dev_wends))
            lat.append(time.perf_counter() - t0)
        p50 = float(np.median(np.asarray(lat)))
        stage.update({
            "xla_p50_s": round(p50, 5),
            "xla_samples_per_sec": round(scanned / p50, 1),
            "xla_hbm_gb_s_lower_bound": round(value_bytes / p50 / 1e9, 1),
        })
        persist(stage)
    except Exception as e:  # noqa: BLE001 — OOM etc.: still try fused
        stage["xla_error"] = f"{type(e).__name__}: {e}"[:300]
        persist(stage)

    if do_fused:
        try:
            fused_iters = max(3, iters // 2) if S >= 1 << 20 else iters
            p50_f, err = run_pallas_fused(ts_row, dev_vals, vbase32, gids,
                                          wends, range_ms, G, xla_res,
                                          fused_iters)
            stage["pallas_p50_s"] = round(p50_f, 5)
            stage["pallas_samples_per_sec"] = round(scanned / p50_f, 1)
            # one HBM pass over the values by construction
            stage["pallas_hbm_gb_s"] = round(value_bytes / p50_f / 1e9, 1)
            Tp = (T + 127) // 128 * 128
            Wp = (W + 127) // 128 * 128
            Gp = max(G, 8)
            flops = 2 * S * Tp * Wp * _FUSED_MATMULS + 2 * Gp * S * Wp
            stage["pallas_model_tflops_per_s"] = round(flops / p50_f / 1e12,
                                                       2)
            if err is not None:
                stage["pallas_max_rel_err_vs_xla"] = (
                    round(err, 9) if np.isfinite(err) else "inf")
            persist(stage)
        except Exception as e:  # noqa: BLE001
            stage["pallas_error"] = f"{type(e).__name__}: {e}"[:300]
            persist(stage)

    # headline for this stage: fastest path whose result is trusted —
    # fused needs a clean cross-check HERE, or (when XLA was unavailable,
    # e.g. OOM at 1M) a clean cross-check recorded at a PREVIOUS stage
    paths = []
    if "xla_p50_s" in stage:
        paths.append(("xla", stage["xla_p50_s"]))
    err_ok = stage.get("pallas_max_rel_err_vs_xla")
    checked_here = isinstance(err_ok, float) and err_ok < 1e-4
    cpu_cert_failed = False
    if platform == "cpu" and xla_res is not None:
        # no Pallas on the CPU path: certify XLA against the f64 oracle so
        # the artifact's number is still self-checking.  Above the cap,
        # certify a group-representative subset (gids cycle through all G
        # groups) by re-running the jitted query on the sliced inputs.
        try:
            Sc = min(S, CONFORMANCE_SERIES_CAP)
            if Sc == S:
                sub_res = xla_res
            else:
                sub_res = np.asarray(query(dev_ts, dev_vals[:Sc],
                                           dev_vbase[:Sc], dev_gids[:Sc],
                                           dev_wends))
            checked_here = cpu_f64_conformance(
                stage, sub_res, ts_row, vals[:Sc], gids[:Sc], G, wends,
                range_ms)
            cpu_cert_failed = not checked_here
        except Exception as e:  # noqa: BLE001 — a cert CRASH (OOM etc.) is
            # not evidence the result is wrong: record it and fall back to
            # conformance inherited from a previously-certified stage
            stage["conformance_error"] = f"{type(e).__name__}: {e}"[:200]
    if "pallas_p50_s" in stage and (
            checked_here or (err_ok is None and xla_res is None
                             and prior_conformance_ok)):
        paths.append(("pallas_fused", stage["pallas_p50_s"]))
        if not checked_here:
            stage["pallas_conformance"] = "inherited from previous stage"
    stage["conformance_ok"] = checked_here or (prior_conformance_ok
                                               and not cpu_cert_failed)
    if cpu_cert_failed:
        # a stage whose own certificate failed must not publish a trusted
        # headline number (raw xla_* timings stay recorded above)
        paths = []
    if paths:
        kernel, p50 = min(paths, key=lambda kv: kv[1])
        stage.update({
            "kernel": kernel,
            "p50_s": round(p50, 5),
            "samples_per_sec": round(scanned / p50, 1),
        })
    persist(stage)
    del dev_ts, dev_vals, dev_vbase, dev_gids, dev_wends
    return stage, ts_row, vals, gids, wends, range_ms, span_hi - span_lo


def measure_ingest(series=262_144, max_seconds=10.0, max_t=256):
    """Host-path ingest throughput: columnar grid appends into one live
    shard (partition creation warmed out of the timed window, no flush, no
    queries) — the `ingest_samples_per_sec` stage of the one-line bench
    contract, so the trajectory tracks the host half of the pipeline and
    not just the device scan path.  Bounded two ways: wall clock and
    samples-per-series (memory)."""
    import numpy as np

    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import counter_batch

    START = 1_600_000_000_000
    ms = TimeSeriesMemStore()
    sh = ms.setup("bench_ingest", 0)
    t0 = time.perf_counter()
    base = counter_batch(series, 1, start_ms=START)
    build_s = time.perf_counter() - t0
    k = 2
    row_base = np.arange(series, dtype=np.float64)[:, None]

    def ingest_once(t_idx):
        ts_row = START + (t_idx + np.arange(k, dtype=np.int64)) * 10_000
        ts2d = np.broadcast_to(ts_row, (series, k))
        vals = (t_idx + np.arange(k, dtype=np.float64))[None, :] * 5.0 \
            + row_base
        return sh.ingest_columns("prom-counter", base.part_keys, ts2d,
                                 {"count": vals}, offset=t_idx)

    ingest_once(0)                       # warm: creates all partitions
    t_idx = k
    n0 = sh.stats.rows_ingested
    t0 = time.perf_counter()
    while (time.perf_counter() - t0 < max_seconds) and t_idx < max_t:
        ingest_once(t_idx)
        t_idx += k
    dt = time.perf_counter() - t0
    n = sh.stats.rows_ingested - n0
    return {"series": series, "samples": int(n),
            "elapsed_s": round(dt, 2),
            "partkey_build_s": round(build_s, 2),
            "dropped": int(sh.stats.rows_dropped),
            "ingest_samples_per_sec": round(n / max(dt, 1e-9), 1)}


def measure_wal(quick=False, series=None):
    """Durability stage (ISSUE 7): WAL-on vs WAL-off columnar ingest
    throughput, restart-replay rate, the remote_write front-door rate,
    and the kill-chaos proof.

    One-line JSON keys:
      wal_off_samples_per_sec / wal_on_samples_per_sec — the same
          ingest_columns loop with and without the group-committed WAL
          in front (fresh store each, same batch shapes)
      wal_overhead_pct / wal_on_vs_off_pct — the durability tax;
          acceptance gate: WAL-on >= 50% of WAL-off
      wal_replay_samples_per_sec — cold-restart replay of the log just
          written, through the same ingest_columns path
      remote_write_samples_per_sec — snappy+protobuf POST /api/v1/write
          end to end (decode -> slabs -> ingest), reference-shaped
          payloads, no socket (the route layer, like the QPS stages)
      wal_kill_acked_lost — SIGKILL a real ingesting node subprocess
          (bench/walchaos.py), replay its WAL, count client-observed
          acknowledged batches missing from the recovered store
          (acceptance gate: 0) — and wal_kill_query_identical: the
          recovered store's query_range answer is byte-identical to an
          uninterrupted run over the same replayed batches
    """
    import shutil
    import tempfile

    from bench.walchaos import START_MS, chaos_batch, chaos_keys
    from filodb_tpu.config import WalConfig
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.wal import WalManager

    S = series or (8_192 if quick else 65_536)
    k = 4
    budget_s = 2.0 if quick else 6.0
    max_batches = 16 if quick else 32
    out = {"series": S, "k": k}
    root = tempfile.mkdtemp(prefix="filodb-wal-bench-")
    keys = chaos_keys(S)

    def ingest_run(wal):
        ms = TimeSeriesMemStore()
        sh = ms.setup("prometheus", 0)
        ts0, v0 = chaos_batch(S, k, 0, START_MS)
        sh.ingest_columns("gauge", keys, ts0, {"value": v0})  # warm: creates
        n0 = sh.stats.rows_ingested
        b = 1
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < budget_s and b <= max_batches:
            ts, vals = chaos_batch(S, k, b, START_MS)
            if wal is not None:
                # the production sink's ordering: append (no wait) ->
                # in-memory ingest overlapping the committer's fsync ->
                # ONE commit wait before the ack
                seq = wal.append_grid(0, "gauge", keys, ts,
                                      {"value": vals}, wait=False)
            else:
                seq = -1
            sh.ingest_columns("gauge", keys, ts, {"value": vals},
                              offset=seq)
            if wal is not None:
                wal.commit(seq)
            b += 1
        dt = time.perf_counter() - t0
        return (sh.stats.rows_ingested - n0) / max(dt, 1e-9)

    # --- WAL-off vs WAL-on, same shapes, fresh stores.  Interleaved
    # rounds, best of each: container/overlay filesystems throw
    # multi-second sync stalls that would otherwise report a durability
    # tax the WAL does not have (one observed run: a single 8 s first
    # fsync at zero load)
    off_sps = on_sps = 0.0
    for rnd in range(2):
        off_sps = max(off_sps, ingest_run(None))
        wal = WalManager(os.path.join(root, f"on{rnd}"), "prometheus",
                         WalConfig(enabled=True))
        try:
            on_sps = max(on_sps, ingest_run(wal))
        finally:
            wal.close()
    out["wal_off_samples_per_sec"] = round(off_sps, 1)
    out["wal_on_samples_per_sec"] = round(on_sps, 1)
    out["wal_overhead_pct"] = round((1.0 - on_sps / max(off_sps, 1e-9))
                                    * 100.0, 1)
    out["wal_on_vs_off_pct"] = round(on_sps / max(off_sps, 1e-9) * 100.0,
                                     1)
    out["wal_gate_ok"] = bool(on_sps >= 0.5 * off_sps)

    # --- cold replay of the last round's log
    from filodb_tpu.wal import replay_dir
    ms2 = TimeSeriesMemStore()
    stats = replay_dir(os.path.join(root, "on1", "prometheus"), ms2,
                       "prometheus")
    out["wal_replay_records"] = stats.records
    out["wal_replay_samples_per_sec"] = round(stats.samples_per_sec, 1)

    # --- remote_write front door (route layer, no socket)
    out.update(_measure_remote_write(quick))

    # --- kill-mid-ingest chaos
    try:
        out.update(_wal_kill_chaos(root, quick))
    except Exception as e:  # noqa: BLE001 — the proof failing must be LOUD
        out["wal_kill_error"] = f"{type(e).__name__}: {e}"[:300]
    shutil.rmtree(root, ignore_errors=True)
    return out


def _measure_remote_write(quick):
    """POST /api/v1/write throughput through the route handler: snappy
    block decompress + prompb decode + slab grouping + ingest_columns
    (the whole server-side cost; payload ENCODE is the client's)."""
    from filodb_tpu.http import remotepb
    from filodb_tpu.standalone import DatasetConfig, FiloServer
    from filodb_tpu.utils import snappy as fsnappy

    S_rw = 2_048 if quick else 8_192
    k = 4
    start = 1_600_000_000_000
    srv = FiloServer(datasets=[DatasetConfig("prometheus", num_shards=2)])
    try:
        payloads = []
        for b in range(6):
            series = []
            for i in range(S_rw):
                labels = [("__name__", "rw_bench_total"), ("_ws_", "rw"),
                          ("_ns_", "bench"), ("inst", f"i{i:05d}")]
                samples = [(float(i + j), start + (b * k + j) * 10_000)
                           for j in range(k)]
                series.append(remotepb.PromTimeSeries(labels, samples))
            payloads.append(fsnappy.compress(
                remotepb.encode_write_request(series)))
        st, _ = srv.api.handle("POST", "/api/v1/write", {}, payloads[0])
        assert st == 204, f"remote_write bench got {st}"
        posted = 0
        t0 = time.perf_counter()
        budget = 2.0 if quick else 5.0
        i = 1
        while time.perf_counter() - t0 < budget and i < len(payloads):
            st, _ = srv.api.handle("POST", "/api/v1/write", {},
                                   payloads[i])
            assert st == 204, f"remote_write bench got {st}"
            posted += S_rw * k
            i += 1
        dt = time.perf_counter() - t0
        return {"remote_write_series": S_rw,
                "remote_write_samples_per_sec":
                    round(posted / max(dt, 1e-9), 1)}
    finally:
        srv.shutdown()


def _wal_kill_chaos(root, quick):
    """SIGKILL a real WAL-ingesting subprocess mid-batch, replay what it
    left on disk, and prove (a) every client-observed acknowledged batch
    survived and (b) the recovered store answers queries byte-identical
    to an uninterrupted run over the same batches."""
    import signal

    from bench.walchaos import START_MS
    from filodb_tpu.config import FilodbSettings
    from filodb_tpu.standalone import DatasetConfig, FiloServer

    S_kill = 1_024 if quick else 4_096
    k = 2
    kill_after = 4 if quick else 8
    wal_root = os.path.join(root, "kill")
    worker = os.path.join(REPO_DIR, "bench", "walchaos.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_DIR
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, worker, "--wal-dir", wal_root,
         "--series", str(S_kill), "--k", str(k)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env, cwd=REPO_DIR)
    acked = -1
    try:
        ready = proc.stdout.readline()
        assert ready.startswith("CHAOS_READY"), f"child: {ready!r}"
        while acked + 1 < kill_after:
            line = proc.stdout.readline()
            if not line:
                raise RuntimeError("chaos child exited early")
            if line.startswith("ACKED"):
                acked = int(line.split()[1])
        # kill MID-batch: the child is inside append/commit of the next
        # batch right after we read this ack
        time.sleep(0.02)
    finally:
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)

    # recovery: a fresh server on the same WAL dir replays at boot
    cfg = FilodbSettings()
    cfg.wal.enabled = True
    cfg.wal.dir = wal_root
    rec = FiloServer(datasets=[DatasetConfig("prometheus", num_shards=1)],
                     config=cfg)
    try:
        shard = rec.memstore.get_shard("prometheus", 0)
        replayed = int(shard.ingested_offset) + 1   # seq b == batch b
        lost = max(0, (acked + 1) - replayed)
        # uninterrupted reference: same batches, no crash, no WAL
        ref = FiloServer(
            datasets=[DatasetConfig("prometheus", num_shards=1)])
        try:
            from bench.walchaos import chaos_batch, chaos_keys
            rkeys = chaos_keys(S_kill)
            rshard = ref.memstore.get_shard("prometheus", 0)
            for b in range(replayed):
                ts, vals = chaos_batch(S_kill, k, b, START_MS)
                rshard.ingest_columns("gauge", rkeys, ts,
                                      {"value": vals})
            q = {"query": "sum(wal_chaos_total)",
                 "start": str(START_MS // 1000),
                 "end": str(START_MS // 1000 + replayed * k * 10),
                 "step": "10"}
            st_a, pay_a = rec.api.handle("GET", "/api/v1/query_range",
                                         dict(q), b"")
            st_b, pay_b = ref.api.handle("GET", "/api/v1/query_range",
                                         dict(q), b"")
            for p in (pay_a, pay_b):
                if isinstance(p, dict):
                    p.pop("traceID", None)   # per-request random id
            identical = (st_a == st_b == 200
                         and json.dumps(pay_a, sort_keys=True)
                         == json.dumps(pay_b, sort_keys=True))
        finally:
            ref.shutdown()
    finally:
        rec.shutdown()
    return {"wal_kill_acked_batches": acked + 1,
            "wal_kill_replayed_batches": replayed,
            "wal_kill_acked_lost": lost,
            "wal_kill_query_identical": bool(identical)}


def _rw_payloads(series, k, batches, start_ms=None, ws="trc"):
    """Pre-encoded remote_write payloads (snappy+prompb) with distinct,
    near-now timestamps per batch — client encode cost stays out of the
    measured server path, and now-ish stamps keep the freshness
    histograms meaningful."""
    from filodb_tpu.http import remotepb
    from filodb_tpu.utils import snappy as fsnappy
    start = start_ms or (int(time.time() * 1000) - batches * k * 1000)
    payloads = []
    for b in range(batches):
        srs = []
        for i in range(series):
            labels = [("__name__", "trace_bench_total"), ("_ws_", ws),
                      ("_ns_", "bench"), ("inst", f"i{i:05d}")]
            samples = [(float(i + j), start + (b * k + j) * 1000)
                       for j in range(k)]
            srs.append(remotepb.PromTimeSeries(labels, samples))
        payloads.append(fsnappy.compress(
            remotepb.encode_write_request(srs)))
    return payloads


def measure_ingesttrace(quick=False, series=None):
    """Write-path tracing stage (ISSUE 12): the observability tax on the
    ingest path, the stitched 2-node trace proof, and the fault-
    visibility drill.

    One-line JSON keys:
      ingest_trace_overhead_pct / ingest_trace_on_samples_per_sec —
          remote_write door throughput with the span+exemplar pipeline
          on vs off (fresh server each round, interleaved, best-of;
          acceptance gate: tracing-on >= 98% of tracing-off)
      ingest_trace_stitched / ingest_trace_nodes / ingest_trace_spans —
          a 2-node RF-2 run (real replica subprocess, quorum acks)
          produces ONE trace id whose span tree covers door -> WAL
          append -> fsync wait -> replication fan-out -> replica WAL ->
          memstore ingest on BOTH nodes
      ingesttrace_fault_visible — an injected wal.fsync delay
          (utils/faults.py) shows up in the fsync-latency histogram,
          the ingest slowlog, AND the freshness histograms, and flips
          health to degraded while sustained
      ingest_freshness_p99_s — the ingest-to-ack p99 over the traced
          run's batches
    """
    import shutil
    import tempfile

    from filodb_tpu.standalone import DatasetConfig, FiloServer
    from filodb_tpu.utils.metrics import (collector, registry,
                                          set_exemplars_enabled,
                                          set_spans_enabled)

    S = series or (1_024 if quick else 2_048)
    k = 4
    batches = 17 if quick else 49
    out = {"ingest_trace_series": S}
    root = tempfile.mkdtemp(prefix="filodb-ingesttrace-")

    # --- tracing tax on the remote_write door.  The per-POST fixed cost
    # (protobuf decode + per-series key hashing) is ~4 orders above the
    # span pipeline's, so a rate-over-rounds compare is pure noise at a
    # 2% gate; instead INTERLEAVE modes POST by POST on one server
    # (distinct pre-encoded payloads, store grows identically under
    # both modes) and compare per-POST MEDIANS — the observability
    # stage's measured-pairs pattern
    def door_tax():
        import gc
        import statistics
        srv = FiloServer(
            datasets=[DatasetConfig("prometheus", num_shards=2)])
        times = {True: [], False: []}
        try:
            payloads = _rw_payloads(S, k, batches)
            st, _ = srv.api.handle("POST", "/api/v1/write", {},
                                   payloads[0])
            assert st == 204, f"ingesttrace warm got {st}"
            # GC pinned: the decode path allocates ~100 objects per
            # series per POST, and gen-2 collections landing on random
            # POSTs are a bimodal ±30% that buries a 2% gate; collect
            # OUTSIDE each timed window instead
            gc.disable()
            for i, p in enumerate(payloads[1:]):
                # ABBA pairing: per-POST cost drifts as the store
                # grows, and a fixed on-then-off order would book the
                # drift entirely against one mode
                pair, first = divmod(i, 2)
                on = (first == 0) == (pair % 2 == 0)
                set_spans_enabled(on)
                set_exemplars_enabled(on)
                gc.collect()
                t0 = time.perf_counter()
                st, _ = srv.api.handle("POST", "/api/v1/write", {}, p)
                assert st == 204, f"ingesttrace bench got {st}"
                times[on].append(time.perf_counter() - t0)
        finally:
            gc.enable()
            srv.shutdown()

        def fastq(xs):
            # mean of the fastest quartile: the modes' best-case paths
            # are the comparable ones — residual scheduler/IO stalls
            # land in the slow tail of BOTH modes but not evenly
            xs = sorted(xs)
            q = max(len(xs) // 4, 1)
            return statistics.mean(xs[:q])

        return fastq(times[True]), fastq(times[False])

    try:
        on_p50, off_p50 = door_tax()
    finally:
        set_spans_enabled(True)
        set_exemplars_enabled(True)
    on_sps = S * k / max(on_p50, 1e-9)
    off_sps = S * k / max(off_p50, 1e-9)
    out["ingest_trace_off_samples_per_sec"] = round(off_sps, 1)
    out["ingest_trace_on_samples_per_sec"] = round(on_sps, 1)
    out["ingest_trace_overhead_pct"] = round(
        (1.0 - on_sps / max(off_sps, 1e-9)) * 100.0, 2)
    overhead_ok = on_sps >= 0.98 * off_sps

    # --- stitched 2-node trace + fault drill: node B is a REAL replica
    # subprocess (bench/chaosnode.py — replication door + its own WAL),
    # node A an in-process FiloServer fanning out at RF-2/quorum
    from filodb_tpu.config import FilodbSettings
    from filodb_tpu.utils.freshness import freshness
    from filodb_tpu.utils.metrics import make_traceparent, mint_trace_id
    from filodb_tpu.utils.slowlog import ingestlog

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_DIR
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO_DIR, "bench", "chaosnode.py"),
         "--name", "B", "--port", "0", "--repl-port", "0",
         "--shards", "0", "--dataset", "tracetest",
         "--series", "8", "--samples", "4",
         "--wal-dir", os.path.join(root, "walB")],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env, cwd=REPO_DIR)
    srv = None
    try:
        ready = json.loads(proc.stdout.readline())
        assert ready.get("ready"), f"chaosnode: {ready}"
        cfg = FilodbSettings()
        cfg.wal.enabled = True
        cfg.wal.dir = os.path.join(root, "walA")
        cfg.replication.enabled = True
        cfg.replication.factor = 2
        cfg.replication.ack_mode = "quorum"
        # a tight SLO so the injected fsync delay below counts as a
        # sustained breach within a few batches
        cfg.ingest.slow_batch_threshold_s = 0.05
        cfg.ingest.freshness_breach_count = 3
        freshness.reset()
        ingestlog.clear()
        srv = FiloServer(
            datasets=[DatasetConfig("tracetest", num_shards=1)],
            config=cfg, node_name="A",
            replication_peers={"B": ("127.0.0.1", ready["repl_port"])})
        tid = mint_trace_id()
        ws = "trc"
        st, pay = srv.api.handle(
            "POST", "/api/v1/write", {}, _rw_payloads(64, 2, 1)[0],
            headers={"traceparent": make_traceparent(tid)})
        assert st == 204, f"traced write got {st}: {pay}"
        assert pay["_headers"]["X-Trace-Id"] == tid
        evs = collector.trace(tid)
        by_node = {}
        for e in evs:
            leaf = e["span"].rsplit(".", 1)[-1]
            by_node.setdefault(e.get("node", ""), set()).add(leaf)
        a_spans = by_node.get("A", set())
        b_spans = by_node.get("B", set())
        stitched = (
            {"remote_write", "wal_append", "wal_commit_wait",
             "replication_fanout", "replica_append",
             "ingest_columns"} <= a_spans
            and {"wal_append", "ingest_columns"} <= b_spans)
        out["ingest_trace_spans"] = len(evs)
        out["ingest_trace_nodes"] = sorted(by_node)
        out["ingest_trace_stitched"] = bool(stitched)
        if not stitched:
            out["ingest_trace_span_tree"] = {
                n: sorted(s) for n, s in by_node.items()}

        # --- fault drill: delay node A's group-commit fsync; the delay
        # must surface in the fsync histogram, the ingest slowlog, the
        # freshness histograms, AND the health verdict (sustained)
        from filodb_tpu.utils.faults import faults
        delay = 0.25
        fsync_hist = registry.histogram("wal_fsync_seconds",
                                        dataset="tracetest")
        ack_hist = registry.histogram("ingest_ack_seconds", ws=ws,
                                      origin="remote_write")
        with faults.plan("wal.fsync", "delay", first_k=8,
                         delay_s=delay):
            for p in _rw_payloads(64, 2, 4, ws=ws):
                st, _ = srv.api.handle("POST", "/api/v1/write", {}, p)
                assert st == 204
        slow_recs = [r for r in ingestlog.entries()
                     if r["stages"]["wal_commit_wait_s"] >= delay * 0.5
                     and r["trace_id"]]
        fresh_hist = registry.histogram("ingest_freshness_seconds",
                                        ws=ws)
        health = srv.api.handle("GET", "/api/v1/status/health",
                                {}, b"")[1]["data"]
        ingest_verdict = health["subsystems"]["ingest"]
        fault_visible = (fsync_hist.max >= delay * 0.8
                         and len(slow_recs) >= 3
                         and ack_hist.max >= delay * 0.8
                         and fresh_hist.count >= 4
                         and ingest_verdict["status"] == "degraded"
                         and health["status"] != "ok")
        out["ingesttrace_fault_visible"] = bool(fault_visible)
        out["ingest_freshness_p99_s"] = round(
            ack_hist.percentile(0.99), 4)
        if not fault_visible:
            out["ingesttrace_fault_detail"] = {
                "fsync_max_s": round(fsync_hist.max, 4),
                "slow_recs": len(slow_recs),
                "ack_max_s": round(ack_hist.max, 4),
                "freshness_count": fresh_hist.count,
                "ingest_verdict": ingest_verdict}
    finally:
        proc.kill()
        proc.wait(timeout=10)
        if srv is not None:
            srv.shutdown()
        freshness.reset()
        freshness.configure(threshold_s=5.0, breach_count=3,
                            window_s=60.0)
        shutil.rmtree(root, ignore_errors=True)

    out["ingesttrace_gate_ok"] = bool(
        out.get("ingest_trace_stitched")
        and out.get("ingesttrace_fault_visible")
        and (quick or overhead_ok))
    return out


COVERAGE_QUERIES = [
    # (name, promql, ragged_ok) — a realistic dashboard mix, expanded from
    # the reference's QueryInMemoryBenchmark set (QUERY_SET in bench/suite).
    # r4: the rate family and instant selectors take ragged working sets
    # (valid-boundary kernel scans / validity one-hots)
    ("sum_rate", 'sum(rate(request_total[5m]))', True),
    ("sum_by_rate", 'sum by (_ns_)(rate(request_total[5m]))', True),
    ("avg_rate", 'avg by (_ns_)(rate(request_total[5m]))', True),
    ("max_rate", 'max by (_ns_)(rate(request_total[5m]))', True),
    ("count_rate", 'count by (_ns_)(rate(request_total[5m]))', True),
    ("sum_increase", 'sum(increase(request_total[5m]))', True),
    ("instant_sum", 'sum by (_ns_)(heap_usage)', True),
    ("sum_over_time", 'sum(sum_over_time(heap_usage[5m]))', True),
    ("avg_over_time", 'avg by (_ns_)(avg_over_time(heap_usage[5m]))',
     True),
    ("count_over_time", 'sum(count_over_time(heap_usage[5m]))', True),
    ("min_over_time", 'min by (_ns_)(min_over_time(heap_usage[5m]))',
     True),
    ("max_over_time", 'max(max_over_time(heap_usage[5m]))', True),
    ("hist_quantile",
     'histogram_quantile(0.9, sum(rate(http_latency[5m])) by (_ns_))',
     False),
]


def measure_fused_coverage():
    """Fraction of the realistic query mix that actually engages a fused
    leaf path (kernel, host fast path, or reduce_window) — measured on a
    live engine, not inferred from the eligibility table.  Runs the same
    mix against a NaN-holed (ragged) working set for the kinds that admit
    it (VERDICT r2 item 2 'emit a fused_coverage fraction')."""
    os.environ["FILODB_TPU_FUSED_INTERPRET"] = "1"
    import numpy as _np

    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.core.records import RecordBatch
    from filodb_tpu.ingest.generator import (counter_batch, gauge_batch,
                                             histogram_batch)
    from filodb_tpu.parallel.shardmapper import ShardEvent, ShardMapper
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.utils.metrics import registry

    START = 1_600_000_000_000
    S, T = 64, 240

    def mk_engine(ragged):
        ms = TimeSeriesMemStore()
        sh = ms.setup("prometheus", 0)
        cb = counter_batch(S, T, start_ms=START)
        gb = gauge_batch(S, T, start_ms=START)
        if ragged:
            # production-shaped working set: scrape gaps in counters AND
            # gauges (r4: the rate family fuses over these too)
            def hole(b, col, seed):
                vals = b.columns[col].copy()
                vals[np.random.default_rng(seed).random(vals.shape)
                     < 0.1] = _np.nan
                return RecordBatch(b.schema, b.part_keys, b.part_idx,
                                   b.timestamps, {col: vals},
                                   b.bucket_les)
            cb = hole(cb, "count", 4)
            gb = hole(gb, "value", 5)
        sh.ingest(cb)
        sh.ingest(gb)
        try:
            sh.ingest(histogram_batch(16, T, start_ms=START))
        except Exception:  # noqa: BLE001 — hist generator optional
            pass
        mapper = ShardMapper(1)
        mapper.update_from_event(
            ShardEvent("IngestionStarted", "prometheus", 0, "b"))
        return QueryEngine("prometheus", ms, mapper)

    counters = ("leaf_fused_kernel", "leaf_fused_count_host",
                "leaf_fused_minmax", "leaf_host_routed")

    def fused_total():
        return sum(registry.counter(c).value for c in counters)

    results = {}
    for mode, ragged in (("dense", False), ("ragged", True)):
        eng = mk_engine(ragged)
        s = START // 1000
        engaged = []
        for name, q, ragged_ok in COVERAGE_QUERIES:
            res = eng.query_range(q, s + 600, 60, s + T * 10)
            if res.error is not None:
                continue
            before = fused_total()
            eng.query_range(q, s + 600, 60, s + T * 10)  # mirror warm now
            if fused_total() > before:
                engaged.append(name)
        applicable = [n for n, _, r_ok in COVERAGE_QUERIES
                      if not ragged or r_ok]
        results[f"fused_coverage_{mode}"] = round(
            len([n for n in engaged if n in applicable])
            / max(len(applicable), 1), 3)
        results[f"fused_engaged_{mode}"] = engaged
    return results


def measure_dashboard_batch(platform):
    """Ops-level dashboard batching (r4): 8 aggregation panels over ONE
    65k working set — merged multi-hot dispatch (fused_leaf_agg_batch)
    vs one dispatch per panel (fused_leaf_agg): the dashboard-latency
    number wherever a query is dispatch-bound; round-4 reference
    capture: TPU_BATCH_r04.json (4.71x at 262k)."""
    from filodb_tpu.ops import pallas_fused as pf
    from filodb_tpu.ops.timewindow import make_window_ends
    interpret = platform != "tpu"
    if interpret and not os.environ.get("FILODB_TPU_FUSED_INTERPRET"):
        return {"skipped": "kernel is MXU-targeted; no TPU backend"}
    S, T, iters = 65_536, 720, 7
    ts_row, vals = make_counter_data(S, T)
    vbase64 = vals[:, 0].astype(np.float64)
    vals32 = (vals.astype(np.float64) - vbase64[:, None]).astype(np.float32)
    vbase32 = vbase64.astype(np.float32)
    wends = make_window_ends(600_000, int(ts_row[-1]), 60_000)
    plan = pf.build_plan(ts_row.astype(np.int64),
                         np.asarray(wends, np.int64), 300_000)
    pv = pf.pad_values(vals32, vbase32, plan)
    groupings = [(1000, "sum"), (100, "avg"), (10, "sum"), (8, "sum"),
                 (500, "sum"), (50, "avg"), (250, "sum"), (2, "sum")]
    panels = [(pf.pad_groups((np.arange(S) % g).astype(np.int32), S, g),
               g, op) for g, op in groupings]

    def batched():
        return pf.fused_leaf_agg_batch(plan, pv, panels, "rate",
                                       precorrected=True,
                                       interpret=interpret, ragged=False,
                                       num_series=S)

    # host copies OUTSIDE the timed region: fused_leaf_agg only takes
    # len(gids) from this, and a per-iteration device pull would bias
    # sequential_p50_s (and so the speedup) upward
    gids_rows = [np.asarray(groups.gids_p[:S, 0]) for groups, _, _ in panels]

    def sequential():
        out = []
        for (g, op), (groups, G, _), grow in zip(groupings, panels,
                                                 gids_rows):
            prep = pf.PreparedInputs(pv.vals_p, pv.vbase_p,
                                     groups.gids_p, groups.gsize)
            out.append(pf.fused_leaf_agg(
                plan, prep, grow, G, "rate", op,
                precorrected=True, interpret=interpret))
        return out

    st = {"series": S, "panels": len(panels),
          "total_groups": sum(g for g, _ in groupings)}
    t0 = time.perf_counter()
    got_b = batched()
    st["batched_compile_s"] = round(time.perf_counter() - t0, 2)
    t0 = time.perf_counter()
    got_s = sequential()
    st["sequential_compile_s"] = round(time.perf_counter() - t0, 2)
    for name, fn in (("batched", batched), ("sequential", sequential)):
        ts = []
        for _ in range(iters):
            t1 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t1)
        ts.sort()
        st[f"{name}_p50_s"] = round(ts[len(ts) // 2], 5)
    st["speedup_p50"] = round(st["sequential_p50_s"]
                              / st["batched_p50_s"], 2)
    st["max_rel_err_batched_vs_sequential"] = max(
        float(np.nanmax(np.abs(b - q) / np.maximum(np.abs(q), 1e-6)))
        for b, q in zip(got_b, got_s))
    return st


def _frontend_fixture(S, T, dataset):
    """Shared workload for the query_frontend and observability stages:
    one live store of S counter series x T 10s scrapes, a QueryFrontend
    over it, and the dashboard-panel query — ONE definition so the two
    acceptance stages can never silently measure different workloads.
    Returns (frontend, engine, query, start_s, end_s, planner_params)."""
    import numpy as np

    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import counter_batch
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.query.frontend import QueryFrontend
    from filodb_tpu.query.rangevector import PlannerParams

    START = 1_600_000_000_000
    ms = TimeSeriesMemStore()
    sh = ms.setup(dataset, 0)
    base = counter_batch(S, 1, start_ms=START)
    row_base = np.arange(S, dtype=np.float64)[:, None]
    for t0 in range(0, T, 40):
        n = min(40, T - t0)
        ts2d = np.broadcast_to(
            START + (t0 + np.arange(n, dtype=np.int64)) * 10_000, (S, n))
        vals = (t0 + np.arange(n, dtype=np.float64))[None, :] * 5.0 \
            + row_base
        sh.ingest_columns("prom-counter", base.part_keys, ts2d,
                          {"count": vals}, offset=t0)
    eng = QueryEngine(dataset, ms)
    fe = QueryFrontend(eng)
    pp = PlannerParams(sample_limit=2_000_000_000, scan_limit=2_000_000_000)
    q = 'sum by (_ns_)(rate(request_total[5m]))'
    s = START // 1000
    start_s, end_s = s + 600, s + (T - 1) * 10   # end == newest sample
    return fe, eng, q, start_s, end_s, pp


def measure_query_frontend(quick=False, series=None, iters=7):
    """Query-serving frontend (PR 2): cached re-poll latency and
    concurrent dashboard-repeat QPS against the sequential no-frontend
    baseline, on one live store at the 262k-series acceptance scale
    (8k under --quick).

    Two numbers ride into the one-line JSON:
      cached_repoll_p50_s — warm identical re-poll through the frontend
        (result-cache hit) vs cold_p50_s (cache cleared per iteration;
        kernel/mirror caches warm in both, so the delta is the frontend's)
      concurrent_qps — 8 threads polling one dashboard panel through the
        frontend (singleflight + cache) vs sequential_baseline_qps (one
        thread straight into the engine: the pre-frontend serving path)
    """
    import threading

    from filodb_tpu.utils.metrics import registry

    S = series or (8_192 if quick else 262_144)
    T = 120                              # 20 min of 10s scrapes
    fe, eng, q, start_s, end_s, pp = _frontend_fixture(
        S, T, "bench_frontend")
    r = fe.query_range(q, start_s, 60, end_s, pp)      # warm everything
    if r.error:
        return {"series": S, "error": r.error[:200]}
    st = {"series": S, "samples_per_series": T, "result_series":
          r.num_series}

    def p50(fn, n=iters):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            res = fn()
            ts.append(time.perf_counter() - t0)
            assert res.error is None, res.error
        ts.sort()
        return ts[len(ts) // 2]

    def cold():
        if fe.cache is not None:
            fe.cache.clear()
        return fe.query_range(q, start_s, 60, end_s, pp)

    st["cold_p50_s"] = round(p50(cold), 5)
    fe.query_range(q, start_s, 60, end_s, pp)          # fill the cache
    st["cached_repoll_p50_s"] = round(
        p50(lambda: fe.query_range(q, start_s, 60, end_s, pp)), 5)
    st["repoll_ratio"] = round(
        st["cached_repoll_p50_s"] / max(st["cold_p50_s"], 1e-9), 4)

    # --- concurrent dashboard-repeat QPS vs the pre-frontend baseline ---
    dur_s = 4.0 if quick else 8.0

    def pump(fn):
        stop_t = time.perf_counter() + dur_s
        n = 0
        while time.perf_counter() < stop_t:
            res = fn()
            assert res.error is None, res.error
            n += 1
        return n / dur_s

    # sequential baseline: the serving path before this PR — every poll
    # pays the full engine cost
    st["sequential_baseline_qps"] = round(
        pump(lambda: eng.query_range(q, start_s, 60, end_s, pp)), 1)
    sf0 = registry.counter("query_singleflight_hits").value
    counts = []
    errors = []
    stop_t = [0.0]

    def client():
        n = 0
        while time.perf_counter() < stop_t[0]:
            res = fe.query_range(q, start_s, 60, end_s, pp)
            if res.error is not None:
                # surface, don't swallow: a thread dying silently would
                # leave a passing-looking concurrent_qps behind
                errors.append(res.error)
                break
            n += 1
        counts.append(n)

    threads = [threading.Thread(target=client) for _ in range(8)]
    stop_t[0] = time.perf_counter() + dur_s
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    if errors:
        st["error"] = f"concurrent stage: {errors[0]}"[:200]
        st["concurrent_errors"] = len(errors)
        return st
    st["concurrent_qps"] = round(sum(counts) / max(wall, 1e-9), 1)
    st["concurrent_threads"] = 8
    st["singleflight_hits"] = int(
        registry.counter("query_singleflight_hits").value - sf0)
    st["qps_vs_sequential"] = round(
        st["concurrent_qps"] / max(st["sequential_baseline_qps"], 1e-9), 1)
    return st


def measure_observability(quick=False, series=None):
    """PR 3 acceptance: the span+stats attribution layer must cost <= 5%
    of the query_frontend concurrent QPS.  Same workload shape as
    measure_query_frontend (8 threads polling one panel through the
    frontend: singleflight + result cache + stats accounting), measured
    with the span pipeline ON vs OFF (utils.metrics.set_spans_enabled)
    in interleaved pairs; `span_overhead_pct` rides the one-line JSON.
    Also sanity-checks the stats payload itself: a run whose overhead is
    low because attribution silently broke must not pass."""
    import threading

    from filodb_tpu.utils import metrics as um

    S = series or (4_096 if quick else 65_536)
    T = 120
    fe, eng, q, start_s, end_s, pp = _frontend_fixture(S, T, "bench_obs")
    r = fe.query_range(q, start_s, 60, end_s, pp)
    if r.error:
        return {"series": S, "error": r.error[:200]}
    st = {"series": S}
    # the attribution payload itself must be live before we credit any
    # overhead number: phases populated, scan counters nonzero
    d = r.stats.to_dict()
    st["stats_phases_ok"] = bool(
        d["phases"]["exec_s"] > 0 and d["samplesScanned"] > 0
        and d["phases"]["parse_s"] >= 0 and "cache" in d)

    dur_s = 1.0 if quick else 2.0
    errors = []

    def pump():
        counts = []
        stop_t = time.perf_counter() + dur_s

        def client():
            n = 0
            while time.perf_counter() < stop_t:
                res = fe.query_range(q, start_s, 60, end_s, pp)
                if res.error is not None:
                    # surface, don't swallow (same stance as the
                    # query_frontend stage): a thread dying silently
                    # would ship a passing-looking overhead number
                    errors.append(res.error)
                    break
                n += 1
            counts.append(n)

        threads = [threading.Thread(target=client) for _ in range(8)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return sum(counts) / max(time.perf_counter() - t0, 1e-9)

    on, off = [], []
    try:
        for _ in range(2 if quick else 3):
            um.set_spans_enabled(True)
            on.append(pump())
            um.set_spans_enabled(False)
            off.append(pump())
    finally:
        um.set_spans_enabled(True)
    if errors:
        st["error"] = f"pump: {errors[0]}"[:200]
        st["pump_errors"] = len(errors)
        return st
    on.sort(); off.sort()
    st["qps_spans_on"] = round(on[len(on) // 2], 1)
    st["qps_spans_off"] = round(off[len(off) // 2], 1)
    st["span_overhead_pct"] = round(
        100.0 * (st["qps_spans_off"] - st["qps_spans_on"])
        / max(st["qps_spans_off"], 1e-9), 2)
    return st


def measure_devicetelem(quick=False, series=None, iters=0):
    """ISSUE-18 acceptance: the per-chip device telemetry subsystem
    (utils/devicetelem.py) measured three ways:

      devicetelem_overhead_pct — the kernel ledger's tax on a concurrent
        8-thread ENGINE workload (every poll dispatches real kernels —
        a frontend cache-hit pump would never touch the ledger), telem
        on vs off in interleaved pairs, medians; gate <= 2%.
      devicetelem_fused_overhead_pct — the same tax on the flagship
        single-thread fused scan p50; gate <= 2%.
      the compile-storm drill — 12 distinct shapes through watched_call
        under one trace id: every compile must land in the ledger with
        shape + origin, fill jit_compile_seconds{kernel}, and flip the
        health `device` subsystem to degraded while sustained.
      devicetelem_mesh_reconciled (>= 2 devices only; the standalone
        `bench.py devicetelem` entry forces 8 virtual host devices) —
        per-device ledger mesh_fused counts reconcile 1:1 with
        mesh_fused_perdevice_dispatches and every mesh chip appears in
        the /admin/devices table.

    A parity check (the ?stats=true per-device split sums to the
    device_s phase) must hold before any overhead number is credited —
    a run whose overhead is low because attribution silently broke must
    not pass."""
    import threading

    import jax

    from filodb_tpu.utils import devicetelem as dt
    from filodb_tpu.utils.health import DEGRADED, HealthEvaluator
    from filodb_tpu.utils.metrics import registry, trace_context

    # flagship scale: the ledger's tax is a fixed few-tens-of-us per
    # dispatch, so the honest denominator is the flagship fused scan's
    # real query time, not a toy store whose 3 ms queries inflate the
    # same microseconds into a fake 2%
    S = series or (16_384 if quick else 65_536)
    T = 120
    fe, eng, q, start_s, end_s, pp = _frontend_fixture(
        S, T, "bench_devtelem")
    r = eng.query_range(q, start_s, 60, end_s, pp)   # cold: real kernels
    if r.error:
        return {"series": S, "error": r.error[:200]}
    st = {"series": S}
    d = r.stats.to_dict()
    split = sum(k["seconds"] for dev in d["devices"].values()
                for k in dev.values())
    dev_s = d["phases"]["device_s"]
    st["devicetelem_parity_ok"] = bool(
        abs(split - dev_s) <= max(1e-4, 0.02 * dev_s)
        and (dev_s == 0 or d["devices"]))

    # --- tax on the concurrent engine workload, telem on vs off ---
    dur_s = 1.5 if quick else 3.0
    errors = []

    def pump():
        counts = []
        stop_t = time.perf_counter() + dur_s

        def client():
            n = 0
            while time.perf_counter() < stop_t:
                res = eng.query_range(q, start_s, 60, end_s, pp)
                if res.error is not None:
                    # surface, don't swallow: a thread dying silently
                    # would ship a passing-looking overhead number
                    errors.append(res.error)
                    break
                n += 1
            counts.append(n)

        threads = [threading.Thread(target=client) for _ in range(8)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return sum(counts) / max(time.perf_counter() - t0, 1e-9)

    on, off = [], []
    try:
        pump()                       # discarded: thread/alloc warmup
        # alternate which arm goes first per pair — CPU frequency ramp
        # and cache warmup drift monotonically across the run, and a
        # fixed on-first order books all of that drift against the
        # ledger.  BEST-of-N per arm (timeit methodology): co-tenant
        # interference only ever subtracts throughput, so the max over
        # attempts compares the two arms on the clean machine instead
        # of on whichever arm a noise spike happened to land on.
        for i in range(4 if quick else 5):
            first_on = (i % 2 == 0)
            dt.set_enabled(first_on)
            (on if first_on else off).append(pump())
            dt.set_enabled(not first_on)
            (off if first_on else on).append(pump())
    finally:
        dt.set_enabled(True)
    if errors:
        st["error"] = f"pump: {errors[0]}"[:200]
        st["pump_errors"] = len(errors)
        return st
    st["devicetelem_qps_on"] = round(max(on), 1)
    st["devicetelem_qps_off"] = round(max(off), 1)
    st["devicetelem_overhead_pct"] = round(
        100.0 * (st["devicetelem_qps_off"] - st["devicetelem_qps_on"])
        / max(st["devicetelem_qps_off"], 1e-9), 2)

    # --- tax on the flagship single-thread fused scan ---
    # query-level PAIRED comparison: adjacent queries (ms apart) see
    # near-identical machine state, so per-pair relative deltas cancel
    # the drift that swamps independent p50s; the 20%-trimmed mean
    # drops GC/interrupt outliers without the median's tiny-sample
    # noise.  Order within a pair alternates so toggling cost (if any)
    # can't book against one arm.
    n_pairs = iters or (50 if quick else 40)
    diffs, on_ts, off_ts = [], [], []

    def one():
        t0 = time.perf_counter()
        res = eng.query_range(q, start_s, 60, end_s, pp)
        assert res.error is None, res.error
        return time.perf_counter() - t0

    try:
        for _ in range(3):                      # discarded warmup
            eng.query_range(q, start_s, 60, end_s, pp)
        for i in range(n_pairs):
            first_on = (i % 2 == 0)
            dt.set_enabled(first_on)
            a = one()
            dt.set_enabled(not first_on)
            b = one()
            on_t, off_t = (a, b) if first_on else (b, a)
            on_ts.append(on_t)
            off_ts.append(off_t)
            diffs.append((on_t - off_t) / off_t)
    finally:
        dt.set_enabled(True)
    on_ts.sort(); off_ts.sort(); diffs.sort()
    k = n_pairs // 5
    core = diffs[k:n_pairs - k]
    st["devicetelem_fused_p50_on_s"] = round(on_ts[n_pairs // 2], 5)
    st["devicetelem_fused_p50_off_s"] = round(off_ts[n_pairs // 2], 5)
    st["devicetelem_fused_overhead_pct"] = round(
        100.0 * sum(core) / max(len(core), 1), 2)

    # --- the compile-storm drill: attributable and health-visible ---
    import jax.numpy as jnp
    storm_fn = jax.jit(lambda x: (x * 2.0).sum())
    origin = "benchstorm" + "0" * 22
    n_storm = 12
    c0 = registry.counter("jit_compile_events", fn="bench_storm").value
    with trace_context(origin):
        for i in range(n_storm):
            x = jnp.zeros((i + 31,))
            dt.watched_call("bench_storm", storm_fn, f"S{i + 31}",
                            lambda x=x: storm_fn(x))
    compiled = int(registry.counter("jit_compile_events",
                                    fn="bench_storm").value - c0)
    st["devicetelem_storm_compiles"] = compiled
    mine = [e for e in dt.telem.recent(limit=200, kind="compile")
            if e["kernel"] == "bench_storm"]
    st["devicetelem_storm_attributed"] = bool(
        len(mine) >= n_storm
        and all(e["origin"] == origin and e["shape"] for e in mine))
    hist_count = 0
    for name, tags, value in registry.snapshot_samples():
        if name == "jit_compile_seconds_count" \
                and ("kernel", "bench_storm") in tags:
            hist_count = int(value)
    st["devicetelem_storm_hist_count"] = hist_count
    dv = HealthEvaluator().evaluate()["subsystems"]["device"]
    st["devicetelem_storm_health_degraded"] = bool(
        dv["status"] == DEGRADED and "compile_storm" in dv["reasons"])

    # --- per-chip placement reconcile (multi-device boxes only) ---
    n_dev = jax.local_device_count()
    st["devicetelem_devices"] = n_dev
    if n_dev >= 2:
        from filodb_tpu.core.index import Equals
        from filodb_tpu.ops.timewindow import make_window_ends
        from filodb_tpu.parallel.mesh import MeshExecutor, make_mesh
        n_time = 2 if n_dev % 2 == 0 and n_dev >= 4 else 1
        n_shard = n_dev // n_time
        total = 512 - (512 % n_shard)
        ms, START = _multichip_store("bench_devtelem_mesh", total, T,
                                     n_shard)
        mesh = make_mesh(n_shard, n_time, devices=jax.devices()[:n_dev])
        ex = MeshExecutor(ms, "bench_devtelem_mesh", mesh)
        end_ms = START + (T - 1) * 10_000
        packed = ex.lookup_and_pack(
            [Equals("_metric_", "request_total")], START, end_ms,
            by=("_ns_",), fn_name="rate")
        wends = make_window_ends(START + 600_000, end_ms, 60_000)

        def counts_by_dev():
            snap = dt.telem.snapshot(recent=0)
            return {dev: row["kernels"].get("mesh_fused",
                                            {}).get("count", 0)
                    for dev, row in snap["devices"].items()}

        before = counts_by_dev()
        pc0 = registry.counter("mesh_fused_perdevice_dispatches").value
        # the reconcile needs the PER-DEVICE kernel branch, which the
        # host-platform router diverts to ops/hostleaf (one host pass,
        # no per-chip dispatches) — interpret-mode Pallas restores the
        # real dispatch topology at this deliberately tiny scale
        had_interp = os.environ.get("FILODB_TPU_FUSED_INTERPRET")
        os.environ["FILODB_TPU_FUSED_INTERPRET"] = "1"
        try:
            for _ in range(3):
                ex.run_agg(packed, wends, range_ms=300_000,
                           fn_name="rate", agg_op="sum")
        finally:
            if had_interp is None:
                os.environ.pop("FILODB_TPU_FUSED_INTERPRET", None)
            else:
                os.environ["FILODB_TPU_FUSED_INTERPRET"] = had_interp
        pc_delta = int(registry.counter(
            "mesh_fused_perdevice_dispatches").value - pc0)
        after = counts_by_dev()
        deltas = {dev: after.get(dev, 0) - before.get(dev, 0)
                  for dev in after}
        touched = {dev for dev, v in deltas.items() if v > 0}
        st["devicetelem_mesh_perdevice_dispatches"] = pc_delta
        st["devicetelem_mesh_devices_touched"] = len(touched)
        st["devicetelem_mesh_reconciled"] = bool(
            pc_delta > 0 and sum(deltas.values()) == pc_delta
            and len(touched) >= 2)

    st["devicetelem_gate_ok"] = bool(
        st["devicetelem_overhead_pct"] <= 2.0
        and st["devicetelem_fused_overhead_pct"] <= 2.0
        and st["devicetelem_parity_ok"]
        and compiled >= 10
        and st["devicetelem_storm_attributed"]
        and st["devicetelem_storm_hist_count"] >= 10
        and st["devicetelem_storm_health_degraded"]
        and st.get("devicetelem_mesh_reconciled", True))
    return st


def measure_activequeries(quick=False, series=None):
    """ISSUE-13 acceptance: live query introspection.

    Two halves ride the one-line JSON:
      activequeries_overhead_pct — the registry's tax on the
        query_frontend concurrent-QPS workload (8 threads polling one
        panel), registry ON vs OFF in interleaved pairs (gate: <= 2%).
      the kill drill — a long COLD two-node query (all data flushed to
        the column store; every leaf demand-pages) is listed in the
        registry with live phase/counters on the coordinator AND the
        remote node, then killed mid-execution: the client gets the
        structured query_canceled, the concurrency slot frees (a
        follow-up query admits without queue wait), and the remote
        leaf's counters stop advancing (registry drains) within 250 ms.
    """
    import threading

    from filodb_tpu.config import FilodbSettings
    from filodb_tpu.query.activequeries import active_queries
    from filodb_tpu.query.frontend import QueryFrontend

    st = {}
    # --- half 1: registry overhead on the concurrent-QPS workload ---
    # cache and singleflight are DISABLED for the pump: a cache hit or
    # dedup follower never registers (by design — it pays two thread-
    # local writes), so the honest tax measurement needs every query to
    # take the registration path: scheduler slot -> engine -> exec tree.
    # The pump scale is pinned SMALL (per-query a few ms): the ratio
    # needs thousands of queries per window to resolve a 2% gate — at
    # 65k series a cache-off query costs ~1 s on CPU, so a 2 s pump
    # would measure ~20 queries of noise, not a tax
    S = series or 2_048
    fe0, eng, q, start_s, end_s, pp = _frontend_fixture(S, 120, "bench_aq")
    cfg = FilodbSettings()
    cfg.query.result_cache_enabled = False
    cfg.query.singleflight_enabled = False
    cfg.query.tenant_usage_enabled = False
    fe = QueryFrontend(eng, config=cfg)
    r = fe.query_range(q, start_s, 60, end_s, pp)
    if r.error:
        return {"series": S, "error": r.error[:200]}
    st["series"] = S
    dur_s = 1.0 if quick else 3.0
    errors = []

    def pump():
        counts = []
        stop_t = time.perf_counter() + dur_s

        def client():
            n = 0
            while time.perf_counter() < stop_t:
                res = fe.query_range(q, start_s, 60, end_s, pp)
                if res.error is not None:
                    errors.append(res.error)
                    break
                n += 1
            counts.append(n)

        threads = [threading.Thread(target=client) for _ in range(8)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return sum(counts) / max(time.perf_counter() - t0, 1e-9)

    on, off = [], []
    try:
        # alternate which mode leads each pair: a monotone warm-up
        # drift across the run must not systematically favor the
        # second-of-pair mode
        for i in range(2 if quick else 5):
            for enabled in ((True, False) if i % 2 == 0
                            else (False, True)):
                active_queries.configure(enabled=enabled)
                (on if enabled else off).append(pump())
    finally:
        active_queries.configure(enabled=True)
    if errors:
        st["error"] = f"pump: {errors[0]}"[:200]
        return st
    on.sort(); off.sort()
    st["qps_registry_on"] = round(on[len(on) // 2], 1)
    st["qps_registry_off"] = round(off[len(off) // 2], 1)
    st["activequeries_overhead_pct"] = round(
        100.0 * (st["qps_registry_off"] - st["qps_registry_on"])
        / max(st["qps_registry_off"], 1e-9), 2)

    # --- half 2: the end-to-end kill drill ---
    drill = _activequeries_kill_drill(quick=quick)
    st.update(drill)
    st["activequeries_gate_ok"] = bool(
        drill.get("activequeries_kill_structured")
        and drill.get("activequeries_listed_remote")
        and drill.get("activequeries_slot_freed")
        and (quick or (st["activequeries_overhead_pct"] <= 2.0
                       and drill.get("activequeries_stop_ms", 1e9)
                       <= 250.0)))
    return st


def _activequeries_kill_drill(quick=False):
    """Two in-process nodes over the real cross-node transport, every
    shard COLD (flushed to a column store, memstore recovered from the
    index only), a frontend coordinator with ONE concurrency slot — the
    'query eating the node' scenario the runbook kills."""
    import threading

    from filodb_tpu.config import FilodbSettings
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.core.store import InMemoryColumnStore, InMemoryMetaStore
    from filodb_tpu.gateway.router import split_batch_by_shard
    from filodb_tpu.ingest.generator import gauge_batch
    from filodb_tpu.parallel.shardmapper import (ShardEvent, ShardMapper,
                                                 SpreadProvider)
    from filodb_tpu.parallel.transport import (NodeQueryServer,
                                               RemoteNodeDispatcher)
    from filodb_tpu.query.activequeries import active_queries
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.query.frontend import QueryFrontend
    from filodb_tpu.query.planner import SingleClusterPlanner
    from filodb_tpu.query.rangevector import PlannerParams

    S = 1_024 if quick else 8_192
    T = 240
    num_shards = 4
    mapper = ShardMapper(num_shards)
    spread = SpreadProvider(default_spread=1)
    owner = {s: ("nodeA" if s < num_shards // 2 else "nodeB")
             for s in range(num_shards)}
    batch = gauge_batch(S, T)
    cold_stores = {}
    for node in ("nodeA", "nodeB"):
        cs, meta = InMemoryColumnStore(), InMemoryMetaStore()
        warm = TimeSeriesMemStore(column_store=cs, meta_store=meta)
        for s, n in owner.items():
            if n == node:
                warm.setup("prometheus", s)
                mapper.update_from_event(
                    ShardEvent("IngestionStarted", "prometheus", s, n))
        for s, sub in split_batch_by_shard(batch, mapper, spread).items():
            if owner[s] == node:
                warm.get_shard("prometheus", s).ingest(sub)
        for s, n in owner.items():
            if n == node:
                warm.get_shard("prometheus", s).flush_all_groups()
        # the COLD node: index recovered, zero resident samples — every
        # query demand-pages through the cancellable loop
        cold = TimeSeriesMemStore(column_store=cs, meta_store=meta)
        for s, n in owner.items():
            if n == node:
                cold.setup("prometheus", s).recover_index()
        cold_stores[node] = cold
    servers = {n: NodeQueryServer(st_).start()
               for n, st_ in cold_stores.items()}
    dispatchers = {n: RemoteNodeDispatcher(*srv.address)
                   for n, srv in servers.items()}
    planner = SingleClusterPlanner(
        "prometheus", mapper, spread,
        dispatcher_factory=lambda s: dispatchers[owner[s]])
    eng = QueryEngine("prometheus", TimeSeriesMemStore(), mapper,
                      planner=planner)
    cfg = FilodbSettings()
    cfg.query.max_concurrent_queries = 1
    cfg.query.result_cache_enabled = False
    cfg.query.tenant_usage_enabled = False
    fe = QueryFrontend(eng, config=cfg)
    pp = PlannerParams(sample_limit=2_000_000_000,
                       scan_limit=2_000_000_000)
    s0 = 1_600_000_000
    out = {}
    res_box = {}

    def victim():
        res_box["res"] = fe.query_range(
            "avg by (_ns_)(avg_over_time(heap_usage[5m]))",
            s0 + 300, 30, s0 + (T - 1) * 10, pp)

    try:
        t = threading.Thread(target=victim)
        t.start()
        # wait for the distributed query to be LIVE: the coordinator
        # entry past the queue AND a remote-role entry with counters
        listed_remote = False
        coord_ent = None
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            ents = active_queries.entries()
            for e in ents:
                if e.role == "frontend" and e.phase in ("executing",
                                                        "gathering"):
                    coord_ent = e
                if e.role == "remote":
                    listed_remote = True
            if coord_ent is not None and listed_remote:
                break
            time.sleep(0.002)
        out["activequeries_listed_remote"] = bool(
            coord_ent is not None and listed_remote)
        if coord_ent is None:
            out["activequeries_error"] = \
                "victim query never reached execution"
            return out
        t_kill = time.perf_counter()
        fe_kill = active_queries.kill(coord_ent.query_id, reason="admin")
        out["activequeries_kill_fanout_nodes"] = \
            len(fe_kill.get("remoteNodes", []))
        t.join(timeout=30)
        out["activequeries_kill_to_client_ms"] = round(
            (time.perf_counter() - t_kill) * 1e3, 1)
        res = res_box.get("res")
        out["activequeries_kill_structured"] = bool(
            res is not None and res.error is not None
            and res.error.startswith("query_canceled"))
        # remote leaves must STOP: all entries under the id drain (their
        # counters cannot advance after deregistration)
        stop_deadline = time.monotonic() + 5.0
        while active_queries.get(coord_ent.query_id) \
                and time.monotonic() < stop_deadline:
            time.sleep(0.002)
        out["activequeries_stop_ms"] = round(
            (time.perf_counter() - t_kill) * 1e3, 1)
        out["activequeries_remote_drained"] = \
            not active_queries.get(coord_ent.query_id)
        # the slot freed: a follow-up query admits with no queue wait
        # (1-slot semaphore — a leaked slot would park it for the full
        # ask timeout)
        res2 = fe.query_range("count(heap_usage)", s0 + 300, 60,
                              s0 + 600, pp)
        out["activequeries_followup_queue_wait_s"] = round(
            res2.stats.queue_wait_s, 4)
        out["activequeries_slot_freed"] = bool(
            res2.error is None and res2.stats.queue_wait_s < 0.5)
    finally:
        for srv in servers.values():
            srv.stop()
    return out


def measure_selfmon(quick=False, series=None):
    """ISSUE-10 acceptance: self-scrape meta-monitoring must cost <= 2%
    of the concurrent-QPS number at the default `selfmon.interval_s`.
    Same 8-thread dashboard-repeat workload as the query_frontend /
    observability stages, measured in interleaved pairs with the
    self-scrape loop ON vs OFF.  Each ON pump window contains exactly
    ONE scrape (the loop's immediate first scrape — including its
    result-cache invalidation, the expensive part: the write moves the
    append horizon, so the next re-poll per thread recomputes the grid
    tail), so the raw pair delta is the cost of one scrape amortized
    over the pump window.  Steady state runs one scrape per
    `selfmon.interval_s` (default 15 s), so the headline
    `selfmon_overhead_pct` normalizes the raw delta by
    pump_window / interval; the raw number rides along as
    `selfmon_overhead_raw_pct`.  Plus the scrape itself timed directly
    (`selfmon_scrape_p50_s`) and a sanity check that the scraped series
    actually ARE queryable through PromQL — a run whose overhead is low
    because the scrape silently wrote nothing must not pass."""
    import threading

    from filodb_tpu.config import SelfMonConfig
    from filodb_tpu.utils.selfmon import SelfScraper

    S = series or (4_096 if quick else 65_536)
    T = 120
    fe, eng, q, start_s, end_s, pp = _frontend_fixture(S, T, "bench_selfmon")
    r = fe.query_range(q, start_s, 60, end_s, pp)
    if r.error:
        return {"series": S, "error": r.error[:200]}
    st = {"series": S}

    # --- the scrape itself, timed directly (no loop thread)
    scraper = SelfScraper(eng.source, "bench_selfmon",
                          node_name="bench",
                          interval_s=SelfMonConfig().interval_s)
    times = []
    for _ in range(3 if quick else 7):
        t0 = time.perf_counter()
        n = scraper.scrape_once()
        times.append(time.perf_counter() - t0)
    times.sort()
    st["selfmon_scrape_p50_s"] = round(times[len(times) // 2], 5)
    st["selfmon_scrape_series"] = n
    if n <= 0:
        st["error"] = "self-scrape wrote zero series"
        return st

    # --- the scraped series must be PromQL-queryable via the ordinary
    # engine path (the entire point of self-scraping); +1 s because the
    # instant API floors to whole seconds and looks back, never forward
    chk = eng.query_instant("selfmon_samples_total", int(time.time()) + 1)
    if chk.error or chk.num_series == 0:
        st["error"] = (f"self-scraped series not queryable: "
                       f"{chk.error or 'no series'}")[:200]
        return st

    dur_s = 1.0 if quick else 2.0
    errors = []

    def pump():
        counts = []
        stop_t = time.perf_counter() + dur_s

        def client():
            c = 0
            while time.perf_counter() < stop_t:
                res = fe.query_range(q, start_s, 60, end_s, pp)
                if res.error is not None:
                    errors.append(res.error)
                    break
                c += 1
            counts.append(c)

        threads = [threading.Thread(target=client) for _ in range(8)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return sum(counts) / max(time.perf_counter() - t0, 1e-9)

    on, off = [], []
    for _ in range(2 if quick else 3):
        live = SelfScraper(eng.source, "bench_selfmon",
                           node_name="bench",
                           interval_s=SelfMonConfig().interval_s)
        live.start()                     # immediate first scrape, then 15 s
        try:
            on.append(pump())
        finally:
            live.stop()
        off.append(pump())
    if errors:
        st["error"] = f"pump: {errors[0]}"[:200]
        return st
    on.sort(); off.sort()
    st["selfmon_qps_on"] = round(on[len(on) // 2], 1)
    st["selfmon_qps_off"] = round(off[len(off) // 2], 1)
    raw = 100.0 * (st["selfmon_qps_off"] - st["selfmon_qps_on"]) \
        / max(st["selfmon_qps_off"], 1e-9)
    st["selfmon_overhead_raw_pct"] = round(raw, 2)
    # one scrape per pump window measured -> one per interval_s steady
    # state: normalize the per-scrape cost to the default cadence
    interval = SelfMonConfig().interval_s
    st["selfmon_interval_s"] = interval
    st["selfmon_overhead_pct"] = round(raw * dur_s / interval, 2)
    st["selfmon_gate_ok"] = bool(st["selfmon_overhead_pct"] <= 2.0)
    return st


def measure_qos(quick=False, series=None):
    """ISSUE-14 acceptance: multi-tenant QoS under overload — the
    noisy-neighbor drill.

    Five tenants share one frontend (cache + singleflight OFF so every
    query contends for real scheduler slots): four well-behaved
    tenants poll their own dashboard panel back-to-back; the abuser
    floods the frontend from 8 threads at full concurrency with a
    dashboard storm of short panels (the classic noisy-neighbor shape:
    thousands of cheap queries saturating every slot).  Phases:

      idle  — the good tenants poll alone: their baseline p99.
      noisy — the abuser floods while the good tenants keep polling.

    Gate (qos_gate_ok): the good tenants' p99 stays within 1.5x of
    their idle p99 (weighted-fair dispatch kept their slots coming),
    the abuser receives structured `tenant_overloaded` 429s WITH a
    Retry-After value (adaptive shedding engaged — never silent queue
    starvation), and the abuser never hits `query_timeout` (doomed
    queries are shed at admission, not left to die in the queue).

    Scheduler capacity scales with the host's cores: concurrent
    EXECUTIONS share the machine, and a capacity past the core count
    measures CPU timeslicing, not admission fairness (on the 1-core
    bench boxes capacity is 1 — the drill's point is who gets the next
    slot, not how many run at once).
    """
    import threading

    from filodb_tpu.config import FilodbSettings
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import gauge_part_keys
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.query.frontend import QueryFrontend
    from filodb_tpu.query.rangevector import PlannerParams

    S = series or (1_024 if quick else 4_096)
    T = 120
    START = 1_600_000_000_000
    goods = ["good0", "good1", "good2", "good3"]
    tenants = goods + ["abuser"]
    capacity = max(1, min(8, os.cpu_count() or 1))
    st = {"series": S, "tenants": len(tenants),
          "qos_capacity": capacity}
    ms = TimeSeriesMemStore()
    sh = ms.setup("bench_qos", 0)
    row_base = np.arange(S, dtype=np.float64)[:, None]
    for ws in tenants:
        keys = gauge_part_keys(S, metric="request_total", ws=ws)
        for t0 in range(0, T, 40):
            n = min(40, T - t0)
            ts2d = np.broadcast_to(
                START + (t0 + np.arange(n, dtype=np.int64)) * 10_000,
                (S, n))
            vals = (t0 + np.arange(n, dtype=np.float64))[None, :] * 5.0 \
                + row_base
            sh.ingest_columns("prom-counter", keys, ts2d,
                              {"count": vals}, offset=t0)
    eng = QueryEngine("bench_qos", ms)
    cfg = FilodbSettings()
    cfg.query.result_cache_enabled = False
    cfg.query.singleflight_enabled = False
    cfg.query.max_concurrent_queries = capacity
    cfg.query.tenant_max_queue_depth = 4
    fe = QueryFrontend(eng, config=cfg)
    pp = PlannerParams(sample_limit=2_000_000_000,
                       scan_limit=2_000_000_000)
    s0 = START // 1000
    start_s, end_s = s0 + 600, s0 + (T - 1) * 10
    ab_end_s = s0 + 660                   # the abuser's short panel

    def q_of(ws):
        return f'sum by (_ns_)(rate(request_total{{_ws_="{ws}"}}[5m]))'

    for ws in goods:                      # warm compile/mirror per shape
        r = fe.query_range(q_of(ws), start_s, 60, end_s, pp)
        if r.error:
            st["error"] = f"warmup[{ws}]: {r.error}"[:200]
            return st
    r = fe.query_range(q_of("abuser"), start_s, 60, ab_end_s, pp)
    if r.error:
        st["error"] = f"warmup[abuser]: {r.error}"[:200]
        return st
    dur_s = 1.5 if quick else 5.0
    good_errors = []

    good_waits = []

    def good_loop(ws, lats, stop_t):
        while time.perf_counter() < stop_t:
            t0 = time.perf_counter()
            res = fe.query_range(q_of(ws), start_s, 60, end_s, pp)
            lats.append(time.perf_counter() - t0)
            good_waits.append(res.stats.queue_wait_s)
            if res.error is not None:
                good_errors.append(f"{ws}: {res.error}"[:200])
                return

    def run_goods(extra=()):
        lats = {ws: [] for ws in goods}
        stop_t = time.perf_counter() + dur_s
        threads = [threading.Thread(target=good_loop,
                                    args=(ws, lats[ws], stop_t))
                   for ws in goods]
        threads += list(extra)
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return [x for ws in goods for x in lats[ws]]

    def p99(xs):
        xs = sorted(xs)
        return xs[min(int(0.99 * len(xs)), len(xs) - 1)] if xs else 0.0

    # --- phase 1: idle baseline ---
    idle = run_goods()
    good_waits.clear()                   # keep only the noisy phase's
    # --- phase 2: the abuser floods at full concurrency ---
    abuse = {"shed": 0, "timeouts": 0, "completed": 0, "other": 0,
             "retry_bad": 0}
    alock = threading.Lock()
    stop_abuse = threading.Event()

    import random as _random

    def abuser_loop():
        rng = _random.Random(id(threading.current_thread()))
        while not stop_abuse.is_set():
            res = fe.query_range(q_of("abuser"), start_s, 60, ab_end_s,
                                 pp)
            err = res.error or ""
            with alock:
                if not err:
                    abuse["completed"] += 1
                elif err.startswith("tenant_overloaded"):
                    abuse["shed"] += 1
                    if not (getattr(res, "retry_after_s", 0.0) > 0.0):
                        abuse["retry_bad"] += 1
                elif err.startswith("query_timeout"):
                    abuse["timeouts"] += 1
                else:
                    abuse["other"] += 1
            if err.startswith("tenant_overloaded"):
                # a minimally-compliant client: back off briefly on a
                # 429 (NOT the full Retry-After — the drill needs
                # sustained flood pressure, just not a shed spin-loop
                # that would measure interpreter contention, not QoS);
                # jittered so 8 threads don't wake in lockstep
                time.sleep(0.02 * (0.5 + rng.random()))

    flood = [threading.Thread(target=abuser_loop, daemon=True)
             for _ in range(8)]
    for t in flood:
        t.start()
    time.sleep(0.3)                      # let the flood saturate first
    noisy = run_goods()
    stop_abuse.set()
    for t in flood:
        t.join(timeout=5)
    if good_errors:
        st["error"] = f"good tenant failed: {good_errors[0]}"[:200]
        return st
    st["qos_good_polls_idle"] = len(idle)
    st["qos_good_polls_noisy"] = len(noisy)
    # how much of the noisy-phase latency was SCHEDULER wait (vs the
    # execution itself) — the diagnostic that says whether a ratio
    # regression is queueing or CPU contention
    st["qos_good_queue_wait_p99_s"] = round(p99(list(good_waits)), 5)
    st["qos_good_p99_idle_s"] = round(p99(idle), 5)
    st["qos_good_p99_noisy_s"] = round(p99(noisy), 5)
    st["qos_p99_ratio"] = round(
        p99(noisy) / max(p99(idle), 1e-9), 3)
    st["qos_abuser_shed"] = abuse["shed"]
    st["qos_abuser_timeouts"] = abuse["timeouts"]
    st["qos_abuser_completed"] = abuse["completed"]
    st["qos_abuser_other_errors"] = abuse["other"]
    st["qos_shed_retry_after_ok"] = bool(abuse["shed"] > 0
                                         and abuse["retry_bad"] == 0)
    # correctness halves of the gate always hold; the p99 ratio is
    # judged at FULL scale only (quick's short phases are too noisy)
    st["qos_gate_ok"] = bool(
        abuse["shed"] > 0 and abuse["timeouts"] == 0
        and abuse["other"] == 0 and st["qos_shed_retry_after_ok"]
        and abuse["completed"] > 0
        and (quick or st["qos_p99_ratio"] <= 1.5))
    return st


def measure_ruler(quick=False, series=None):
    """PR 5 acceptance: the ruler as a precompute engine.  A group of 8
    aggregation rules (the dashboard-panel shapes) evaluates against the
    live store at ticks spanning the query window, then:

      ruler_eval_p50_s         — one full group iteration (8 instant
                                 queries through the frontend + columnar
                                 write-back) at the acceptance scale
      recorded_query_speedup_x — the SAME dashboard aggregate served
                                 from the recorded series vs evaluating
                                 the raw expression over the range
                                 (gate: >= 10x — the entire point of
                                 recording rules)
      ruler_overhead_pct       — frontend QPS with the ruler's
                                 evaluation loops live vs stopped (the
                                 standing-query tax on serving traffic,
                                 result-cache invalidation churn from
                                 the write-backs included)
    """
    import threading

    from filodb_tpu.config import RulesConfig
    from filodb_tpu.rules import MemstoreSink, Ruler, WebhookNotifier
    from filodb_tpu.rules.config import Rule, RuleGroup

    S = series or (8_192 if quick else 262_144)
    T = 120
    fe, eng, q, start_s, end_s, pp = _frontend_fixture(S, T, "bench_ruler")
    rules = tuple(
        Rule(name, expr, "recording") for name, expr in [
            ("ns:request_total:rate5m",
             "sum by (_ns_)(rate(request_total[5m]))"),
            ("dc:request_total:rate5m",
             "sum by (dc)(rate(request_total[5m]))"),
            ("total:request_total:rate5m",
             "sum(rate(request_total[5m]))"),
            ("ns:request_total:avg_rate5m",
             "avg by (_ns_)(rate(request_total[5m]))"),
            ("ns:request_total:max_rate5m",
             "max by (_ns_)(rate(request_total[5m]))"),
            ("dc:request_total:increase1m",
             "sum by (dc)(increase(request_total[1m]))"),
            ("ns:request_total:series",
             "count by (_ns_)(rate(request_total[5m]))"),
            ("total:recorded:rate5m",      # 2nd-order: reads rule 1
             "sum(ns:request_total:rate5m)"),
        ])
    group = RuleGroup("bench", 30.0, rules)
    ruler = Ruler(fe, MemstoreSink(eng.source, "bench_ruler"),
                  groups=[group], config=RulesConfig(),
                  notifier=WebhookNotifier(sleep=lambda s: None))
    st = {"series": S, "rules": len(rules)}

    # materialize the recorded series across the query window (30s
    # ticks), timing each full iteration
    ticks = list(range(start_s, end_s + 1, 30))
    durs = []
    for ts in ticks:
        t0 = time.perf_counter()
        if not ruler.evaluate_group("bench", ts=ts):
            bad = [r["lastError"]
                   for r in ruler.rules_payload()["groups"][0]["rules"]
                   if r["lastError"]]
            return {**st, "error": f"rule eval failed: {bad[:1]}"[:200]}
        durs.append(time.perf_counter() - t0)
    durs.sort()
    st["iterations"] = len(ticks)
    st["ruler_eval_p50_s"] = round(durs[len(durs) // 2], 5)

    # the dashboard aggregate from the recorded series vs the raw expr
    def p50(fn, n=5):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            res = fn()
            if res.error:
                raise RuntimeError(res.error)
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    raw_p50 = p50(lambda: eng.query_range(
        "sum by (_ns_)(rate(request_total[5m]))", start_s, 30, end_s, pp))
    rec_p50 = p50(lambda: eng.query_range(
        "ns:request_total:rate5m", start_s, 30, end_s, pp))
    st["raw_aggregate_p50_s"] = round(raw_p50, 5)
    st["recorded_aggregate_p50_s"] = round(rec_p50, 5)
    st["recorded_query_speedup_x"] = round(raw_p50 / max(rec_p50, 1e-9), 1)

    # serving overhead: frontend QPS with the evaluation loops live vs
    # stopped.  The ruler's clock is pinned into the data window so the
    # rules do real work; a short interval keeps several iterations
    # inside the measurement window.
    dur_s = 2.0 if quick else 4.0
    errors = []

    def pump(seconds=None):
        counts = []
        stop_t = time.perf_counter() + (seconds or dur_s)

        def client():
            n = 0
            while time.perf_counter() < stop_t:
                res = fe.query_range(q, start_s, 60, end_s, pp)
                if res.error is not None:
                    errors.append(res.error)
                    break
                n += 1
            counts.append(n)

        threads = [threading.Thread(target=client) for _ in range(4)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return sum(counts) / max(time.perf_counter() - t0, 1e-9)

    pump(1.0)     # warm the serving path: off/on must differ only by
    qps_off = pump()  # the ruler, not by who ran first on cold caches
    interval = max(1.0, 2.0 * st["ruler_eval_p50_s"])
    offset = end_s - time.time()
    live = Ruler(fe, MemstoreSink(eng.source, "bench_ruler"),
                 groups=[RuleGroup("bench", interval, rules)],
                 config=RulesConfig(),
                 notifier=WebhookNotifier(sleep=lambda s: None),
                 clock=lambda: time.time() + offset)
    live.start()
    try:
        # the loop's first tick lands anywhere up to one interval +
        # stagger after start(): measure over >= 1.5 intervals so the
        # window is guaranteed to contain evaluations — otherwise a
        # short pump can miss the phase entirely and report ~0 overhead
        qps_on = pump(max(dur_s, 1.5 * interval))
    finally:
        live.stop()
    if errors:
        st["error"] = f"pump: {errors[0]}"[:200]
        return st
    st["qps_ruler_off"] = round(qps_off, 1)
    st["qps_ruler_on"] = round(qps_on, 1)
    st["ruler_overhead_pct"] = round(
        100.0 * (qps_off - qps_on) / max(qps_off, 1e-9), 2)
    return st


def _multichip_block(START, t0, n, r0, r1):
    """One [r1-r0, n] (timestamps, values) block of the multichip
    stage's monotone counter workload starting at scrape index t0 —
    the SINGLE home of the value formula, shared by the store builder
    and the acceptance probe's tail ingest (a divergent tail would
    introduce counter resets and invalidate the pack-memo check)."""
    import numpy as np
    ts2d = np.broadcast_to(
        START + (t0 + np.arange(n, dtype=np.int64)) * 10_000, (r1 - r0, n))
    vals = (t0 + np.arange(n, dtype=np.float64))[None, :] * 5.0 \
        + np.arange(r0, r1, dtype=np.float64)[:, None]
    return ts2d, vals


def _multichip_store(dataset, total_series, T, n_shard):
    """n_shard-sharded memstore of monotone counter series — the
    multichip stage's workload, split contiguously across shards so the
    mesh's 'shard' axis maps 1:1 onto memstore shards."""
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import counter_batch

    START = 1_600_000_000_000
    ms = TimeSeriesMemStore()
    base = counter_batch(total_series, 1, start_ms=START)
    per = total_series // n_shard
    for s in range(n_shard):
        sh = ms.setup(dataset, s)
        r0 = s * per
        r1 = total_series if s == n_shard - 1 else r0 + per
        keys = base.part_keys[r0:r1]
        for t0 in range(0, T, 40):
            n = min(40, T - t0)
            ts2d, vals = _multichip_block(START, t0, n, r0, r1)
            sh.ingest_columns("prom-counter", keys, ts2d, {"count": vals},
                              offset=t0)
    return ms, START


def measure_multichip(quick=False, series=None, iters=0):
    """Multi-chip fused scan stage (ISSUE 6 / ROADMAP item 2): the
    flagship `sum by (rate())` aggregate over an n-device
    ('shard' x 'time') mesh through MeshExecutor.run_agg, which routes
    fused-eligible aggregates through PER-DEVICE dispatch of the
    single-chip kernel + partial-only merges (parallel/mesh.py) — never
    the fused-in-shard_map composition that inverted the single-chip win
    ~30x (MULTICHIP_r05.json: warm 25.3 s vs 0.88 s general).

    Emits (one-line JSON keys):
      multichip_fused_warm_s   — warm p50 of the per-device fused route
      multichip_general_warm_s — warm p50 of the general mesh path over
                                 the SAME pack (the shard_map XLA path)
      multichip_scaling_x      — single-device warm p50 / mesh warm p50
                                 for the same total workload
    Gate: fused warm <= general warm (the inversion is dead), checked in
    `multichip_inversion_gone`.

    A box that claims TPU but exposes < 2 local devices FAILS this stage
    (raises — recorded as a loud stage error, never a silent skip): one
    chip must not masquerade as a scaling measurement.
    Host platforms need XLA_FLAGS=--xla_force_host_platform_device_count
    (the `bench.py multichip` standalone entry sets it before jax init).
    """
    import jax

    from filodb_tpu.core.index import Equals
    from filodb_tpu.ops import agg as agg_ops
    from filodb_tpu.ops.timewindow import make_window_ends
    from filodb_tpu.parallel.mesh import (MeshExecutor, make_mesh,
                                          distributed_window_agg)
    from filodb_tpu.utils.metrics import registry

    n_dev = jax.local_device_count()
    platform = jax.default_backend()
    if n_dev < 2:
        raise RuntimeError(
            f"multichip stage needs >= 2 local devices, have {n_dev} on "
            f"backend {platform!r}"
            + ("" if platform == "tpu" else " — run `python bench.py "
               "multichip` (forces 8 virtual host devices) or set "
               "XLA_FLAGS=--xla_force_host_platform_device_count=8"))
    n_time = 2 if n_dev % 2 == 0 and n_dev >= 4 else 1
    n_shard = n_dev // n_time
    total = series or (8_192 if quick else 262_144)
    total -= total % n_shard
    T = 120                              # 20 min of 10s scrapes
    iters = iters or (3 if quick else 5)
    st = {"devices": n_dev, "mesh": f"{n_shard}x{n_time}",
          "series": total, "samples_per_series": T}

    ms, START = _multichip_store("bench_multichip", total, T, n_shard)
    mesh = make_mesh(n_shard, n_time, devices=jax.devices()[:n_dev])
    ex = MeshExecutor(ms, "bench_multichip", mesh)
    filters = [Equals("_metric_", "request_total")]
    end_ms = START + (T - 1) * 10_000
    wends = make_window_ends(START + 600_000, end_ms, 60_000)
    range_ms = 300_000
    span = total * (T - 60)              # samples inside the queried span

    def p50(fn, n=iters):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    packed = ex.lookup_and_pack(filters, START, end_ms, by=("_ns_",),
                                fn_name="rate")
    k0 = registry.counter("mesh_fused_kernel").value
    h0 = registry.counter("mesh_fused_host").value

    def fused_once():
        out, _ = ex.run_agg(packed, wends, range_ms=range_ms,
                            fn_name="rate", agg_op="sum")
        return out

    t0 = time.perf_counter()
    fused_res = fused_once()             # compile + warm every cache
    st["fused_cold_s"] = round(time.perf_counter() - t0, 4)
    took_kernel = registry.counter("mesh_fused_kernel").value > k0
    took_host = registry.counter("mesh_fused_host").value > h0
    st["multichip_fused_route"] = ("kernel" if took_kernel
                                   else "host" if took_host
                                   else "general(fallback)")
    fused_warm = p50(fused_once)
    st["multichip_fused_warm_s"] = round(fused_warm, 5)
    st["multichip_samples_per_sec"] = round(span / fused_warm, 1)
    st["multichip_perdevice_dispatches"] = \
        registry.counter("mesh_fused_perdevice_dispatches").value

    # general mesh path (shard_map XLA kernels) over the SAME pack — the
    # 0.88 s side of the MULTICHIP_r05 inversion
    from jax.sharding import NamedSharding, PartitionSpec as P
    wends_p, W = ex._prep_wends(packed, wends)
    wends_dev = jax.device_put(wends_p, NamedSharding(mesh, P("time")))

    def general_once():
        partials = distributed_window_agg(
            mesh, packed.ts_off, packed.values, packed.group_ids,
            wends_dev, range_ms=range_ms, fn_name="rate", agg_op="sum",
            num_groups=packed.num_groups, base_ms=packed.base_ms,
            vbase=packed.vbase, precorrected=packed.precorrected,
            dense=packed.dense)
        return np.asarray(agg_ops.present("sum", partials))[:, :W]

    t0 = time.perf_counter()
    general_res = general_once()
    st["general_cold_s"] = round(time.perf_counter() - t0, 4)
    general_warm = p50(general_once)
    st["multichip_general_warm_s"] = round(general_warm, 5)
    # the gate needs dispatch EVIDENCE, not just timing: a silent
    # fallback to the general path makes fused ~= general and would
    # pass a coin-flip comparison with zero per-device work measured
    st["multichip_inversion_gone"] = bool(
        (took_kernel or took_host) and fused_warm <= general_warm)
    err = float(np.nanmax(np.abs(np.asarray(fused_res, np.float64)
                                 - general_res)
                          / np.maximum(np.abs(general_res), 1e-9)))
    st["max_rel_err_vs_general"] = round(err, 9)

    # scaling: same total workload on ONE device (1x1 mesh, 1-shard
    # store) — the denominator every later device should shrink
    ms1, _ = _multichip_store("bench_multichip1", total, T, 1)
    mesh1 = make_mesh(1, 1, devices=jax.devices()[:1])
    ex1 = MeshExecutor(ms1, "bench_multichip1", mesh1)
    packed1 = ex1.lookup_and_pack(filters, START, end_ms, by=("_ns_",),
                                  fn_name="rate")

    def single_once():
        out, _ = ex1.run_agg(packed1, wends, range_ms=range_ms,
                             fn_name="rate", agg_op="sum")
        return out

    single_once()                        # compile
    single_warm = p50(single_once)
    st["multichip_single_device_warm_s"] = round(single_warm, 5)
    st["multichip_scaling_x"] = round(single_warm / fused_warm, 3)

    # ISSUE-6 acceptance: a re-poll after value-only ingest must hit the
    # packing-layout memo (repack out of the warm-query profile)
    m0 = registry.counter("mesh_pack_memo_hits").value
    from filodb_tpu.ingest.generator import counter_batch as _cb
    tail = _cb(total, 1, start_ms=START)
    per = total // n_shard
    for s in range(n_shard):
        r0 = s * per
        r1 = total if s == n_shard - 1 else r0 + per
        ts2d, vals = _multichip_block(START, T, 1, r0, r1)
        ms.get_shard("bench_multichip", s).ingest_columns(
            "prom-counter", tail.part_keys[r0:r1], ts2d, {"count": vals},
            offset=T)
    ex.lookup_and_pack(filters, START, end_ms + 10_000, by=("_ns_",),
                       fn_name="rate")
    st["multichip_pack_memo_hits"] = \
        registry.counter("mesh_pack_memo_hits").value - m0
    return st


def run_chaos(quick=False, series=None):
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

    Full phase detail lands in SOAK_CHAOS.json."""
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
    with open(os.path.join(REPO_DIR, "SOAK_CHAOS.json"), "w") as f:
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


def measure_longrange(quick=False, series=None):
    """Historical-tier stage (ISSUE 8): multi-day persisted dataset,
    compacted into columnar segments, served through the cold DeviceMirror
    region and the tier-stitched planner.

    One-line JSON keys:
      longrange_cold_scan_samples_per_sec — FIRST scan over the persisted
          range (segments decoded + uploaded on the query's critical
          path); gate (a): >= 1/10 of the in-memory scan number
      longrange_warm_cold_ratio — cold-region-resident re-scan vs the
          in-memory number; gate (b): >= 0.5
      longrange_stitch_identical — a query_range spanning
          raw + downsample + persisted stitched into one grid,
          bit-identical to an all-in-memory reference store holding the
          same samples; gate (c): True
    """
    import shutil
    import tempfile

    from filodb_tpu.core.devicecache import ColdSegmentCache
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.core.partkey import PartKey
    from filodb_tpu.downsample import (DownsampleClusterPlanner,
                                       DownsampledTimeSeriesStore,
                                       ShardDownsampler)
    from filodb_tpu.parallel.shardmapper import ShardEvent, ShardMapper
    from filodb_tpu.persist.compactor import SegmentCompactor
    from filodb_tpu.persist.localstore import LocalDiskColumnStore
    from filodb_tpu.persist.segments import PersistedTier, SegmentStore
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.query.planner import SingleClusterPlanner
    from filodb_tpu.query.planners import (LongTimeRangePlanner,
                                           PersistedClusterPlanner)

    DS = "prometheus"
    S = series or (512 if quick else 4_096)
    INTERVAL = 300_000                   # 5m scrape == ds resolution
    # 24h windows: long-retention sizing (doc/operations.md runbook) —
    # per-segment fixed costs amortize over 288 samples/series
    WINDOW = (6 if quick else 24) * 3600 * 1000
    days = 1 if quick else 4
    NS = days * 24 * 3600 * 1000 // INTERVAL
    T0 = 1_600_000_000_000 - (1_600_000_000_000 % WINDOW)
    ts_grid = T0 + np.arange(NS, dtype=np.int64) * INTERVAL
    pks = [PartKey("m", (("inst", f"i{i}"), ("_ws_", "bench"),
                         ("_ns_", "lr")))
           for i in range(S)]
    # small integers: every op exact in f32, so the stitch gate can demand
    # BIT-identical results across tiers
    vals = (np.arange(S)[:, None] % 97 * 10.0
            + (np.arange(NS) % 11)[None, :])

    def fill(shard, t_slice=slice(None)):
        tg = ts_grid[t_slice]
        shard.ingest_columns("gauge", pks,
                             np.broadcast_to(tg, (S, len(tg))),
                             {"value": vals[:, t_slice]})

    out = {"series": S, "samples": int(S * NS), "days": days}
    root = tempfile.mkdtemp(prefix="filodb-longrange-")
    try:
        # persisted side: ingest -> flush -> compact -> segments
        cs = LocalDiskColumnStore(root)
        ms_disk = TimeSeriesMemStore(column_store=cs)
        sh = ms_disk.setup(DS, 0)
        sh.shard_downsampler = ShardDownsampler(resolutions=(INTERVAL,))
        fill(sh)
        t0 = time.perf_counter()
        sh.flush_all_groups()
        out["flush_s"] = round(time.perf_counter() - t0, 2)
        ds_store = DownsampledTimeSeriesStore(DS, column_store=cs,
                                              resolutions=(INTERVAL,))
        ds_store.setup_shard(0)
        ds_store.ingest_downsample_batches(
            0, sh.shard_downsampler.result_batches())
        seg_store = SegmentStore(root)
        comp = SegmentCompactor(cs, seg_store, DS, 1, window_ms=WINDOW,
                                closed_lag_ms=0)
        t0 = time.perf_counter()
        n_segs = comp.compact_all(now_ms=int(ts_grid[-1]) + 10 * WINDOW)
        out["compact_s"] = round(time.perf_counter() - t0, 2)
        out["segments"] = n_segs
        # drop the OLDEST segment: that span is downsample-only, so the
        # stitch query genuinely crosses all three tiers
        metas = seg_store.list(DS, 0)
        seg_store.remove(metas[0])
        ds_only_end = metas[0].end_ms
        cache = ColdSegmentCache(8 << 30, use_placer=False)
        tier = PersistedTier(seg_store, DS, 1, cache)
        # live memory: the last window only (the working set)
        tail_from = NS - WINDOW // INTERVAL
        ms_live = TimeSeriesMemStore()
        fill(ms_live.setup(DS, 0), slice(tail_from, None))
        earliest_raw = int(ts_grid[tail_from])
        # reference: everything in one in-memory store
        ms_ref = TimeSeriesMemStore()
        fill(ms_ref.setup(DS, 0))

        mapper = ShardMapper(1)
        mapper.update_from_event(
            ShardEvent("IngestionStarted", DS, 0, "n"))

        class _Src:
            def __init__(self, store):
                self.store = store

            def get_shard(self, dataset, shard_num):
                if "::ds::" in dataset:
                    return ds_store.get_shard(dataset, shard_num)
                return self.store.get_shard(dataset, shard_num)

            def shards_for(self, dataset):
                return self.store.shards_for(dataset)

        ltr = LongTimeRangePlanner(
            SingleClusterPlanner(DS, mapper),
            DownsampleClusterPlanner(ds_store, mapper),
            earliest_raw_time_fn=lambda: earliest_raw,
            latest_downsample_time_fn=lambda: 1 << 62,
            persisted_planner=PersistedClusterPlanner(DS, mapper, tier),
            persisted_range_fn=tier.range)
        eng_tier = QueryEngine(DS, _Src(ms_live), mapper, planner=ltr)
        eng_ref = QueryEngine(DS, _Src(ms_ref), mapper,
                              planner=SingleClusterPlanner(DS, mapper))

        from filodb_tpu.query.rangevector import PlannerParams
        params = PlannerParams(sample_limit=1 << 40)
        q = "sum(m)"
        # persisted-only span (cold scan target): past the ds-only head,
        # before the in-memory tail
        cold_start_s = ds_only_end // 1000 + 1800
        cold_end_s = earliest_raw // 1000 - 1800
        step_s = 600

        def run(eng, start_s, end_s):
            t0 = time.perf_counter()
            res = eng.query_range(q, start_s, step_s, end_s,
                                  planner_params=params)
            dt = time.perf_counter() - t0
            if res.error:
                raise RuntimeError(f"longrange query failed: {res.error}")
            return res, dt

        # in-memory FIRST-scan number over the SAME span: a fresh engine
        # with no device mirror yet, so the hot path pays its own page-in
        # (the [S, T] upload) on the query's critical path — the
        # apples-to-apples comparator for the cold tier's first scan
        res, dt = run(eng_ref, cold_start_s, cold_end_s)
        mem_first_sps = res.stats.samples_scanned / max(dt, 1e-9)
        out["longrange_mem_first_samples_per_sec"] = round(mem_first_sps, 1)
        # warm in-memory number (mirror resident, caches hot): best of 3
        mem_sps = 0.0
        for _ in range(3):
            res, dt = run(eng_ref, cold_start_s, cold_end_s)
            mem_sps = max(mem_sps,
                          res.stats.samples_scanned / max(dt, 1e-9))
        out["longrange_mem_samples_per_sec"] = round(mem_sps, 1)
        # cold FIRST-EVER scan: segments decode + upload + first-shape XLA
        # compiles on the critical path (recorded, not gated — production
        # restarts deserialize compiles from the persistent cache the
        # server wires in apply_jax_runtime)
        res, dt = run(eng_tier, cold_start_s, cold_end_s)
        out["longrange_cold_first_samples_per_sec"] = round(
            res.stats.samples_scanned / max(dt, 1e-9), 1)
        out["longrange_cold_verdict"] = res.stats.cold_tier
        out["longrange_cold_samples_paged"] = res.stats.samples_paged
        # the GATED cold number: fresh cold region + fresh tier over the
        # same segment files (every block re-decodes and re-uploads), warm
        # code paths — the restart-with-compile-cache shape
        tier2 = PersistedTier(seg_store, DS, 1,
                              ColdSegmentCache(8 << 30, use_placer=False))
        ltr2 = LongTimeRangePlanner(
            SingleClusterPlanner(DS, mapper),
            DownsampleClusterPlanner(ds_store, mapper),
            earliest_raw_time_fn=lambda: earliest_raw,
            latest_downsample_time_fn=lambda: 1 << 62,
            persisted_planner=PersistedClusterPlanner(DS, mapper, tier2),
            persisted_range_fn=tier2.range)
        eng_tier2 = QueryEngine(DS, _Src(ms_live), mapper, planner=ltr2)
        res, dt = run(eng_tier2, cold_start_s, cold_end_s)
        if res.stats.cold_tier != "cold_paged":
            raise RuntimeError("gated cold scan did not page")
        cold_sps = res.stats.samples_scanned / max(dt, 1e-9)
        out["longrange_cold_scan_samples_per_sec"] = round(cold_sps, 1)
        # warm re-scan: cold region resident (best of 3, like mem)
        warm_sps = 0.0
        for _ in range(3):
            res, dt = run(eng_tier, cold_start_s, cold_end_s)
            warm_sps = max(warm_sps,
                           res.stats.samples_scanned / max(dt, 1e-9))
        out["longrange_warm_verdict"] = res.stats.cold_tier
        out["longrange_warm_samples_per_sec"] = round(warm_sps, 1)
        # gate (a) compares first-scan to first-scan (both tiers pay
        # their page-in); the warm-based ratio rides along for context
        out["longrange_cold_vs_mem_ratio"] = round(
            cold_sps / max(mem_first_sps, 1e-9), 3)
        out["longrange_cold_vs_mem_warm_ratio"] = round(
            cold_sps / max(mem_sps, 1e-9), 3)
        out["longrange_warm_cold_ratio"] = round(
            warm_sps / max(mem_sps, 1e-9), 3)
        # stitched three-tier query vs the all-in-memory reference:
        # bit-identical over the same samples
        full_start_s = int(ts_grid[0]) // 1000 + 1800
        full_end_s = int(ts_grid[-1]) // 1000
        identical = True
        for qq in ("m", "sum(m)"):
            rt = eng_tier.query_range(qq, full_start_s, step_s, full_end_s,
                                      planner_params=params)
            rr = eng_ref.query_range(qq, full_start_s, step_s, full_end_s,
                                     planner_params=params)
            if rt.error or rr.error:
                raise RuntimeError(rt.error or rr.error)
            a = {k: (w, v) for k, w, v in rt.series()}
            b = {k: (w, v) for k, w, v in rr.series()}
            if set(a) != set(b):
                identical = False
                continue
            for k in a:
                wa, va = a[k]
                wb, vb = b[k]
                nn = np.isnan(va) & np.isnan(vb)
                if not (np.array_equal(wa, wb)
                        and np.array_equal(va[~nn], vb[~nn])
                        and np.array_equal(np.isnan(va), np.isnan(vb))):
                    identical = False
        out["longrange_stitch_identical"] = bool(identical)
        out["longrange_gate_cold_ok"] = bool(
            cold_sps >= 0.1 * mem_first_sps)
        out["longrange_gate_warm_ok"] = bool(warm_sps >= 0.5 * mem_sps)
        out["longrange_gate_ok"] = bool(
            out["longrange_gate_cold_ok"] and out["longrange_gate_warm_ok"]
            and identical)
        # LRU bound proof rides the stage too: sweep with a budget half
        # the working set and counter-assert the booked bytes
        small = ColdSegmentCache(
            max(m.device_bytes_estimate() for m in seg_store.list(DS, 0))
            * 3 // 2, use_placer=False)
        tier_small = PersistedTier(seg_store, DS, 1, small)
        over = False
        for m in seg_store.list(DS, 0):
            tier_small.get_block(m)
            over = over or small.bytes_booked > small.limit_bytes
        out["longrange_lru_bounded"] = bool(not over)
        return out
    finally:
        shutil.rmtree(root, ignore_errors=True)


def host_baselines(ts_row, vals, gids, wends, range_ms, span):
    """CPU reference numbers: vectorized numpy, per-window Python-loop
    iterator, and the single-core C iterator (the compiled
    ChunkedWindowIterator stand-in — no JVM exists in this environment,
    so this is the honest 'iterator on one core' comparator; see
    native/filodb_native.cc filodb_iter_rate and BASELINE.md)."""
    G = int(gids.max()) + 1
    Sv = min(vals.shape[0], 65_536)
    t0 = time.perf_counter()
    numpy_vectorized_baseline(ts_row, vals[:Sv].astype(np.float64),
                              gids[:Sv], G, wends.astype(np.int64), range_ms)
    vec_sps = (Sv * span) / (time.perf_counter() - t0)
    Sb = min(vals.shape[0], 512)
    t0 = time.perf_counter()
    numpy_iterator_baseline(ts_row, vals[:Sb].astype(np.float64),
                            wends.astype(np.int64), range_ms)
    it_sps = (Sb * span) / (time.perf_counter() - t0)
    c_sps = 0.0
    from filodb_tpu import native
    if native.lib is not None:
        Sc = min(vals.shape[0], 16_384)
        t0 = time.perf_counter()
        native.lib.iter_rate(ts_row, vals[:Sc].astype(np.float64),
                             wends.astype(np.int64), range_ms)
        c_sps = (Sc * span) / (time.perf_counter() - t0)
    return vec_sps, it_sps, c_sps


def measure_distexec(quick=False, series=None):
    """ISSUE-15 acceptance: aggregation pushdown + streaming distributed
    execution.

    Three proofs ride the one-line JSON:
      distexec_wire_bytes_ratio — a fan-out `sum by (...)` over FOUR
        data nodes with node-level pushdown ON vs the ship-everything
        baseline (map phase on the coordinator, full per-shard series
        blocks crossing the wire), measured from QueryStats.wire_bytes.
        Gate: >= 10x fewer bytes, results BIT-identical (integer-valued
        samples keep every partial-sum component exact, so the merge
        tree's association cannot perturb a bit).
      distexec_frontend_peak_rss_mb — a long-range-shaped (30-day-grid-
        sized, W~3k steps) single-node query whose [S, W] reply streams
        as bounded CRC frames into a preallocated block, traced-peak
        (tracemalloc, numpy included) vs the materialize-everything
        single-frame baseline.  Gate: streamed peak under a FIXED
        budget (3/4 of the bytes the children shipped + 2 MB frame
        slack) that the materialize-everything baseline exceeds.
      distexec_pushdown_speedup_x — wall p50 of the fan-out aggregation
        pushed vs ship-everything (reported, not gated: the wire is
        loopback here; real networks only widen it).
    """
    import tracemalloc

    from filodb_tpu.config import settings
    from filodb_tpu.ingest.generator import gauge_batch
    from filodb_tpu.parallel.testcluster import make_fanout_cluster
    from filodb_tpu.query.rangevector import PlannerParams

    st = {}
    START = 1_600_000_020_000
    S0 = START // 1000

    def as_map(res):
        out = {}
        for b in res.blocks:
            vals = np.asarray(b.values)
            for i, k in enumerate(b.keys):
                out[k] = (tuple(np.asarray(b.wends).tolist()),
                          vals[i].tobytes())
        return out

    # ---- half 1: 4-node fan-out aggregation, pushed vs ship-everything
    S = series or (2_048 if quick else 16_384)
    T = 360 if quick else 720                    # 10 s scrape samples
    batch = gauge_batch(S, T, start_ms=START, metric="bench_gauge")
    batch.columns["value"] = np.floor(batch.columns["value"])
    cluster = make_fanout_cluster([batch], num_shards=8,
                                  nodes=("n1", "n2", "n3", "n4"),
                                  with_truth=False)
    st["series"] = S
    try:
        q = "sum by (dc)(bench_gauge)"
        rng_args = (S0 + 600, 60, S0 + 600 + 60 * (60 if quick else 110))
        runs = {}
        iters = 3 if quick else 5
        for push in (True, False):
            # the off side is the SHIP-EVERYTHING strawman (full per-
            # series blocks over the wire), not pushdown=False — that
            # merely restores the per-shard [G, W] partial dispatch
            pp = PlannerParams(aggregation_pushdown=push,
                               ship_raw_series=not push)
            walls, wires, frames, verdicts, rmap = [], [], [], [], None
            for _ in range(iters):
                t0 = time.perf_counter()
                r = cluster.engine.query_range(q, *rng_args, pp)
                walls.append(time.perf_counter() - t0)
                if r.error:
                    st["error"] = f"fanout ({push=}): {r.error}"[:300]
                    return st
                wires.append(r.stats.wire_bytes)
                frames.append(r.stats.streamed_frames)
                verdicts.append((r.stats.pushdown_pushed,
                                 r.stats.pushdown_fallback))
                rmap = as_map(r)
            runs[push] = {"wall_p50": sorted(walls)[len(walls) // 2],
                          "wire": sorted(wires)[len(wires) // 2],
                          "frames": max(frames),
                          "verdicts": verdicts[-1], "map": rmap}
        on, off = runs[True], runs[False]
        st["distexec_wire_on_bytes"] = int(on["wire"])
        st["distexec_wire_off_bytes"] = int(off["wire"])
        st["distexec_wire_bytes_ratio"] = round(
            off["wire"] / max(on["wire"], 1), 1)
        st["distexec_pushdown_speedup_x"] = round(
            off["wall_p50"] / max(on["wall_p50"], 1e-9), 2)
        st["distexec_bit_identical"] = bool(on["map"] == off["map"]
                                            and on["map"])
        st["distexec_pushed_nodes"] = int(on["verdicts"][0])
    finally:
        cluster.stop()

    # ---- half 2: long-range streamed aggregation vs materialize-all.
    # A 30-day-grid-sized [S, W] block lives on ONE data node; the
    # coordinator runs `sum by (...)` over ship_raw_series children (the
    # full-series-over-the-wire shape raw selectors and non-pushable
    # ops always have, forced here for a deterministic bound).  Baseline
    # buffers each whole reply + decode copies; streamed mode folds
    # every CRC frame through map+reduce as it arrives, so the
    # coordinator never holds more than a frame and the [G, W] partial.
    Sw = 512 if quick else 1_024
    W = 1_440 if quick else 2_880               # 30-day-grid-sized [S, W]
    wide = gauge_batch(Sw, W, start_ms=START, step_ms=60_000,
                       metric="wide_gauge")
    wide.columns["value"] = np.floor(wide.columns["value"])
    one = make_fanout_cluster([wide], num_shards=2, nodes=("n1",),
                              with_truth=False)
    saved_frame = settings().query.stream_frame_bytes
    try:
        qw = "sum by (_ns_)(wide_gauge)"
        wargs = (S0 + 600, 60, S0 + 60 * W)
        pp = PlannerParams(aggregation_pushdown=False,
                           ship_raw_series=True,
                           sample_limit=200_000_000)
        peaks = {}
        maps = {}
        shipped = 0
        # frame bound scaled to the stage size so quick mode streams too
        # (production default stays 2 MiB; the bound just has to be well
        # under one shard's reply for the fold to engage)
        frame = (256 << 10) if quick else (1 << 20)
        for mode, frame_bytes in (("baseline", 0), ("streamed", frame)):
            settings().query.stream_frame_bytes = frame_bytes
            one.engine.query_range(qw, *wargs, pp)      # warm the path
            tracemalloc.start()
            tracemalloc.reset_peak()
            r = one.engine.query_range(qw, *wargs, pp)
            _, peak = tracemalloc.get_traced_memory()
            tracemalloc.stop()
            if r.error:
                st["error"] = f"longrange ({mode}): {r.error}"[:300]
                return st
            peaks[mode] = peak
            maps[mode] = as_map(r)
            shipped = max(shipped, r.stats.wire_bytes)
            if mode == "streamed":
                st["distexec_stream_frames"] = int(r.stats.streamed_frames)
        st["distexec_frontend_peak_rss_mb"] = round(
            peaks["streamed"] / (1 << 20), 1)
        st["distexec_baseline_peak_rss_mb"] = round(
            peaks["baseline"] / (1 << 20), 1)
        # FIXED budget: 3/4 of the bytes the children ship plus frame
        # slack — the materialize-everything baseline necessarily
        # exceeds the shipped bytes (whole reply buffer + decode
        # copies), while the fold holds a frame and a [G, W] partial
        budget_mb = round(shipped / (1 << 20) * 0.75 + 2.0, 1)
        st["distexec_rss_budget_mb"] = budget_mb
        st["distexec_stream_identical"] = bool(
            maps["streamed"] == maps["baseline"] and maps["streamed"])
    finally:
        settings().query.stream_frame_bytes = saved_frame
        one.stop()

    st["distexec_gate_ok"] = bool(
        st["distexec_wire_bytes_ratio"] >= 10.0
        and st["distexec_bit_identical"]
        and st["distexec_stream_identical"]
        and st["distexec_stream_frames"] > 1
        and st["distexec_frontend_peak_rss_mb"] <= budget_mb
        and st["distexec_baseline_peak_rss_mb"] > budget_mb)
    return st


def measure_index(quick=False, series=None):
    """ISSUE-16 acceptance: the bitmap posting engine under high
    cardinality.

    Builds a zipf-skewed shard index (10M part keys at full scale; the
    head metric/namespace own most series, a 100k-value instance label
    carries the regex load), then measures:
      index_build_keys_per_sec — add_partition throughput (reported).
      index_equals_lookup_p50_ms — point lookups on the high-cardinality
        label via part_ids_from_filters.  Gate: < 1 ms.
      index_regex_plan_p50_ms — first-plan `=~` queries over DISTINCT
        patterns (alternation / prefix / trigram-contains / class
        shapes), so the per-(label,pattern) memo cannot flatter the
        number; the one-time trigram-map build is warmed first and
        reported separately.  Gate: p50 < 10 ms.
      index_churn_rss_growth_pct — a 3x-shard-size churn soak on a
        separate index (evict-all / refill generations with ever-
        increasing pids, tombstone-threshold compaction like the
        index_compaction job); full-occupancy memory_bytes() of the
        last generation vs the first.  Gate: <= 10%.
    """
    from filodb_tpu.core.index import (Equals, EqualsRegex, MAX_TIME,
                                       PartKeyIndex)
    from filodb_tpu.core.partkey import PartKey

    st = {}
    S = series or (1_000_000 if quick else 10_000_000)
    st["index_series"] = S
    rng = np.random.default_rng(16)

    def p50_ms(xs):
        return round(sorted(xs)[len(xs) // 2] * 1000.0, 3)

    # ---- zipf label universe.  kv tuples are interned so 10M PartKeys
    # share label-pair objects (the index stores refs, not copies)
    n_inst = min(100_000, max(1_000, S // 100))
    metrics = [f"metric_{i:04d}" for i in range(1_000)]
    nss = [f"ns-{i:04d}" for i in range(5_000)]
    wss = [f"ws-{i:02d}" for i in range(50)]
    insts = [f"host-{i:06d}-dc{i % 8}" for i in range(n_inst)]
    ns_kv = [("_ns_", v) for v in nss]
    ws_kv = [("_ws_", v) for v in wss]
    inst_kv = [("instance", v) for v in insts]
    gen_kv = [("gen", f"g{i}") for i in range(S // n_inst + 1)]
    mi = np.minimum(rng.zipf(1.3, size=S) - 1, len(metrics) - 1).tolist()
    ni = np.minimum(rng.zipf(1.2, size=S) - 1, len(nss) - 1).tolist()
    wi = np.minimum(rng.zipf(1.5, size=S) - 1, len(wss) - 1).tolist()

    idx = PartKeyIndex()
    t0 = time.perf_counter()
    for i in range(S):
        pk = PartKey(metrics[mi[i]],
                     (ns_kv[ni[i]], ws_kv[wi[i]],
                      gen_kv[i // n_inst], inst_kv[i % n_inst]))
        idx.add_partition(i, pk, 1_000_000)
    build_s = time.perf_counter() - t0
    st["index_build_keys_per_sec"] = int(S / build_s)
    st["index_memory_bytes"] = int(idx.memory_bytes())

    # ---- equals point lookups on the 100k-value label
    eq_walls = []
    for k in rng.integers(0, n_inst, size=(100 if quick else 300)):
        f = [Equals("instance", insts[int(k)])]
        t0 = time.perf_counter()
        ids = idx.part_ids_from_filters(f, 0, MAX_TIME)
        eq_walls.append(time.perf_counter() - t0)
        assert ids.size == S // n_inst, "equals lookup lost series"
    st["index_equals_lookup_p50_ms"] = p50_ms(eq_walls)

    # ---- regex planning: warm the one-time sorted-dict + trigram build
    # (amortized per label until its value set changes), then time
    # DISTINCT first-plan patterns so the memo can't answer
    t0 = time.perf_counter()
    idx.part_ids_from_filters(
        [EqualsRegex("instance", ".*zz-warmup-zz.*")], 0, MAX_TIME)
    st["index_trigram_build_ms"] = round(
        (time.perf_counter() - t0) * 1000.0, 1)
    pats = []
    for k in range(8):
        a, b = (k * 37) % n_inst, (n_inst - 1 - k * 53) % n_inst
        pats.append(f"{insts[a]}|{insts[b]}")           # alternation
    for k in range(8):
        pats.append(f"host-{(k * 997) % n_inst:06d}.*")  # narrow prefix
    for k in range(8):
        pats.append(f"host-{k:04d}.*")                  # ~100-value prefix
    for k in range(8):
        pats.append(f".*{k:03d}-dc{k % 8}")             # trigram contains
    for k in range(4):
        pats.append(f"host-0{k:02d}[0-4].*")            # prefix + class
    plan_walls = []
    for pat in pats:
        f = [EqualsRegex("instance", pat)]
        t0 = time.perf_counter()
        idx.part_ids_from_filters(f, 0, MAX_TIME)
        plan_walls.append(time.perf_counter() - t0)
    st["index_regex_plan_p50_ms"] = p50_ms(plan_walls)
    st["index_regex_plan_max_ms"] = round(max(plan_walls) * 1000.0, 3)
    memo_walls = []
    for pat in pats:
        f = [EqualsRegex("instance", pat)]
        t0 = time.perf_counter()
        idx.part_ids_from_filters(f, 0, MAX_TIME)
        memo_walls.append(time.perf_counter() - t0)
    st["index_regex_memo_p50_ms"] = p50_ms(memo_walls)
    del idx, mi, ni, wi

    # ---- churn soak: 3 evict-all/refill generations, pids never reused
    # (the shard assigns monotonically), compaction driven through the
    # same maybe_compact(threshold) entry point as the background job
    churn_n = 80_000 if quick else 400_000
    st["index_churn_series"] = churn_n
    cidx = PartKeyIndex()
    pid = 0
    mems = []
    for gen in range(3):
        pids = []
        for i in range(churn_n):
            pk = PartKey(metrics[i % 200],
                         (ns_kv[i % 500], ws_kv[i % 50],
                          inst_kv[i % n_inst]))
            cidx.add_partition(pid, pk, 1_000_000)
            pids.append(pid)
            pid += 1
        mems.append(cidx.memory_bytes())        # full-occupancy footprint
        if gen < 2:
            for j, p in enumerate(pids):
                cidx.remove_partition(p)
                if (j + 1) % 50_000 == 0:
                    cidx.maybe_compact(8_192)
            cidx.maybe_compact(1)               # the job's final sweep
            if cidx.tombstone_count:
                st["error"] = "churn compaction left tombstones"
                return st
    st["index_churn_rss_growth_pct"] = round(
        (mems[-1] - mems[0]) / mems[0] * 100.0, 1)
    st["index_gate_ok"] = bool(
        st["index_regex_plan_p50_ms"] < 10.0
        and st["index_equals_lookup_p50_ms"] < 1.0
        and st["index_churn_rss_growth_pct"] <= 10.0)
    return st


def measure_exprfuse(quick=False, series=None, iters=0):
    """ISSUE-17 acceptance: whole-expression device compilation.

    An 8-panel mixed dashboard (aggregated rates, a rank aggregation,
    and two vector-matching binary ops) over ONE shared working set,
    evaluated two ways:

      optimized — engine.query_range_batch with query.exprfuse on: the
        expression compiler walks every tree, runs each in-process
        leaf's fused preflight under one batch-gather-memo scope (the
        working set is scanned, offset-gridded, and counter-corrected
        ONCE for the whole dashboard), and the leaves evaluate as [G, W]
        partials — no per-node [S, W] intermediates.
      per-node assembly — one query_range per panel with exprfuse off
        and leaf fusion diverted (host_route_max_samples=0): every plan
        node materializes its full output (the leaf ships raw series,
        PeriodicSamplesMapper materializes [S, W] per panel, the
        aggregate reduces it), and every panel re-gathers the store.

    Gate (full scale): optimized p50 >= 5x faster, results BIT-identical
    (same wends, same value bytes, per series key).  The stage pins the
    host-route configuration on every backend — it measures expression-
    level fusion and scan sharing; the kernel-dispatch amortization has
    its own stage (dashboard_batch) and on-chip capture.
    """
    from filodb_tpu.config import settings
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import counter_batch
    from filodb_tpu.query.engine import QueryEngine
    from filodb_tpu.query.rangevector import PlannerParams
    from filodb_tpu.utils.metrics import registry

    S = series or (8_192 if quick else 1_048_576)
    T = 96                               # 16 min of 10s scrapes
    START = 1_600_000_000_000
    st = {"series": S, "samples_per_series": T, "panels": 8}
    qconf = settings().query
    sconf = settings().store
    saved = (qconf.exprfuse_enabled, qconf.host_route_max_samples,
             sconf.device_mirror_enabled,
             os.environ.get("FILODB_TPU_FORCE_HOST_ROUTE"),
             qconf.default_timeout_s)
    try:
        # deterministic routing for the comparison: no device mirror
        # (its snapshot gather is a third path, measured elsewhere),
        # host-routed fused leaves on any backend; no query deadline
        # (the 1M-series COLD baseline pass on a host backend can
        # exceed the serving default — this is a bench, not a server)
        sconf.device_mirror_enabled = False
        qconf.default_timeout_s = 0.0
        os.environ["FILODB_TPU_FORCE_HOST_ROUTE"] = "1"
        ms = TimeSeriesMemStore()
        ms.setup("bench_exprfuse", 0)
        sh = ms.get_shard("bench_exprfuse", 0)
        base = counter_batch(S, 1, start_ms=START)
        row_base = np.arange(S, dtype=np.float64)[:, None]
        for t0 in range(0, T, 40):
            n = min(40, T - t0)
            ts2d = np.broadcast_to(
                START + (t0 + np.arange(n, dtype=np.int64)) * 10_000,
                (S, n))
            vals = (t0 + np.arange(n, dtype=np.float64))[None, :] * 5.0 \
                + row_base
            sh.ingest_columns("prom-counter", base.part_keys, ts2d,
                              {"count": vals}, offset=t0)
        eng = QueryEngine("bench_exprfuse", ms)
        pp = PlannerParams(sample_limit=2_000_000_000,
                           scan_limit=2_000_000_000)
        s0 = START // 1000
        args = (s0 + 600, 60, s0 + (T - 1) * 10)
        m = "request_total"
        panels = [
            f'sum by (_ns_)(rate({m}[5m]))',
            f'avg by (_ns_)(rate({m}[5m]))',
            f'max by (_ns_)(max_over_time({m}[5m]))',
            f'count by (_ns_)(rate({m}[5m]))',
            f'sum by (_ns_)(rate({m}[5m]))'
            f' / on (_ns_) count by (_ns_)(rate({m}[5m]))',
            f'sum by (_ns_)(increase({m}[5m]))',
            f'topk(3, sum by (_ns_)(rate({m}[5m])))',
            f'sum by (_ns_)(rate({m}[5m]))'
            f' > bool on (_ns_) avg by (_ns_)(rate({m}[5m]))',
        ]

        def as_map(res):
            out = {}
            for b in res.blocks:
                vals = np.asarray(b.values)
                for i, k in enumerate(b.keys):
                    out[k] = (tuple(np.asarray(b.wends).tolist()),
                              vals[i].tobytes())
            return out

        def p50(fn, n):
            ts = []
            for _ in range(n):
                t0 = time.perf_counter()
                fn()
                ts.append(time.perf_counter() - t0)
            ts.sort()
            return ts[len(ts) // 2]

        # --- optimized: one compiled batch over the dashboard
        qconf.exprfuse_enabled = True
        qconf.host_route_max_samples = 1 << 60
        memo0 = registry.counter("leaf_gather_memo_hits").value
        on = eng.query_range_batch(panels, *args, pp)       # warm
        for q, r in zip(panels, on):
            if r.error:
                st["exprfuse_error"] = f"batch: {q}: {r.error}"[:300]
                return st
        st["exprfuse_memo_hits"] = int(
            registry.counter("leaf_gather_memo_hits").value - memo0)
        st["exprfuse_fused"] = sum(r.stats.exprfuse_fused for r in on)
        st["exprfuse_degraded"] = sum(r.stats.exprfuse_degraded
                                      for r in on)
        on_iters = iters or (3 if quick else 5)
        st["exprfuse_p50_s"] = round(p50(
            lambda: eng.query_range_batch(panels, *args, pp), on_iters), 5)

        # --- per-node assembly: sequential, every node materializes
        qconf.exprfuse_enabled = False
        qconf.host_route_max_samples = 0
        off = [eng.query_range(q, *args, pp) for q in panels]    # warm
        for q, r in zip(panels, off):
            if r.error:
                st["exprfuse_error"] = f"per-node: {q}: {r.error}"[:300]
                return st
        off_iters = iters or 3
        st["exprfuse_baseline_p50_s"] = round(p50(
            lambda: [eng.query_range(q, *args, pp) for q in panels],
            off_iters), 5)

        st["exprfuse_speedup_x"] = round(
            st["exprfuse_baseline_p50_s"]
            / max(st["exprfuse_p50_s"], 1e-9), 2)
        maps_on = [as_map(r) for r in on]
        maps_off = [as_map(r) for r in off]
        st["exprfuse_identical"] = bool(
            maps_on == maps_off and any(m for m in maps_on))
        # quick's toy store can't amortize the one shared scan; the 5x
        # gate is judged at FULL scale only (the ratio still rides the
        # line), correctness gates always hold
        st["exprfuse_gate_ok"] = bool(
            st["exprfuse_identical"] and st["exprfuse_fused"] > 0
            and st["exprfuse_degraded"] == 0
            and (quick or st["exprfuse_speedup_x"] >= 5.0))
    finally:
        (qconf.exprfuse_enabled, qconf.host_route_max_samples,
         sconf.device_mirror_enabled) = saved[:3]
        qconf.default_timeout_s = saved[4]
        if saved[3] is None:
            os.environ.pop("FILODB_TPU_FORCE_HOST_ROUTE", None)
        else:
            os.environ["FILODB_TPU_FORCE_HOST_ROUTE"] = saved[3]
    return st


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("stage", nargs="?", default="",
                    choices=["", "chaos", "multichip", "wal", "longrange",
                             "selfmon", "replication", "ingesttrace",
                             "activequeries", "qos", "distexec", "index",
                             "exprfuse", "devicetelem", "objectstore",
                             "federation"],
                    help="optional standalone stage: 'federation' runs "
                         "the cross-cluster federation stage (two-"
                         "cluster testbench: pushed [G, W] cluster "
                         "partials and shipped series bit-identical to "
                         "a single-cluster truth, dead-cluster flagged "
                         "partial naming the cluster + breaker "
                         "recovery, pushed-vs-shipped wire ratio >= "
                         "1.2x) and exits nonzero on a gate failure; "
                         "'objectstore' runs "
                         "the disaggregated cold-tier stage (disk-kill "
                         "drill with byte-identical rebuild from shared "
                         "object store + WAL tail, elastic-read gate "
                         ">=1.8x QPS with 2 stateless query nodes, "
                         "dead-store flagged-partial degrade) and exits "
                         "nonzero on a gate failure; 'chaos' runs the "
                         "failure-domain chaos harness (SIGKILL one of "
                         "three RF-2 data nodes mid-traffic; gates "
                         "availability=1.0 with zero partials and zero "
                         "acked loss) and writes SOAK_CHAOS.json; "
                         "'replication' runs the in-process replication "
                         "stage (RF-2 vs RF-1 fan-out throughput, WAL-"
                         "segment catch-up drain, live shard handoff "
                         "under traffic) and exits nonzero on a gate "
                         "failure; "
                         "'multichip' runs the multi-device fused-scan "
                         "stage in-process (8 virtual devices on host "
                         "platforms) and exits nonzero if the fused "
                         "path loses to the general path; 'wal' runs "
                         "the durability stage (WAL on/off ingest, "
                         "replay, remote_write door, kill-mid-ingest "
                         "zero-acked-loss proof) and exits nonzero on "
                         "a gate failure; 'longrange' runs the "
                         "historical-tier stage (compacted segments, "
                         "cold DeviceMirror region, tier-stitched "
                         "planning) and exits nonzero when a cold-scan "
                         "or stitch gate fails; 'selfmon' runs the "
                         "self-scrape meta-monitoring stage (overhead "
                         "on concurrent QPS + scrape p50) and exits "
                         "nonzero when overhead exceeds 2%; "
                         "'ingesttrace' runs the write-path tracing "
                         "stage (span-pipeline tax on the remote_write "
                         "door, the stitched 2-node trace proof, the "
                         "wal.fsync fault-visibility drill) and exits "
                         "nonzero when tracing-on falls under 98% of "
                         "tracing-off or the trace/fault evidence is "
                         "missing; 'activequeries' runs the live-"
                         "introspection stage (registry tax on "
                         "concurrent QPS, gate <= 2%, plus the two-node "
                         "cold-query kill drill: structured "
                         "query_canceled, slot freed, remote drained "
                         "within 250 ms) and exits nonzero on a gate "
                         "failure; 'qos' runs the multi-tenant "
                         "noisy-neighbor stage (one abusive tenant "
                         "floods the frontend at full concurrency "
                         "while well-behaved tenants keep polling; "
                         "gates good-tenant p99 within 1.5x of idle "
                         "and the abuser receiving structured 429 + "
                         "Retry-After, never query_timeout) and exits "
                         "nonzero on a gate failure; 'index' runs the "
                         "high-cardinality bitmap-index stage (10M-key "
                         "zipf shard; gates regex first-plan p50 < 10 "
                         "ms, equals p50 < 1 ms, and a 3x churn soak "
                         "holding index memory within 10%) and exits "
                         "nonzero on a gate failure; 'exprfuse' runs "
                         "the whole-expression compilation stage (an "
                         "8-mixed-panel dashboard incl. vector-matching "
                         "binary ops over a 1M-series store, compiled "
                         "batch vs per-node assembly; gates >= 5x p50 "
                         "and bit-identical results) and exits nonzero "
                         "on a gate failure; 'devicetelem' runs the "
                         "device-telemetry stage on 8 virtual devices "
                         "(kernel-ledger tax on concurrent engine QPS "
                         "and on the flagship fused scan, both gated "
                         "<= 2%; a 12-shape compile-storm drill that "
                         "must be attributable in the ledger, fill "
                         "jit_compile_seconds, and flip device health; "
                         "per-chip mesh dispatch reconcile) and exits "
                         "nonzero on a gate failure")
    ap.add_argument("--quick", action="store_true",
                    help="small config for smoke runs")
    ap.add_argument("--series", type=int, default=0)
    ap.add_argument("--iters", type=int, default=0)
    ap.add_argument("--_worker", action="store_true",
                    help="internal: run the measurement in this process")
    ap.add_argument("--run-id", default="")
    ap.add_argument("--platform", default="default",
                    choices=["default", "cpu"],
                    help="internal: pin the jax platform for a worker run")
    return ap.parse_args(argv)


def assemble_result(platform, stages, vec_sps, it_sps, c_sps=0.0,
                    partial=False):
    """One JSON line from whatever stages completed.  The headline is the
    highest-throughput trusted stage — comparable round-over-round; on
    chip the 1M north-star stage wins this naturally (bigger batches
    amortize better), while the CPU fallback's relaxed-budget 1M point
    rides along in the north_star_* fields instead of deflating the
    headline."""
    best_name, best = None, None
    for name, st in stages.items():
        if "samples_per_sec" in st and (
                best is None or st["samples_per_sec"]
                > best["samples_per_sec"]):
            best_name, best = name, st
    result = {"metric": "promql_samples_scanned_per_sec",
              "unit": "samples/s", "platform": platform}
    if best is None:
        result.update({"value": 0.0, "vs_baseline": 0.0,
                       "error": "no stage produced a trusted number"})
    else:
        result.update({
            "value": best["samples_per_sec"],
            "vs_baseline": (round(best["samples_per_sec"] / vec_sps, 2)
                            if vec_sps else 0.0),
            "p50_query_latency_s": best["p50_s"],
            "kernel": best.get("kernel"),
            "series": best["series"], "windows": best["windows"],
            "groups": best["groups"], "headline_stage": best_name,
        })
        if vec_sps:
            result["baseline_samples_per_sec"] = round(vec_sps, 1)
            result["baseline_kind"] = \
                "vectorized numpy, same algorithm, host CPU"
        if it_sps:
            result["iterator_baseline_samples_per_sec"] = round(it_sps, 1)
            result["vs_iterator_baseline"] = \
                round(best["samples_per_sec"] / it_sps, 1)
        if c_sps:
            # the honest compiled-iterator comparator (single C core; no
            # JVM exists here — see BASELINE.md north-star note)
            result["iterator_c_samples_per_sec"] = round(c_sps, 1)
            result["vs_iterator_c"] = \
                round(best["samples_per_sec"] / c_sps, 1)
    ing = stages.get("ingest", {})
    if "ingest_samples_per_sec" in ing:
        # the host half of the pipeline, in the parsed line from round 1
        # (this PR's ISSUE: the driver must track ingest, not just scan)
        result["ingest_samples_per_sec"] = ing["ingest_samples_per_sec"]
        result["ingest_series"] = ing["series"]
    cov = stages.get("fused_coverage", {})
    for k in ("fused_coverage_dense", "fused_coverage_ragged"):
        if k in cov:
            result[k] = cov[k]
    db = stages.get("dashboard_batch", {})
    if "speedup_p50" in db:
        result["dashboard_batch_speedup"] = db["speedup_p50"]
    qf = stages.get("query_frontend", {})
    for k in ("concurrent_qps", "cached_repoll_p50_s", "cold_p50_s",
              "sequential_baseline_qps", "qps_vs_sequential",
              "repoll_ratio"):
        if k in qf:
            # the PR-2 serving acceptance pair (+ context): concurrent
            # dashboard QPS through the frontend and the warm re-poll p50
            result[k] = qf[k]
    obs = stages.get("observability", {})
    if "span_overhead_pct" in obs:
        # PR-3 acceptance: span+stats attribution overhead on the
        # query_frontend QPS number (gate: <= 5%)
        result["span_overhead_pct"] = obs["span_overhead_pct"]
        result["observability_stats_ok"] = obs.get("stats_phases_ok")
    aq = stages.get("activequeries", {})
    for k in ("activequeries_overhead_pct", "activequeries_gate_ok",
              "activequeries_kill_structured", "activequeries_stop_ms",
              "activequeries_slot_freed", "activequeries_listed_remote",
              "activequeries_kill_to_client_ms"):
        if k in aq:
            # ISSUE-13 acceptance: registry tax on concurrent QPS
            # (gate <= 2%) + the kill-drill evidence
            result[k] = aq[k]
    if "error" in aq:
        result["activequeries_error"] = aq["error"]
    sm = stages.get("selfmon", {})
    for k in ("selfmon_overhead_pct", "selfmon_scrape_p50_s",
              "selfmon_scrape_series", "selfmon_gate_ok"):
        if k in sm:
            # ISSUE-10 acceptance: the self-scrape tax on concurrent QPS
            # (gate: <= 2% at the default selfmon.interval_s) and the
            # scrape p50
            result[k] = sm[k]
    if "error" in sm:
        # loud-fail contract (like multichip/wal/longrange): a broken
        # self-monitoring stage rides into the parsed line
        result["selfmon_error"] = sm["error"]
    rul = stages.get("ruler", {})
    for k in ("ruler_eval_p50_s", "recorded_query_speedup_x",
              "ruler_overhead_pct"):
        if k in rul:
            # PR-5 acceptance: full group-iteration p50 (8 rules through
            # the frontend + write-back), the dashboard aggregate served
            # from the recorded series vs the raw expression (gate:
            # >= 10x), and the standing-query tax on serving QPS
            result[k] = rul[k]
    mc = stages.get("multichip", {})
    for k in ("multichip_fused_warm_s", "multichip_general_warm_s",
              "multichip_scaling_x", "multichip_inversion_gone",
              "multichip_fused_route", "multichip_pack_memo_hits"):
        if k in mc:
            # ISSUE-6 acceptance: per-device fused dispatch vs the
            # general mesh path (gate: fused <= general — the
            # MULTICHIP_r05 30x inversion is dead) + mesh scaling vs one
            # device and the repack-memo hit evidence
            result[k] = mc[k]
    if "error" in mc:
        # the loud-fail contract: a TPU box without >= 2 devices (or any
        # multichip failure) rides into the parsed line, never vanishes
        result["multichip_error"] = mc["error"]
    wl = stages.get("wal", {})
    for k in ("remote_write_samples_per_sec", "wal_overhead_pct",
              "wal_on_vs_off_pct", "wal_on_samples_per_sec",
              "wal_replay_samples_per_sec", "wal_kill_acked_lost",
              "wal_kill_query_identical"):
        if k in wl:
            # ISSUE-7 acceptance: the durability tax (gate: WAL-on >=
            # 50% of WAL-off), replay rate, the remote_write door rate,
            # and the kill-chaos zero-acked-loss proof (gate: 0 lost,
            # recovered answers byte-identical)
            result[k] = wl[k]
    for k in ("error", "wal_kill_error"):
        if k in wl:
            result["wal_error"] = wl[k]
    it = stages.get("ingesttrace", {})
    for k in ("ingest_trace_overhead_pct",
              "ingest_trace_on_samples_per_sec",
              "ingest_trace_stitched", "ingest_trace_nodes",
              "ingest_freshness_p99_s", "ingesttrace_fault_visible",
              "ingesttrace_gate_ok"):
        if k in it:
            # ISSUE-12 acceptance: tracing-on >= 98% of tracing-off on
            # the remote_write door, ONE stitched 2-node write-path
            # trace, and the wal.fsync fault drill visible in the fsync
            # histogram + ingest slowlog + freshness histograms + the
            # health verdict
            result[k] = it[k]
    if "error" in it:
        # loud-fail contract (like wal/selfmon): a broken write-path
        # tracing stage rides into the parsed line, never vanishes
        result["ingesttrace_error"] = it["error"]
    lr = stages.get("longrange", {})
    for k in ("longrange_cold_scan_samples_per_sec",
              "longrange_warm_cold_ratio", "longrange_stitch_identical",
              "longrange_cold_vs_mem_ratio",
              "longrange_mem_samples_per_sec", "longrange_lru_bounded",
              "longrange_gate_ok"):
        if k in lr:
            # ISSUE-8 acceptance: cold first-scan >= 1/10 of in-memory,
            # cold-region-resident re-scan >= 1/2, stitched
            # raw+downsample+persisted bit-identical to a single-tier
            # reference, and the cold region's LRU byte bound held
            result[k] = lr[k]
    if "error" in lr:
        # loud-fail contract (like multichip): a broken historical tier
        # rides into the parsed line, never vanishes
        result["longrange_error"] = lr["error"]
    dx = stages.get("distexec", {})
    for k in ("distexec_wire_bytes_ratio", "distexec_pushdown_speedup_x",
              "distexec_bit_identical", "distexec_frontend_peak_rss_mb",
              "distexec_baseline_peak_rss_mb", "distexec_rss_budget_mb",
              "distexec_stream_frames", "distexec_stream_identical",
              "distexec_pushed_nodes", "distexec_gate_ok"):
        if k in dx:
            # ISSUE-15 acceptance: 4-node fan-out aggregation moves
            # >= 10x fewer wire bytes pushed vs ship-everything (bit-
            # identical), and a long-range streamed reply holds traced
            # peak memory under a fixed budget that the materialize-
            # everything baseline exceeds
            result[k] = dx[k]
    if "error" in dx:
        result["distexec_error"] = dx["error"]
    ix = stages.get("index", {})
    for k in ("index_series", "index_build_keys_per_sec",
              "index_equals_lookup_p50_ms", "index_regex_plan_p50_ms",
              "index_regex_plan_max_ms", "index_regex_memo_p50_ms",
              "index_trigram_build_ms", "index_churn_rss_growth_pct",
              "index_memory_bytes", "index_gate_ok"):
        if k in ix:
            # ISSUE-16 acceptance: bitmap postings plan `=~` under 10 ms
            # and answer equals under 1 ms on a zipf shard, while the
            # churn soak holds index memory within 10% across evict-all
            # generations (compaction + container rebase working)
            result[k] = ix[k]
    if "error" in ix:
        result["index_error"] = ix["error"]
    ef = stages.get("exprfuse", {})
    for k in ("exprfuse_p50_s", "exprfuse_baseline_p50_s",
              "exprfuse_speedup_x", "exprfuse_identical",
              "exprfuse_fused", "exprfuse_degraded",
              "exprfuse_memo_hits", "exprfuse_gate_ok"):
        if k in ef:
            # ISSUE-17 acceptance: the 8-mixed-panel dashboard compiled
            # as one batch runs >= 5x faster than per-node assembly with
            # BIT-identical results (and every panel fused, none
            # degraded)
            result[k] = ef[k]
    for k in ("error", "exprfuse_error"):
        if k in ef:
            result["exprfuse_error"] = ef[k]
    dtl = stages.get("devicetelem", {})
    for k in ("devicetelem_overhead_pct", "devicetelem_fused_overhead_pct",
              "devicetelem_parity_ok", "devicetelem_storm_compiles",
              "devicetelem_storm_attributed",
              "devicetelem_storm_hist_count",
              "devicetelem_storm_health_degraded",
              "devicetelem_mesh_reconciled", "devicetelem_gate_ok"):
        if k in dtl:
            # ISSUE-18 acceptance: the per-chip kernel ledger costs
            # <= 2% on concurrent QPS and on the flagship fused scan,
            # an injected recompile storm is attributable (shape +
            # origin) and flips device health, and per-device mesh
            # dispatch counts reconcile with the untagged counter
            result[k] = dtl[k]
    if "error" in dtl:
        result["devicetelem_error"] = dtl["error"]
    ns = stages.get("north_star_1m") or stages.get("cpu_north_star_1m")
    if ns and "samples_per_sec" in ns:
        result.update({
            "north_star_series": ns["series"],
            "north_star_p50_s": ns["p50_s"],
            "north_star_samples_per_sec": ns["samples_per_sec"],
            "north_star_kernel": ns.get("kernel"),
        })
    if partial:
        result["partial"] = True
    # ONE COMPACT LINE is the driver contract (BENCH_r04.json came back
    # "parsed": null when embedded stage detail outgrew the driver's tail
    # capture) — full stage dicts live in BENCH_PARTIAL.json; the line
    # carries only a per-stage p50 summary
    result["stage_p50_s"] = {
        name: st.get("p50_s") for name, st in stages.items()
        if isinstance(st, dict) and "p50_s" in st}
    result["stage_detail"] = "BENCH_PARTIAL.json"
    return result


def run_worker(args):
    import jax

    if args.platform == "cpu":
        # env vars may be too late once something has imported jax — pin
        # via jax.config (same as tests/conftest.py)
        jax.config.update("jax_platforms", "cpu")

    # persistent compile cache: repeated attempts must not pay cold XLA
    # compiles again (the one rule: config.apply_jax_runtime)
    from filodb_tpu.config import apply_jax_runtime, settings
    apply_jax_runtime(settings())

    platform = jax.devices()[0].platform
    quick = args.quick
    T = 720                                  # 2h of 10s samples
    iters = args.iters or (3 if quick else 10)
    writer = PartialWriter(args.run_id or "adhoc", platform)

    if args.series:
        ladder = [("explicit", args.series, iters)]
    elif quick:
        ladder = [("quick_8k", 8_192, iters)]
    elif platform == "cpu":
        # CPU runs must finish within the supervisor timeout; the 1M
        # north-star SHAPE still gets a measured point (relaxed iters) so
        # the target workload has executed somewhere every round
        ladder = [("cpu_65k", 65_536, iters),
                  ("cpu_north_star_1m", 1_048_576, 3)]
    else:
        # smallest-first: an interrupted run must still leave a trusted
        # TPU number behind before the big stages start
        ladder = [("warm_8k", 8_192, iters),
                  ("warm_65k", 65_536, iters),
                  ("warm_262k", 262_144, iters),
                  ("north_star_1m", 1_048_576, iters)]

    stages = {}
    baseline_inputs = None
    conformance_ok = False
    for name, S, stage_iters in ladder:
        try:
            st, ts_row, vals, gids, wends, range_ms, span = measure_stage(
                S, T, stage_iters, platform,
                do_fused=platform != "cpu",
                persist=lambda d, n=name: writer.stage(n, d),
                prior_conformance_ok=conformance_ok)
            conformance_ok = conformance_ok or bool(
                st.get("conformance_ok"))
            stages[name] = st
            if baseline_inputs is None or S <= 262_144:
                baseline_inputs = (ts_row, vals, gids, wends, range_ms,
                                   span)
            else:
                del ts_row, vals
        except Exception as e:  # noqa: BLE001 — later stages may still work
            stages[name] = {"series": S, "samples_per_series": T,
                            "error": f"{type(e).__name__}: {e}"[:300]}
            writer.stage(name, stages[name])

    vec_sps = it_sps = c_sps = 0.0
    if baseline_inputs is not None:
        vec_sps, it_sps, c_sps = host_baselines(*baseline_inputs)
        writer.stage("host_baselines", {
            "vectorized_numpy_samples_per_sec": round(vec_sps, 1),
            "iterator_numpy_samples_per_sec": round(it_sps, 1),
            "iterator_c_samples_per_sec": round(c_sps, 1)})

    try:
        ing = measure_ingest(series=65_536 if quick else 262_144,
                             max_seconds=5.0 if quick else 10.0)
        writer.stage("ingest", ing)
        stages["ingest"] = ing
    except Exception as e:  # noqa: BLE001 — ingest stage must not sink the run
        writer.stage("ingest", {"error": f"{type(e).__name__}: {e}"[:300]})

    try:
        cov = measure_fused_coverage()
        writer.stage("fused_coverage", cov)
        stages["fused_coverage"] = cov
    except Exception as e:  # noqa: BLE001 — coverage must not sink the run
        writer.stage("fused_coverage",
                     {"error": f"{type(e).__name__}: {e}"[:300]})

    if not quick:
        try:
            db = measure_dashboard_batch(platform)
            writer.stage("dashboard_batch", db)
            stages["dashboard_batch"] = db
        except Exception as e:  # noqa: BLE001 — must not sink the run
            writer.stage("dashboard_batch",
                         {"error": f"{type(e).__name__}: {e}"[:300]})

    try:
        qf = measure_query_frontend(quick=quick)
        writer.stage("query_frontend", qf)
        stages["query_frontend"] = qf
    except Exception as e:  # noqa: BLE001 — must not sink the run
        writer.stage("query_frontend",
                     {"error": f"{type(e).__name__}: {e}"[:300]})

    try:
        obs = measure_observability(quick=quick)
        writer.stage("observability", obs)
        stages["observability"] = obs
    except Exception as e:  # noqa: BLE001 — must not sink the run
        writer.stage("observability",
                     {"error": f"{type(e).__name__}: {e}"[:300]})

    try:
        # live-introspection stage (ISSUE 13): registry tax on the
        # concurrent-QPS workload (gate: <= 2%) + the two-node cold-
        # query kill drill
        aq = measure_activequeries(quick=quick)
        writer.stage("activequeries", aq)
        stages["activequeries"] = aq
    except Exception as e:  # noqa: BLE001 — must not sink the run
        stages["activequeries"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        writer.stage("activequeries", stages["activequeries"])

    try:
        # self-observability stage (ISSUE 10): self-scrape overhead on
        # the serving QPS number + the scrape p50 (gate: <= 2%)
        sm = measure_selfmon(quick=quick)
        writer.stage("selfmon", sm)
        stages["selfmon"] = sm
    except Exception as e:  # noqa: BLE001 — must not sink the run
        stages["selfmon"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        writer.stage("selfmon", stages["selfmon"])

    try:
        rul = measure_ruler(quick=quick)
        writer.stage("ruler", rul)
        stages["ruler"] = rul
    except Exception as e:  # noqa: BLE001 — must not sink the run
        writer.stage("ruler", {"error": f"{type(e).__name__}: {e}"[:300]})

    try:
        # durability stage (ISSUE 7): WAL on/off ingest, replay rate,
        # remote_write door, kill-mid-ingest zero-acked-loss proof
        wl = measure_wal(quick=quick)
        writer.stage("wal", wl)
        stages["wal"] = wl
    except Exception as e:  # noqa: BLE001 — must not sink the run
        stages["wal"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        writer.stage("wal", stages["wal"])

    try:
        # write-path tracing stage (ISSUE 12): span-pipeline tax on the
        # remote_write door, stitched 2-node trace, fault visibility
        it = measure_ingesttrace(quick=quick)
        writer.stage("ingesttrace", it)
        stages["ingesttrace"] = it
    except Exception as e:  # noqa: BLE001 — must not sink the run
        stages["ingesttrace"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        writer.stage("ingesttrace", stages["ingesttrace"])

    try:
        # historical-tier stage (ISSUE 8): compacted segments, cold
        # DeviceMirror region, tier-stitched planning
        lr = measure_longrange(quick=quick)
        writer.stage("longrange", lr)
        stages["longrange"] = lr
    except Exception as e:  # noqa: BLE001 — must not sink the run
        stages["longrange"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        writer.stage("longrange", stages["longrange"])

    try:
        # distributed-execution stage (ISSUE 15): 4-node aggregation
        # pushdown wire ratio + bit-identity, streamed-reply peak-RSS
        # bound vs the materialize-everything baseline
        dx = measure_distexec(quick=quick)
        writer.stage("distexec", dx)
        stages["distexec"] = dx
    except Exception as e:  # noqa: BLE001 — must not sink the run
        stages["distexec"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        writer.stage("distexec", stages["distexec"])

    try:
        # bitmap index stage (ISSUE 16): ladder-sized shard (1M full /
        # 50k quick — the gating 10M run is the standalone `index`
        # stage); regex planning + equals p50, churn memory flatness
        ix = measure_index(quick=quick,
                           series=(50_000 if quick else 1_000_000))
        writer.stage("index", ix)
        stages["index"] = ix
    except Exception as e:  # noqa: BLE001 — must not sink the run
        stages["index"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        writer.stage("index", stages["index"])

    try:
        # whole-expression compilation stage (ISSUE 17): 8-mixed-panel
        # dashboard (incl. vector-matching binary ops) compiled as one
        # batch vs per-node assembly — 1M series full, 8k quick
        ef = measure_exprfuse(quick=quick)
        writer.stage("exprfuse", ef)
        stages["exprfuse"] = ef
    except Exception as e:  # noqa: BLE001 — must not sink the run
        stages["exprfuse"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        writer.stage("exprfuse", stages["exprfuse"])

    try:
        # kernel-ledger tax + compile-storm drill; the mesh reconcile
        # leg self-skips on a 1-device box (the standalone entry forces
        # 8 virtual devices for it)
        dtl = measure_devicetelem(quick=quick)
        writer.stage("devicetelem", dtl)
        stages["devicetelem"] = dtl
    except Exception as e:  # noqa: BLE001 — must not sink the run
        stages["devicetelem"] = {
            "error": f"{type(e).__name__}: {e}"[:300]}
        writer.stage("devicetelem", stages["devicetelem"])

    try:
        # measure_fused_coverage leaves FILODB_TPU_FUSED_INTERPRET=1
        # behind for the dashboard stage's interpret-mode CPU kernel
        # runs; inheriting it here would reroute the per-device unit
        # from the host fused leaf into interpret-mode Pallas at full
        # scale — orders of magnitude slower, and a route production
        # never takes.  Nothing after this stage reads the var.
        os.environ.pop("FILODB_TPU_FUSED_INTERPRET", None)
        mc = measure_multichip(quick=quick)
        writer.stage("multichip", mc)
        stages["multichip"] = mc
    except Exception as e:  # noqa: BLE001 — a 1-device box records a
        # LOUD error here (never a skip): a TPU claim without >= 2
        # devices must surface in the one-line JSON (ISSUE 6 satellite)
        stages["multichip"] = {"error": f"{type(e).__name__}: {e}"[:300]}
        writer.stage("multichip", stages["multichip"])

    result = assemble_result(platform, stages, vec_sps, it_sps,
                             c_sps)
    writer.finish()
    print(json.dumps(result))


def _spawn_worker(args, platform, timeout_s, run_id):
    """Run the measurement in a child under a hard timeout; return the
    parsed JSON result dict or None."""
    cmd = [sys.executable, os.path.abspath(__file__), "--_worker",
           "--platform", platform, "--run-id", run_id]
    if args.quick:
        cmd.append("--quick")
    if args.series:
        cmd += ["--series", str(args.series)]
    if args.iters:
        cmd += ["--iters", str(args.iters)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout_s)
    except subprocess.TimeoutExpired:
        print(f"bench: worker ({platform}) timed out after {timeout_s}s",
              file=sys.stderr)
        return None
    if proc.returncode != 0:
        tail = "\n".join(proc.stderr.strip().splitlines()[-5:])
        print(f"bench: worker ({platform}) rc={proc.returncode}:\n{tail}",
              file=sys.stderr)
        return None
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    print(f"bench: worker ({platform}) emitted no JSON", file=sys.stderr)
    return None


def _recover_partial(run_id):
    """If a dead worker left completed stages behind, synthesize the final
    line from them (partial=true) rather than discarding TPU evidence."""
    try:
        with open(PARTIAL_PATH) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if doc.get("run_id") != run_id or not doc.get("stages"):
        return None
    hb = doc["stages"].get("host_baselines", {})
    result = assemble_result(
        doc.get("platform", "unknown"), doc["stages"],
        hb.get("vectorized_numpy_samples_per_sec", 0.0),
        hb.get("iterator_numpy_samples_per_sec", 0.0),
        hb.get("iterator_c_samples_per_sec", 0.0), partial=True)
    if result.get("value"):
        return result
    return None


def _probe_default_backend(timeout_s):
    """Init the default jax backend in a child; return its platform name or
    None if init fails/hangs.  Cheap insurance against a backend that
    hangs at start-up."""
    code = "import jax; print(jax.devices()[0].platform)"
    try:
        p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        print(f"bench: backend probe timed out after {timeout_s}s",
              file=sys.stderr)
        return None
    if p.returncode == 0 and p.stdout.strip():
        return p.stdout.strip().splitlines()[-1]
    return None


def main():
    args = parse_args()
    if args.stage == "multichip":
        # standalone multi-chip stage: runs IN THIS process.  Host
        # platforms get 8 virtual devices — XLA_FLAGS must land before
        # the first backend init (backends initialize lazily, so the env
        # var still takes even if jax is already imported).  A TPU backend
        # ignores the host-platform flag.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        try:
            mc = measure_multichip(quick=args.quick,
                                   series=args.series or None,
                                   iters=args.iters)
        except Exception as e:  # noqa: BLE001 — loud one-line fail
            print(json.dumps({
                "metric": "multichip_fused_warm_s", "unit": "s",
                "error": f"{type(e).__name__}: {e}"[:300]}))
            sys.exit(1)
        mc = {"metric": "multichip_fused_warm_s", "unit": "s",
              "value": mc.get("multichip_fused_warm_s"), **mc}
        print(json.dumps(mc))
        sys.exit(0 if mc.get("multichip_inversion_gone") else 1)
    if args.stage == "wal":
        # standalone durability stage: CPU-pinned (the WAL measures the
        # host ingest + fsync path, not kernels); prints the one-line
        # wal JSON and exits nonzero when a hard gate fails — WAL-on
        # under 50% of WAL-off, or ANY acknowledged sample lost in the
        # kill-chaos replay
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        wl = measure_wal(quick=args.quick, series=args.series or None)
        wl = {"metric": "wal_on_samples_per_sec", "unit": "samples/s",
              "value": wl.get("wal_on_samples_per_sec"), **wl}
        print(json.dumps(wl))
        # the durability gates always hold; the 50% throughput gate is
        # judged at FULL scale only (quick's toy batches cannot amortize
        # an fsync — the reported ratio still rides the line)
        ok = (wl.get("wal_kill_acked_lost") == 0
              and wl.get("wal_kill_query_identical")
              and (args.quick or wl.get("wal_gate_ok")))
        sys.exit(0 if ok else 1)
    if args.stage == "longrange":
        # standalone historical-tier stage: CPU-pinned like wal (the
        # gates are ratios against an in-memory reference on the same
        # backend); prints the one-line longrange JSON and exits nonzero
        # when a gate fails
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        # persistent XLA compile cache, like run_worker: the stage's warm
        # numbers must not be polluted by first-boot compiles
        from filodb_tpu.config import apply_jax_runtime, settings
        apply_jax_runtime(settings())
        try:
            lr = measure_longrange(quick=args.quick,
                                   series=args.series or None)
        except Exception as e:  # noqa: BLE001 — loud one-line fail
            print(json.dumps({
                "metric": "longrange_cold_scan_samples_per_sec",
                "unit": "samples/s",
                "longrange_error": f"{type(e).__name__}: {e}"[:300]}))
            sys.exit(1)
        lr = {"metric": "longrange_cold_scan_samples_per_sec",
              "unit": "samples/s",
              "value": lr.get("longrange_cold_scan_samples_per_sec"),
              **lr}
        print(json.dumps(lr))
        # correctness gates always hold; the throughput ratios are judged
        # at FULL scale only (quick's toy windows cannot amortize a
        # page-in — the measured ratios still ride the line)
        ok = (lr.get("longrange_stitch_identical")
              and lr.get("longrange_lru_bounded")
              and (args.quick or lr.get("longrange_gate_ok")))
        sys.exit(0 if ok else 1)
    if args.stage == "selfmon":
        # standalone self-observability stage: CPU-pinned (it measures
        # the scrape + serving overhead, not kernels); prints the
        # one-line selfmon JSON, exits nonzero when the 2% overhead
        # gate fails or the stage errors (loud-fail contract)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        try:
            sm = measure_selfmon(quick=args.quick,
                                 series=args.series or None)
        except Exception as e:  # noqa: BLE001 — loud one-line fail
            print(json.dumps({
                "metric": "selfmon_overhead_pct", "unit": "%",
                "selfmon_error": f"{type(e).__name__}: {e}"[:300]}))
            sys.exit(1)
        sm = {"metric": "selfmon_overhead_pct", "unit": "%",
              "value": sm.get("selfmon_overhead_pct"), **sm}
        if "error" in sm:
            sm["selfmon_error"] = sm["error"]
        print(json.dumps(sm))
        # quick's short pumps are too noisy to judge a 2% ratio; the
        # measured number still rides the line
        sys.exit(0 if "error" not in sm
                 and (args.quick or sm.get("selfmon_gate_ok")) else 1)
    if args.stage == "ingesttrace":
        # standalone write-path tracing stage: CPU-pinned (it measures
        # the door + WAL + replication path, not kernels); prints the
        # one-line ingesttrace JSON and exits nonzero when a gate fails
        # (loud-fail contract like wal/selfmon)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        try:
            it = measure_ingesttrace(quick=args.quick,
                                     series=args.series or None)
        except Exception as e:  # noqa: BLE001 — loud one-line fail
            print(json.dumps({
                "metric": "ingest_trace_overhead_pct", "unit": "%",
                "ingesttrace_error": f"{type(e).__name__}: {e}"[:300]}))
            sys.exit(1)
        it = {"metric": "ingest_trace_overhead_pct", "unit": "%",
              "value": it.get("ingest_trace_overhead_pct"), **it}
        print(json.dumps(it))
        # the stitched-trace and fault-visibility proofs always gate;
        # the 2% throughput tax is judged at FULL scale only (quick's
        # toy batches cannot average out scheduler noise)
        sys.exit(0 if it.get("ingesttrace_gate_ok") else 1)
    if args.stage == "activequeries":
        # standalone live-introspection stage: CPU-pinned (it measures
        # registry/kill machinery, not kernels); prints the one-line
        # activequeries JSON and exits nonzero when a gate fails
        # (loud-fail contract like selfmon/ingesttrace)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        try:
            aq = measure_activequeries(quick=args.quick,
                                       series=args.series or None)
        except Exception as e:  # noqa: BLE001 — loud one-line fail
            print(json.dumps({
                "metric": "activequeries_overhead_pct", "unit": "%",
                "activequeries_error": f"{type(e).__name__}: {e}"[:300]}))
            sys.exit(1)
        aq = {"metric": "activequeries_overhead_pct", "unit": "%",
              "value": aq.get("activequeries_overhead_pct"), **aq}
        if "error" in aq:
            aq["activequeries_error"] = aq["error"]
        print(json.dumps(aq))
        # the kill-drill correctness gates always hold; the 2% overhead
        # and 250 ms drain ratios are judged at FULL scale only (quick's
        # short pumps are too noisy)
        sys.exit(0 if "error" not in aq
                 and aq.get("activequeries_gate_ok") else 1)
    if args.stage == "qos":
        # standalone multi-tenant QoS stage: CPU-pinned (it measures the
        # fairness/shedding machinery, not kernels); prints the one-line
        # qos JSON and exits nonzero when the noisy-neighbor gate fails
        # (loud-fail contract like selfmon/activequeries)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        try:
            qs = measure_qos(quick=args.quick,
                             series=args.series or None)
        except Exception as e:  # noqa: BLE001 — loud one-line fail
            print(json.dumps({
                "metric": "qos_p99_ratio", "unit": "x",
                "qos_error": f"{type(e).__name__}: {e}"[:300]}))
            sys.exit(1)
        qs = {"metric": "qos_p99_ratio", "unit": "x",
              "value": qs.get("qos_p99_ratio"), **qs}
        if "error" in qs:
            qs["qos_error"] = qs["error"]
        print(json.dumps(qs))
        sys.exit(0 if "error" not in qs and qs.get("qos_gate_ok")
                 else 1)
    if args.stage == "distexec":
        # standalone distributed-execution stage: CPU-pinned (it
        # measures wire/merge machinery, not kernels); prints the
        # one-line distexec JSON and exits nonzero when a gate fails
        # (loud-fail contract like selfmon/activequeries)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        try:
            dx = measure_distexec(quick=args.quick,
                                  series=args.series or None)
        except Exception as e:  # noqa: BLE001 — loud one-line fail
            print(json.dumps({
                "metric": "distexec_wire_bytes_ratio", "unit": "x",
                "distexec_error": f"{type(e).__name__}: {e}"[:300]}))
            sys.exit(1)
        dx = {"metric": "distexec_wire_bytes_ratio", "unit": "x",
              "value": dx.get("distexec_wire_bytes_ratio"), **dx}
        if "error" in dx:
            dx["distexec_error"] = dx["error"]
        print(json.dumps(dx))
        sys.exit(0 if "error" not in dx and dx.get("distexec_gate_ok")
                 else 1)
    if args.stage == "index":
        # standalone high-cardinality index stage: CPU-pinned (it
        # measures posting/planning machinery, not kernels); builds the
        # full 10M-key zipf shard, prints the one-line index JSON and
        # exits nonzero when a gate fails (loud-fail contract like
        # selfmon/distexec)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        try:
            ix = measure_index(quick=args.quick,
                               series=args.series or None)
        except Exception as e:  # noqa: BLE001 — loud one-line fail
            print(json.dumps({
                "metric": "index_regex_plan_p50_ms", "unit": "ms",
                "index_error": f"{type(e).__name__}: {e}"[:300]}))
            sys.exit(1)
        ix = {"metric": "index_regex_plan_p50_ms", "unit": "ms",
              "value": ix.get("index_regex_plan_p50_ms"), **ix}
        if "error" in ix:
            ix["index_error"] = ix["error"]
        print(json.dumps(ix))
        sys.exit(0 if "error" not in ix and ix.get("index_gate_ok")
                 else 1)
    if args.stage == "exprfuse":
        # standalone whole-expression compilation stage: CPU-pinned (it
        # measures the expression compiler + scan sharing, not kernels —
        # the stage pins host-routed leaves on every backend anyway);
        # builds the full 1M-series dashboard store, prints the one-line
        # exprfuse JSON and exits nonzero when a gate fails (loud-fail
        # contract like distexec/index)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        try:
            ef = measure_exprfuse(quick=args.quick,
                                  series=args.series or None,
                                  iters=args.iters)
        except Exception as e:  # noqa: BLE001 — loud one-line fail
            print(json.dumps({
                "metric": "exprfuse_speedup_x", "unit": "x",
                "exprfuse_error": f"{type(e).__name__}: {e}"[:300]}))
            sys.exit(1)
        ef = {"metric": "exprfuse_speedup_x", "unit": "x",
              "value": ef.get("exprfuse_speedup_x"), **ef}
        if "error" in ef:
            ef["exprfuse_error"] = ef["error"]
        print(json.dumps(ef))
        sys.exit(0 if "error" not in ef and "exprfuse_error" not in ef
                 and ef.get("exprfuse_gate_ok") else 1)
    if args.stage == "devicetelem":
        # standalone device-telemetry stage: CPU-pinned with 8 virtual
        # host devices so the per-chip mesh reconcile leg runs (ISSUE-18
        # acceptance wants /admin/devices reflecting real per-chip
        # placement, not a 1-device degenerate); prints the one-line
        # devicetelem JSON and exits nonzero when a gate fails
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        try:
            dtl = measure_devicetelem(quick=args.quick,
                                      series=args.series or None,
                                      iters=args.iters)
        except Exception as e:  # noqa: BLE001 — loud one-line fail
            print(json.dumps({
                "metric": "devicetelem_overhead_pct", "unit": "%",
                "devicetelem_error": f"{type(e).__name__}: {e}"[:300]}))
            sys.exit(1)
        dtl = {"metric": "devicetelem_overhead_pct", "unit": "%",
               "value": dtl.get("devicetelem_overhead_pct"), **dtl}
        if "error" in dtl:
            dtl["devicetelem_error"] = dtl["error"]
        print(json.dumps(dtl))
        sys.exit(0 if "error" not in dtl
                 and "devicetelem_error" not in dtl
                 and dtl.get("devicetelem_gate_ok") else 1)
    if args.stage == "chaos":
        # standalone failure-domain stage: runs IN THIS process (CPU-
        # pinned; chaos measures degradation machinery, not kernels),
        # SIGKILLs and respawns one of three RF-2 data-node
        # subprocesses mid-traffic, prints the one-line chaos JSON and
        # writes SOAK_CHAOS.json; nonzero exit when the flipped gate
        # (availability 1.0, zero partials, zero acked loss) fails
        try:
            r = run_chaos(quick=args.quick, series=args.series or None)
        except Exception as e:  # noqa: BLE001 — loud one-line fail
            print(json.dumps({
                "metric": "chaos_availability", "unit": "fraction",
                "chaos_error": f"{type(e).__name__}: {e}"[:300]}))
            sys.exit(1)
        print(json.dumps(r))
        sys.exit(0 if r.get("chaos_gate_ok") else 1)
    if args.stage == "replication":
        try:
            r = run_replication(quick=args.quick,
                                series=args.series or None)
        except Exception as e:  # noqa: BLE001 — loud one-line fail
            print(json.dumps({
                "metric": "replication_rf2_vs_rf1_pct", "unit": "%",
                "replication_error": f"{type(e).__name__}: {e}"[:300]}))
            sys.exit(1)
        print(json.dumps(r))
        sys.exit(0 if r.get("replication_gate_ok") else 1)
    if args.stage == "objectstore":
        try:
            r = run_objectstore(quick=args.quick,
                                series=args.series or None)
        except Exception as e:  # noqa: BLE001 — loud one-line fail
            print(json.dumps({
                "metric": "objectstore_elastic_qps_ratio", "unit": "x",
                "objectstore_error": f"{type(e).__name__}: {e}"[:300]}))
            sys.exit(1)
        print(json.dumps(r))
        sys.exit(0 if r.get("objectstore_gate_ok") else 1)
    if args.stage == "federation":
        try:
            r = run_federation(quick=args.quick,
                               series=args.series or None)
        except Exception as e:  # noqa: BLE001 — loud one-line fail
            print(json.dumps({
                "metric": "federation_wire_ratio_x", "unit": "x",
                "federation_error": f"{type(e).__name__}: {e}"[:300]}))
            sys.exit(1)
        print(json.dumps(r))
        sys.exit(0 if r.get("federation_gate_ok") else 1)
    if args._worker:
        run_worker(args)
        return

    run_id = f"bench-{os.getpid()}-{int(time.time())}"
    # Supervisor (stays off jax: the probe and the worker are children, run
    # one after the other, so each can hold the chip): probe the default
    # backend under a short timeout and run the measurement there.  No
    # chip, no number — a CPU run happens only on an explicit
    # --platform cpu, and is labelled as one.
    if args.platform == "cpu":
        result = _spawn_worker(args, "cpu", 2700, run_id)
        if result is None:
            print(json.dumps({"error": "cpu bench attempt failed",
                              "platform": "cpu"}))
            sys.exit(1)
        print(json.dumps(result))
        return
    tpu_timeout = int(os.environ.get("FILODB_BENCH_TPU_TIMEOUT",
                                     "600" if args.quick else "2400"))
    plat = _probe_default_backend(180)
    if plat in (None, "cpu"):
        print(json.dumps({
            "error": "no accelerator: the default jax backend is "
                     f"{plat or 'unavailable'}; pass --platform cpu to "
                     "ask for a CPU run explicitly",
            "platform": plat or "none"}))
        sys.exit(1)
    for _ in range(2):
        result = _spawn_worker(args, "default", tpu_timeout, run_id)
        if result is None:
            result = _recover_partial(run_id)
        if result is not None:
            print(json.dumps(result))
            return
    print(json.dumps({"error": "all bench attempts on the default backend "
                               "failed", "platform": plat}))
    sys.exit(1)


if __name__ == "__main__":
    main()
