#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served query path starts and
answers correctly on an attached TPU.

One process (the one that holds the chip) starts a `FiloServer` the way
`filo-cli serve` does, loads counter series at flagship size through the
columnar ingest door, and drives the HTTP doors over a real socket:
PromQL range and instant queries, a snappy-protobuf remote write, and the
read-back of that write.  Every answer is checked against a plain f64 NumPy
evaluation of the same PromQL semantics written HERE (not ops/hostleaf.py,
which is a route under test), and the route each query took is read from the
program's own counters on /metrics and /admin/devices.

    python chip_smoke.py                  # one chip, 262,144 x 720
    python chip_smoke.py --series 524288  # any other size
    python chip_smoke.py --chips 4        # four-chip phase only, 1,048,576 x 720
    python chip_smoke.py --rehearse --series 2048          # CPU, no chip
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --rehearse --chips 4 --series 2048

Without --rehearse it needs a TPU: no accelerator means a non-zero exit and
`{"ok": false, ...}`.  Every phase failure propagates to a non-zero exit.
The timings printed are a smoke run's observations, claimed as nothing.
The last line of stdout is `{"ok": true, "device": {...}}`.
"""
import argparse
import json
import os
import resource
import sys
import time
import urllib.parse
import urllib.request

REPO_DIR = os.path.dirname(os.path.abspath(__file__))
START_MS = 1_600_000_000_000
STEP_MS = 10_000                 # 10 s scrape
RANGE_MS = 300_000               # [5m]
QSTEP_S = 60                     # query_range step
QSPAN_S = 3600                   # 1 h
N_WINDOWS = QSPAN_S // QSTEP_S + 1
REPEATS = 5                      # warm repeats, window moved back one step each
HOST_BOUND_REPEATS = 1           # ... of a query the program answers on the host
NUM_APPS = 10                    # _ns_ = App-<i % 10>
DATASET = "prometheus"
CHUNK = 65_536                   # series generated / checked / ingested at once
# tests/test_tpu_conformance.py:111-133 — what the f32 path is held to
TOL = {"counter": dict(rtol=2e-5, atol=1e-4),
       "other": dict(rtol=5e-4, atol=5e-3)}
SCAN_LIMIT = 2_000_000_000       # per-request scanLimit= (default 50M/shard)
FLAGSHIP_SERIES = 1_048_576
# The cut from the flagship size, and why (CHANGES.md PR 24, my chip runs):
# at 1,048,576 x 720 the one-chip machine's 40 GiB of host memory ran out
# during the first query's mirror build (store arrays grown by doubling +
# f64 rebase temporaries), and the phases whose cost follows the SERIES
# count, not the samples (key routing, partition creation, the two
# host-bound query routes, one compile per shard shape) would not leave the
# run inside its 1200 s limit even with fewer samples per series.  So the
# series are cut and the samples kept; 262,144 is the size that has passed
# on the chip, with a host peak RSS of 28.8 GB (so 524,288 would not fit).
DEFAULT_SERIES = 262_144


def parse_args():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--series", type=int, default=None,
                   help=f"default {DEFAULT_SERIES} on one chip, "
                        f"{FLAGSHIP_SERIES} with --chips 4 (a bigger host)")
    p.add_argument("--samples", type=int, default=720)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    p.add_argument("--rehearse", action="store_true",
                   help="run on the CPU at a tiny size with interpret-mode "
                        "kernels; proves control flow, never the chip")
    return p.parse_args()


ARGS = parse_args()
if ARGS.series is None:
    ARGS.series = FLAGSHIP_SERIES if ARGS.chips == 4 else DEFAULT_SERIES
if ARGS.rehearse:
    # the only way to run without a chip; set before jax is imported
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["FILODB_TPU_FUSED_INTERPRET"] = "1"
    if ARGS.chips > 1:
        os.environ["FILODB_TPU_FORCE_SHARDED_MIRROR"] = "1"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={ARGS.chips}"
            ).strip()


def emit(phase, **kw):
    print(json.dumps({"phase": phase, **kw}), flush=True)


import jax  # noqa: E402  (after the rehearsal environment is set)

DEVICE = {"platform": jax.devices()[0].platform,
          "kind": jax.devices()[0].device_kind,
          "count": len(jax.devices())}
if not ARGS.rehearse and DEVICE["platform"] != "tpu":
    print(json.dumps({"ok": False, "error": "no TPU attached (use "
                      "--rehearse for a CPU control-flow run)",
                      "device": DEVICE}))
    sys.exit(1)
if DEVICE["count"] < ARGS.chips:
    print(json.dumps({"ok": False, "error": f"--chips {ARGS.chips} needs "
                      f"{ARGS.chips} devices", "device": DEVICE}))
    sys.exit(1)

# built from committed files only: whatever native library a chip run uses
# is the one filodb_native.cc + Makefile build on this machine, or none.
# (A rehearsal leaves it alone: test workers beside it may be loading it.)
for _stale in ("libfilodb_native.so", ".build_failed"):
    _p = os.path.join(REPO_DIR, "filodb_tpu", "native", _stale)
    if not ARGS.rehearse and os.path.exists(_p):
        os.remove(_p)

import numpy as np  # noqa: E402

from filodb_tpu import native  # noqa: E402
from filodb_tpu.config import apply_jax_runtime  # noqa: E402
from filodb_tpu.core.partkey import PartKey  # noqa: E402
from filodb_tpu.http import remotepb  # noqa: E402
from filodb_tpu.standalone import DatasetConfig, FiloServer  # noqa: E402
from filodb_tpu.utils import snappy  # noqa: E402

# ------------------------------------------------------------ f64 reference

# the benchmark's plain NumPy reference: windows (wend-range, wend] on one
# shared timestamp row, Prometheus' extrapolatedRate on reset-corrected values
from benchmark.reference import (correct_counters, ref_increase,  # noqa: E402
                                 ref_sum_over_time)


class GroupSums:
    """sum by (_ns_) accumulated chunk by chunk: [NUM_APPS, W] sums and
    present-counts (a window no series has a value in is absent)."""

    def __init__(self, wends):
        self.wends = wends
        self.sums = np.zeros((NUM_APPS, len(wends)))
        self.cnt = np.zeros((NUM_APPS, len(wends)))

    def add(self, per_series, gids):
        fin = np.isfinite(per_series)
        z = np.where(fin, per_series, 0.0)
        for g in range(NUM_APPS):
            m = gids == g
            self.sums[g] += z[m].sum(axis=0)
            self.cnt[g] += fin[m].sum(axis=0)

    def by_ns(self, scale=1.0):
        """{ns label: {unix seconds: value}} for present cells."""
        out = {}
        for g in range(NUM_APPS):
            out[f"App-{g}"] = {
                int(w // 1000): self.sums[g, i] * scale
                for i, w in enumerate(self.wends) if self.cnt[g, i] > 0}
        return out

    def total(self, scale=1.0):
        s, c = self.sums.sum(axis=0), self.cnt.sum(axis=0)
        return {"": {int(w // 1000): s[i] * scale
                     for i, w in enumerate(self.wends) if c[i] > 0}}


# ------------------------------------------------------------------- data


def series_keys(metric, lo, hi):
    """ingest/generator.gauge_part_keys identities for series lo..hi-1."""
    return [PartKey.make(metric, {"_ws_": "demo",
                                  "_ns_": f"App-{i % NUM_APPS}",
                                  "instance": f"Instance-{i}",
                                  "dc": f"DC{i % 2}"})
            for i in range(lo, hi)]


def counter_chunk(rng, out):
    """ingest/generator.counter_batch semantics, written into `out` [n, T]:
    exponential increments, each series resetting to ~0 once in the second
    half."""
    n, T = out.shape
    rng.standard_exponential(out=out)
    out *= 10.0
    np.cumsum(out, axis=1, out=out)
    if T > 10:
        for s, r in enumerate(rng.integers(T // 2, T, size=n)):
            out[s, r:] -= out[s, r - 1]
    return out


def gauge_chunk(rng, out):
    """ingest/generator.gauge_batch semantics, written into `out` [n, T]."""
    n, T = out.shape
    phase = rng.uniform(0, 2 * np.pi, size=n)
    rng.standard_normal(out=out)
    out *= 2.0
    out += 100.0 + 50.0 * np.sin(np.arange(T)[None, :] / 20.0
                                 + phase[:, None])
    return out


def range_regex(hi):
    """Regex alternation matching the decimal integers 0..hi."""
    s = str(hi)
    parts = ["[0-9]"] + ["[1-9]" + "[0-9]" * (d - 1)
                         for d in range(2, len(s))]
    if len(s) > 1:
        for i, ch in enumerate(s):
            low = 1 if i == 0 else 0
            top = int(ch) - (0 if i == len(s) - 1 else 1)
            if top >= low:
                parts.append(f"{s[:i]}[{low}-{top}]"
                             + "[0-9]" * (len(s) - i - 1))
    else:
        parts = [f"[0-{s}]"]
    return "|".join(parts)


# ------------------------------------------------------------------- http


class Client:
    def __init__(self, port):
        self.base = f"http://127.0.0.1:{port}"

    def get(self, path, **params):
        url = self.base + path
        if params:
            url += "?" + urllib.parse.urlencode(params)
        with urllib.request.urlopen(url, timeout=1100) as r:
            return r.read()

    def query(self, path, **params):
        t0 = time.perf_counter()
        body = json.loads(self.get(path, scanLimit=SCAN_LIMIT, stats="true",
                                   **params))
        secs = time.perf_counter() - t0
        assert body["status"] == "success", body
        return body, secs

    def post_write(self, payload):
        req = urllib.request.Request(self.base + "/api/v1/write",
                                     data=payload, method="POST")
        with urllib.request.urlopen(req, timeout=600) as r:
            return r.status

    def counters(self):
        """Unlabelled and labelled samples of /metrics, summed per family."""
        out = {}
        for line in self.get("/metrics").decode().splitlines():
            if not line or line[0] == "#":
                continue
            name, _, val = line.rpartition(" ")
            fam = name.split("{", 1)[0]
            try:
                out[fam] = out.get(fam, 0.0) + float(val)
            except ValueError:
                pass
        return out

    def devices(self):
        return json.loads(self.get("/admin/devices"))["data"]["devices"]


ROUTE_COUNTERS = ("leaf_fused_kernel_total", "leaf_host_routed_total",
                  "leaf_fused_errors_total", "warmup_compile_errors_total",
                  "device_mirror_query_fallbacks_total",
                  "device_mirror_refreshes_total")


def route_delta(before, after):
    return {k: int(after.get(k, 0) - before.get(k, 0))
            for k in ROUTE_COUNTERS if after.get(k, 0) != before.get(k, 0)}


def device_dispatches(devs):
    """{"device/kernel": dispatch count} from /admin/devices."""
    return {f"{d}/{name}": int(k["count"])
            for d, st in devs.items() for name, k in st["kernels"].items()}


def compare(got, want, kind, what):
    """{group label: {unix s: value}} served vs reference, at the tolerance
    of `kind`; a cell present on one side only fails.  Returns the largest
    relative error seen."""
    assert set(got) == set(want), (what, sorted(got), sorted(want))
    worst = 0.0
    for g, series in want.items():
        assert set(got[g]) == set(series), \
            (what, g, "timestamps differ", len(got[g]), len(series))
        for t, w in series.items():
            err = abs(got[g][t] - w)
            assert err <= TOL[kind]["atol"] + TOL[kind]["rtol"] * abs(w), \
                (what, g, t, got[g][t], w)
            worst = max(worst, err / max(abs(w), 1e-300))
    return worst


def by_ns(body):
    """Matrix or vector response -> {_ns_ label: {unix s: value}}."""
    return {row["metric"].get("_ns_", ""): {
        int(float(t)): float(v)
        for t, v in (row.get("values") or [row["value"]])}
        for row in body["data"]["result"]}


def window_grid(end_ms, n):
    """n query window ends, one query step apart, the last at end_ms."""
    return end_ms - np.arange(n, dtype=np.int64)[::-1] * QSTEP_S * 1000


def subset(ref, t_lo, t_hi):
    return {g: {t: v for t, v in s.items() if t_lo <= t <= t_hi}
            for g, s in ref.items()}


# ------------------------------------------------------------------ phases


def load(server, metric, schema, col, S, T, seed, make_chunk, inc=None,
         sot=None, written=None):
    """Generate, reference-evaluate and ingest S series x T samples through
    shard.ingest_columns, routed to shards as the gateway routes them.
    The f64 reference is accumulated chunk by chunk (`inc`: GroupSums of
    increase, `sot`: of sum_over_time), so the raw data is never held whole
    beside the store.  `written` = (newer[n], bump[n], GroupSums): the first
    n series get one newer scrape later, over the remote-write door; their
    newer values are recorded here and a second increase reference includes
    them (what the store must answer after the write)."""
    mapper, spread = server.mappers[DATASET], server.spreads[DATASET]
    shards = server.memstore.shards_for(DATASET)
    ts_row = START_MS + np.arange(T, dtype=np.int64) * STEP_MS
    per_shard = np.zeros(len(shards), np.int64)
    n_buf = min(CHUNK, S)
    vbuf, wbuf = np.empty((n_buf, T)), np.empty((n_buf, T + 1))
    t_keys = t_ref = t_ingest = 0.0
    for c, lo in enumerate(range(0, S, CHUNK)):
        hi = min(lo + CHUNK, S)
        n = hi - lo
        t0 = time.perf_counter()
        keys = series_keys(metric, lo, hi)
        shard_of = np.fromiter(
            (mapper.ingestion_shard(pk.shard_key_hash(), pk.partition_hash(),
                                    spread.spread_for(pk.shard_key()))
             for pk in keys), np.int64, n)
        t1 = time.perf_counter()
        vals = make_chunk(np.random.default_rng([seed, c]), vbuf[:n])
        gids = np.arange(lo, hi) % NUM_APPS
        if sot is not None:
            csum = np.cumsum(vals, axis=1, out=wbuf[:n, :T])
            sot.add(ref_sum_over_time(ts_row, csum, sot.wends, RANGE_MS),
                    gids)
        if inc is not None:
            corr = correct_counters(vals, wbuf[:n, :T])
            inc.add(ref_increase(ts_row, corr, inc.wends, RANGE_MS), gids)
        if written is not None:
            newer, bump, acc = written
            k = max(min(len(newer), hi) - lo, 0)    # written series here
            if k:
                # a newer, larger sample: no reset, same correction
                newer[lo:lo + k] = vals[:k, -1] + bump[lo:lo + k]
                wbuf[:k, T] = corr[:k, -1] + bump[lo:lo + k]
                acc.add(ref_increase(np.append(ts_row, ts_row[-1] + STEP_MS),
                                     wbuf[:k], acc.wends, RANGE_MS), gids[:k])
            if k < n:
                acc.add(ref_increase(ts_row, corr[k:], acc.wends, RANGE_MS),
                        gids[k:])
        t2 = time.perf_counter()
        for sh in shards:
            idx = np.flatnonzero(shard_of == sh.shard_num)
            if idx.size:
                got = sh.ingest_columns(
                    schema, [keys[i] for i in idx],
                    np.broadcast_to(ts_row, (idx.size, T)),
                    {col: vals[idx]}, offset=c)
                assert got == idx.size * T, (got, idx.size * T)
                per_shard[sh.shard_num] += idx.size
        t3 = time.perf_counter()
        t_keys += t1 - t0
        t_ref += t2 - t1
        t_ingest += t3 - t2
    emit("load", metric=metric, schema=schema, series=S, samples=T,
         series_per_shard=per_shard.tolist(),
         keys_and_routing_s=round(t_keys, 2),
         generate_and_reference_s=round(t_ref, 2),
         ingest_columns_s=round(t_ingest, 2))
    return per_shard


def timed_queries(cli, name, path, promql, want, kind, end_s, expect_fused,
                  instant=False, repeats=REPEATS):
    """One cold request then `repeats` warm ones, the window moved back one
    step per repeat so the result cache and singleflight cannot answer in
    the device's place.  Every answer is checked; every request's route is
    read from the counters around it.  expect_fused True: the fused kernel
    must serve every request and the host none; None: a device route, any;
    False: whatever route the program's cost rule picks, printed."""
    times, worst, routes = [], 0.0, []
    for k in range(repeats + 1):
        e = end_s - k * QSTEP_S
        before = cli.counters()
        if instant:
            body, secs = cli.query(path, query=promql, time=e)
            ref = subset(want, e, e)
        else:
            body, secs = cli.query(path, query=promql, start=e - QSPAN_S,
                                   end=e, step=QSTEP_S)
            ref = subset(want, e - QSPAN_S, e)
        d = route_delta(before, cli.counters())
        worst = max(worst, compare(by_ns(body), ref, kind, f"{name}#{k}"))
        stats = body["data"].get("stats") or body.get("stats") or {}
        assert stats.get("cache", {}).get("result", "") != "hit", \
            (name, k, "answered by the result cache")
        for bad in ("leaf_fused_errors_total", "warmup_compile_errors_total",
                    "device_mirror_query_fallbacks_total"):
            assert bad not in d, (name, k, d)
        if expect_fused:
            assert d.get("leaf_fused_kernel_total", 0) >= 1, (name, k, d)
        if expect_fused is not False:
            assert "leaf_host_routed_total" not in d, (name, k, d)
        routes.append(d)
        times.append(secs)
    warm = sorted(times[1:])
    emit("query", name=name, promql=promql, checked_vs_f64_reference=True,
         requests=len(times), max_rel_err=worst,
         first_s=round(times[0], 4), warm_p50_s=round(warm[len(warm) // 2], 4),
         warm_s=[round(t, 4) for t in times[1:]],
         route_first=routes[0], route_warm=routes[-1],
         phases_last_warm=stats.get("phases"),
         fused_kernel=bool(routes[-1].get("leaf_fused_kernel_total")),
         host_routed=bool(routes[-1].get("leaf_host_routed_total")))
    return times


# TSBS devops `double-groupby` (benchmark cell tsbscpu-gauges-40k.double-
# groupby): rows of 13 h 9 min at a 10 s interval, the last twelve hours at
# a one-hour step, a group a host.  A dataset of its own: rows of another
# length in the smoke's dataset would put its mirrors on a placed grid
TSBS_DATASET, TSBS_T = "tsbs", 4_736
TSBS_RANGE_MS, TSBS_STEP_S, TSBS_SPAN_S = 3_600_000, 3_600, 43_200


def double_groupby(server, cli, hosts):
    """`avg by (hostname)(avg_over_time(cpu_usage_user[1h]))` over `hosts`
    rows of 4,736 samples through the HTTP door, every cell against the f64
    reference: the fused kernel must serve it, its band built in tiles (five
    whole [4736, 128] matrices do not fit the chip's vector memory), and
    nothing may take the general XLA path."""
    from benchmark.generators.clamped_walk import chunk as clamped_walk
    mapper, spread = server.mappers[TSBS_DATASET], \
        server.spreads[TSBS_DATASET]
    ts_row = START_MS + np.arange(TSBS_T, dtype=np.int64) * STEP_MS
    keys = [PartKey.make("cpu_usage_user", {
        "_ws_": "tsbs", "_ns_": f"service-{i % 20}", "hostname": f"host_{i}"})
        for i in range(hosts)]
    shard_of = np.fromiter(
        (mapper.ingestion_shard(pk.shard_key_hash(), pk.partition_hash(),
                                spread.spread_for(pk.shard_key()))
         for pk in keys), np.int64, hosts)
    vals = clamped_walk(np.random.default_rng([ARGS.seed, 48]),
                        np.empty((hosts, TSBS_T)))
    per_shard = []
    for sh in server.memstore.shards_for(TSBS_DATASET):
        idx = np.flatnonzero(shard_of == sh.shard_num)
        per_shard.append(int(idx.size))
        if idx.size:
            got = sh.ingest_columns(
                "gauge", [keys[i] for i in idx],
                np.broadcast_to(ts_row, (idx.size, TSBS_T)),
                {"value": vals[idx]}, offset=0)
            assert got == idx.size * TSBS_T, (got, idx.size * TSBS_T)
    csum = np.cumsum(vals, axis=1)
    promql = "avg by (hostname)(avg_over_time(cpu_usage_user[1h]))"
    path = f"/promql/{TSBS_DATASET}/api/v1/query_range"
    times, worst = [], 0.0
    # window ends whole seconds off the grid, as a query's `end` is `now`;
    # cold, then warm at another end (the result cache must not answer)
    for end_s in (int(ts_row[-1]) // 1000 - 17, int(ts_row[-1]) // 1000 - 4):
        wends = end_s * 1000 - np.arange(
            TSBS_SPAN_S // TSBS_STEP_S + 1, dtype=np.int64)[::-1] \
            * TSBS_STEP_S * 1000
        want = ref_sum_over_time(ts_row, csum, wends, TSBS_RANGE_MS) \
            / (TSBS_RANGE_MS // STEP_MS)
        before = cli.counters()
        body, secs = cli.query(path, query=promql, start=end_s - TSBS_SPAN_S,
                               end=end_s, step=TSBS_STEP_S)
        after = cli.counters()
        d = route_delta(before, after)
        times.append(secs)
        rows = body["data"]["result"]
        assert len(rows) == hosts, (len(rows), hosts)
        for row in rows:
            i = int(row["metric"]["hostname"].split("_")[1])
            got = np.array([float(v) for _, v in row["values"]])
            assert [int(float(t)) for t, _ in row["values"]] \
                == (wends // 1000).tolist(), (i, "timestamps differ")
            err = np.abs(got - want[i])
            assert (err <= TOL["other"]["atol"]
                    + TOL["other"]["rtol"] * np.abs(want[i])).all(), \
                (i, got, want[i])
            worst = max(worst, float((err / np.maximum(np.abs(want[i]),
                                                       1e-300)).max()))
        tiles = int(after.get("fused_band_tiles_total", 0)
                    - before.get("fused_band_tiles_total", 0))
        assert d.get("leaf_fused_kernel_total", 0) >= 1 and tiles >= 1, \
            (d, tiles)
        for bad in ("leaf_general_path_total", "leaf_fused_errors_total",
                    "leaf_host_gather_total", "leaf_inexact_times_total",
                    "leaf_host_routed_total"):
            assert after.get(bad, 0) == before.get(bad, 0), (bad, d)
    emit("query", name="double-groupby", promql=promql, hosts=hosts,
         samples=TSBS_T, series_per_shard=per_shard,
         checked_vs_f64_reference=True, max_rel_err=worst,
         first_s=round(times[0], 4), warm_s=round(times[1], 4),
         route_warm=d, fused_kernel=True, band_tiles=tiles)


# A fleet that churns (benchmark cell promchurn-counters-262k.open): counters
# at a scrape offset of their own, a quarter of them short of samples
CHURN_DATASET = "churn"


def churned_fleet(server, cli, series, T):
    """`sum by (_ns_)(rate(churned_total[5m]))` over counters scraped at
    offsets of their own of which every fourth starts late, ends early or
    misses scrapes, every cell against the f64 reference of the samples
    that exist (the benchmark's `references/churned_scrapes.py`): the mirror
    places the rows on the scrape grid's slots, every leaf runs the RAGGED
    fused kernel, and its working sets are stored whole rows first: a launch
    books the rows of its sets and those the dense body runs over, and both
    counters must move."""
    from benchmark.references.churned_scrapes import series_increase
    rng = np.random.default_rng([ARGS.seed, 50])
    mapper, spread = server.mappers[CHURN_DATASET], \
        server.spreads[CHURN_DATASET]
    ts_row = START_MS + np.arange(T, dtype=np.int64) * STEP_MS
    keys = [PartKey.make("churned_total", {
        "_ws_": "demo", "_ns_": f"App-{i % NUM_APPS}",
        "instance": f"Instance-{i}"}) for i in range(series)]
    shard_of = np.fromiter(
        (mapper.ingestion_shard(pk.shard_key_hash(), pk.partition_hash(),
                                spread.spread_for(pk.shard_key()))
         for pk in keys), np.int64, series)
    phase = rng.integers(0, STEP_MS, series)
    vals = counter_chunk(rng, np.empty((series, T)))
    exists = np.ones((series, T), bool)
    # the short rows' lives: (first scrape, last + 1, first missed, the
    # first after), at one of sixteen scrapes each, so that the rows of one
    # life load as one rectangle
    life = np.tile(np.array([0, T, T, T]), (series, 1))
    short = np.flatnonzero(np.arange(series) % 4 == 3)
    at = T // 8 + rng.integers(0, 16, short.size) * (3 * T // 64)
    life[short[short % 3 == 0], 0] = at[short % 3 == 0]     # a late start
    life[short[short % 3 == 1], 1] = at[short % 3 == 1]     # an early end
    life[short[short % 3 == 2], 2] = at[short % 3 == 2]     # missed scrapes
    life[short[short % 3 == 2], 3] = at[short % 3 == 2] + 3
    for (a, b, c, d) in np.unique(life, axis=0):
        rows = np.flatnonzero((life == (a, b, c, d)).all(axis=1))
        exists[rows, :a] = exists[rows, b:] = exists[rows, c:d] = False
        for lo, hi in ((a, min(b, c)), (d, b)):
            if hi <= lo:
                continue
            for sh in server.memstore.shards_for(CHURN_DATASET):
                idx = rows[shard_of[rows] == sh.shard_num]
                if idx.size:
                    got = sh.ingest_columns(
                        "prom-counter", [keys[i] for i in idx],
                        ts_row[None, lo:hi] + phase[idx, None],
                        {"count": vals[idx, lo:hi]}, offset=0)
                    assert got == idx.size * (hi - lo), (got, lo, hi)
    end_s = int(ts_row[-1]) // 1000
    promql = "sum by (_ns_)(rate(churned_total[5m]))"
    path = f"/promql/{CHURN_DATASET}/api/v1/query_range"
    gids = np.arange(series) % NUM_APPS
    times, worst = [], 0.0
    for back in (0, 1):                 # cold, then warm a step earlier
        wends = window_grid((end_s - back * QSTEP_S) * 1000, N_WINDOWS)
        want = GroupSums(wends)
        want.add(series_increase(ts_row, phase, vals, exists, wends,
                                 RANGE_MS), gids)
        before = cli.counters()
        body, secs = cli.query(path, query=promql,
                               start=int(wends[0]) // 1000,
                               end=int(wends[-1]) // 1000, step=QSTEP_S)
        after = cli.counters()
        times.append(secs)
        worst = max(worst, compare(by_ns(body),
                                   want.by_ns(1000.0 / RANGE_MS), "counter",
                                   f"churned-fleet#{back}"))
        moved = {k: int(after.get(k, 0) - before.get(k, 0)) for k in (
            "leaf_ragged_fused_total", "fused_enqueues_total",
            "fused_set_rows_total", "fused_whole_rows_total",
            "leaf_general_path_total", "leaf_fused_errors_total",
            "leaf_host_gather_total", "leaf_offgrid_total",
            "leaf_host_routed_total")}
        assert moved["leaf_ragged_fused_total"] >= 1 \
            and moved["fused_enqueues_total"] >= 1, moved
        # the sets hold both kinds of row: the dense body ran over the
        # whole ones, three quarters of the fleet and their rungs' padding
        assert 0 < moved["fused_whole_rows_total"] \
            < moved["fused_set_rows_total"], moved
        for bad in ("leaf_general_path_total", "leaf_fused_errors_total",
                    "leaf_host_gather_total", "leaf_offgrid_total",
                    "leaf_host_routed_total"):
            assert moved[bad] == 0, (bad, moved)
    emit("query", name="churned-fleet", promql=promql, series=series,
         short_rows=int(short.size), checked_vs_f64_reference=True,
         max_rel_err=worst, first_s=round(times[0], 4),
         warm_s=round(times[1], 4), route_warm=moved,
         ragged_fused_kernel=True,
         whole_row_share=round(moved["fused_whole_rows_total"]
                               / moved["fused_set_rows_total"], 4))


def cache_state(path):
    n = len(os.listdir(path)) if path and os.path.isdir(path) else 0
    return {"dir": path, "entries": n}


def main():
    T, S = ARGS.samples, ARGS.series
    n_write = 4096 if S >= 32768 else max(S // 8, 8)
    # every gauge shard must hold more than query.host_route_max_samples
    # (2M) in the queried hour, or the host answers in the device's place
    S_gauge = 512 if ARGS.rehearse else max(S // 8, 32_768)
    emit("start", device=DEVICE, series=S, samples=T, chips=ARGS.chips,
         rehearsal=ARGS.rehearse, seed=ARGS.seed,
         native=("built here" if native.lib is not None else "none"),
         note="smoke run, not a benchmark")
    if S < FLAGSHIP_SERIES and not ARGS.rehearse:
        emit("cut", series=S, samples=T, flagship_series=FLAGSHIP_SERIES,
             reason="1,048,576 x 720 ran out of the one-chip machine's "
                    "40 GiB of host memory during the first query's mirror "
                    "build, and the phases that follow the series count "
                    "(routing, partition creation, the host-bound query "
                    "routes, one compile per shard shape) would not fit "
                    "the 1200 s limit with fewer samples either: series "
                    "cut to the size that has passed on the chip, samples "
                    "kept (CHANGES.md PR 24)"
             if S == DEFAULT_SERIES else "--series given by the caller")

    cache_hits = {"hit": 0, "miss": 0}

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            cache_hits["hit"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            cache_hits["miss"] += 1
    jax.monitoring.register_event_listener(on_event)

    server = FiloServer([DatasetConfig(DATASET, 4),
                         DatasetConfig(TSBS_DATASET, 4),
                         DatasetConfig(CHURN_DATASET, 4)],
                        http_host="127.0.0.1", http_port=0)
    cache_dir = apply_jax_runtime(server.config)
    cache0 = cache_state(cache_dir)
    server.start()
    try:
        cli = Client(server.http.port)
        if ARGS.chips == 1:
            run_one_chip(server, cli, S, T, S_gauge, n_write)
        else:
            run_four_chips(server, cli, S, T, S_gauge)
        emit("compile_cache", before=cache0, after=cache_state(cache_dir),
             persistent_cache_hits=cache_hits["hit"],
             persistent_cache_misses=cache_hits["miss"],
             env_var_set=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))
        emit("memory", host_peak_rss_bytes=resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024, devices={
            str(d): {k: v for k, v in (d.memory_stats() or {}).items()
                     if k in ("bytes_in_use", "peak_bytes_in_use",
                              "bytes_limit")}
            for d in jax.local_devices()})
    finally:
        server.shutdown()
    print(json.dumps({"ok": True, "device": DEVICE}), flush=True)


def run_one_chip(server, cli, S, T, S_gauge, n_write):
    end_ms = START_MS + (T - 1) * STEP_MS          # newest loaded sample
    end_s = end_ms // 1000
    grid_a = window_grid(end_ms, N_WINDOWS + REPEATS)
    end2_ms = end_ms + STEP_MS                     # the remote-written scrape
    end2_s = end2_ms // 1000
    grid_b = window_grid(end2_ms, N_WINDOWS + 1)
    inc_a, sot_a, inc_b = GroupSums(grid_a), GroupSums(grid_a), \
        GroupSums(grid_b)
    # the newer scrape continues each written series' counter: its value
    # depends on the series' last loaded value, recorded by load()
    newer = np.zeros(n_write)
    bump = np.random.default_rng([ARGS.seed, 1 << 20]) \
        .exponential(10.0, size=n_write)
    t0 = time.perf_counter()
    per_shard = load(server, "request_total", "prom-counter", "count", S, T,
                     ARGS.seed, counter_chunk, inc=inc_a, sot=sot_a,
                     written=(newer, bump, inc_b))
    g_sot = GroupSums(grid_a)
    load(server, "heap_usage", "gauge", "value", S_gauge, T, ARGS.seed + 1,
         gauge_chunk, sot=g_sot)
    emit("loaded", total_s=round(time.perf_counter() - t0, 2),
         samples=(S + S_gauge) * T)

    m0 = cli.counters()
    # --- uniform grid: the fused kernel must serve every request
    rate_q = "sum by (_ns_)(rate(request_total[5m]))"
    t_rate = timed_queries(cli, "rate", "/api/v1/query_range", rate_q,
                           inc_a.by_ns(1000.0 / RANGE_MS), "counter", end_s,
                           expect_fused=True)
    m1 = cli.counters()
    emit("mirror", note="built by the first query",
         full_uploads=int(m1.get("device_mirror_refreshes_total", 0)
                          - m0.get("device_mirror_refreshes_total", 0)),
         build_s=round(m1.get("device_mirror_full_upload_seconds_sum", 0)
                       - m0.get("device_mirror_full_upload_seconds_sum", 0),
                       3),
         first_query_s=round(t_rate[0], 3))
    timed_queries(cli, "increase", "/api/v1/query_range",
                  "sum(increase(request_total[5m]))", inc_a.total(),
                  "counter", end_s, expect_fused=True)
    # sum_over_time needs a counter column's RAW values, so by design it
    # bypasses the (reset-corrected) device mirror: host gather, general
    # XLA path, working set shipped per query.  Checked; one warm repeat
    emit("cut", queries=["sum_over_time(counter)", "rate-instant when the "
                         "program routes it to the host"],
         warm_repeats=HOST_BOUND_REPEATS,
         reason="host-bound routes: 21 s and 34 s per request at 262,144 "
                "series on the one-chip machine (CHANGES.md PR 24); five "
                "warm repeats of each would not leave the smoke inside its "
                "1200 s limit")
    timed_queries(cli, "sum_over_time(counter)", "/api/v1/query_range",
                  "sum by (_ns_)(sum_over_time(request_total[5m]))",
                  sot_a.by_ns(), "other", end_s, expect_fused=None,
                  repeats=HOST_BOUND_REPEATS)
    timed_queries(cli, "sum_over_time(gauge)", "/api/v1/query_range",
                  "sum by (_ns_)(sum_over_time(heap_usage[5m]))",
                  g_sot.by_ns(), "other", end_s, expect_fused=True)
    # the instant form scans 5 minutes per series: below
    # query.host_route_max_samples per shard the program answers it on the
    # host by design (on a TPU backend), above it the fused kernel must
    inst_scan = per_shard * (RANGE_MS // STEP_MS)
    cap = server.config.query.host_route_max_samples
    inst_fused = True if DEVICE["platform"] != "tpu" or inst_scan.min() > cap \
        else False
    timed_queries(cli, "rate-instant", "/api/v1/query", rate_q,
                  inc_a.by_ns(1000.0 / RANGE_MS), "counter", end_s,
                  expect_fused=inst_fused, instant=True,
                  repeats=REPEATS if inst_fused else HOST_BOUND_REPEATS)
    if not inst_fused:
        emit("note", query="rate-instant", est_scan_per_shard=inst_scan
             .tolist(), host_route_max_samples=cap,
             text="may be answered on the host by design at this size; "
                  "not counted as a device check")

    # --- the remote-write door: one newer scrape for n_write series
    ws = [remotepb.PromTimeSeries(
        [("__name__", "request_total"), ("_ws_", "demo"),
         ("_ns_", f"App-{i % NUM_APPS}"), ("instance", f"Instance-{i}"),
         ("dc", f"DC{i % 2}")], [(float(newer[i]), int(end2_ms))])
        for i in range(n_write)]
    payload = snappy.compress(remotepb.encode_write_request(ws))
    before = cli.counters()
    t0 = time.perf_counter()
    status = cli.post_write(payload)
    w_s = time.perf_counter() - t0
    after = cli.counters()
    assert 200 <= status < 300, status
    got = int(after.get("remote_write_samples_total", 0)
              - before.get("remote_write_samples_total", 0))
    assert got == n_write, (got, n_write)
    emit("remote_write", status=status, series=n_write, bytes=len(payload),
         seconds=round(w_s, 4), samples_ingested=got)

    # --- after the ack: the grid is no longer uniform.  The repeat query
    # must still be answered on the device (ragged kernel or general XLA)
    before = cli.counters()
    dev0 = device_dispatches(cli.devices())
    want_b = inc_b.by_ns(1000.0 / RANGE_MS)
    body, secs = cli.query("/api/v1/query_range", query=rate_q,
                           start=end2_s - QSPAN_S, end=end2_s, step=QSTEP_S)
    d = route_delta(before, cli.counters())
    dev1 = device_dispatches(cli.devices())
    err = compare(by_ns(body), subset(want_b, end2_s - QSPAN_S, end2_s),
                  "counter", "rate-after-write")
    for bad in ("leaf_host_routed_total", "leaf_fused_errors_total",
                "device_mirror_query_fallbacks_total"):
        assert bad not in d, ("rate-after-write", d)
    on_dev = {k: dev1[k] - dev0.get(k, 0) for k in dev1
              if dev1[k] != dev0.get(k, 0)}
    # the general path's jit has no telemetry entry of its own: that it ran
    # on device-resident rows shows as mirror_gather dispatches with no
    # host route and no mirror fallback
    assert on_dev, "no device dispatch served the non-uniform repeat query"
    route = ("ragged fused kernel" if d.get("leaf_fused_kernel_total")
             else "general XLA path over device-mirror rows")
    body2, secs2 = cli.query("/api/v1/query_range", query=rate_q,
                             start=end2_s - QSPAN_S - QSTEP_S,
                             end=end2_s - QSTEP_S, step=QSTEP_S)
    err = max(err, compare(
        by_ns(body2), subset(want_b, end2_s - QSPAN_S - QSTEP_S,
                             end2_s - QSTEP_S), "counter",
        "rate-after-write#1"))
    emit("query", name="rate-after-write", promql=rate_q,
         checked_vs_f64_reference=True, acknowledged_write_visible=True,
         max_rel_err=err, first_s=round(secs, 4), warm_s=round(secs2, 4),
         route=d, served_on_device_by=route, device_dispatches=on_dev)

    sel = 'request_total{instance=~"Instance-(%s)"}' % \
        range_regex(n_write - 1)
    before = cli.counters()
    body, secs = cli.query("/api/v1/query", query=sel, time=end2_s)
    d = route_delta(before, cli.counters())
    rows = body["data"]["result"]
    assert len(rows) == n_write, (len(rows), n_write)
    worst = 0.0
    for row in rows:
        i = int(row["metric"]["instance"].split("-")[1])
        t, v = row["value"]
        assert int(float(t)) == end2_s
        w = newer[i]
        assert abs(float(v) - w) <= 1e-6 * abs(w), (i, v, w)
        worst = max(worst, abs(float(v) - w) / abs(w))
    emit("query", name="read-back-selector", series=len(rows),
         checked_vs_written_values=True, max_rel_err=worst,
         seconds=round(secs, 4), route=d,
         note="a raw selector under query.host_route_max_samples, gathered "
              "on the host by design: not a device check")

    # --- rows of thirteen hours, a group a host: the band in tiles.  Every
    # shard's leaf must scan more than query.host_route_max_samples
    double_groupby(server, cli, 64 if ARGS.rehearse else 4_000)

    # --- a fleet that churns: the ragged kernel over a placed mirror, its
    # working sets stored whole rows first.  Every shard's leaf must scan
    # more than query.host_route_max_samples
    churned_fleet(server, cli, 256 if ARGS.rehearse else 65_536, T)

    # --- the device, by the program's own telemetry
    m2 = cli.counters()
    for bad in ("leaf_fused_errors_total", "warmup_compile_errors_total"):
        assert m2.get(bad, 0) == m0.get(bad, 0), bad
    devs = cli.devices()
    plat = DEVICE["platform"].upper()
    on = {k: v for k, v in devs.items() if plat in k.upper()}
    assert on, ("no device of the platform in /admin/devices", list(devs))
    for k, st in on.items():
        assert st["dispatches"] > 0 and st["hbm"].get("hot", 0) > 0, (k, st)
    emit("devices", admin_devices={
        k: {"dispatches": st["dispatches"], "compiles": st["compiles"],
            "compile_s": st["compileSeconds"], "hbm": st["hbm"],
            "kernels": st["kernels"]} for k, st in devs.items()},
        route_counters={k: int(m2.get(k, 0)) for k in ROUTE_COUNTERS})


def run_four_chips(server, cli, S, T, S_gauge):
    """What exists only across chips, on the path that serves: one mirror
    per device and a fused call on every device behind the served queries,
    their partials merged on the host, each answer against the f64
    reference, and nothing else."""
    end_ms = START_MS + (T - 1) * STEP_MS
    end_s = end_ms // 1000
    grid_a = window_grid(end_ms, N_WINDOWS + REPEATS)
    inc_a, g_sot = GroupSums(grid_a), GroupSums(grid_a)
    load(server, "request_total", "prom-counter", "count", S, T, ARGS.seed,
         counter_chunk, inc=inc_a)
    load(server, "heap_usage", "gauge", "value", S_gauge, T, ARGS.seed + 1,
         gauge_chunk, sot=g_sot)
    m0 = cli.counters()
    rate_q = "sum by (_ns_)(rate(request_total[5m]))"
    timed_queries(cli, "rate", "/api/v1/query_range", rate_q,
                  inc_a.by_ns(1000.0 / RANGE_MS), "counter", end_s,
                  expect_fused=True)
    timed_queries(cli, "sum_over_time(gauge)", "/api/v1/query_range",
                  "sum by (_ns_)(sum_over_time(heap_usage[5m]))",
                  g_sot.by_ns(), "other", end_s, expect_fused=True)
    devs = cli.devices()
    plat = DEVICE["platform"].upper()
    used = {k: st for k, st in devs.items() if plat in k.upper()
            and st["hbm"].get("hot", 0) > 0
            and any(name.startswith("fused_") and k2["count"] > 0
                    for name, k2 in st["kernels"].items())}
    emit("devices", admin_devices={
        k: {"dispatches": st["dispatches"], "hbm": st["hbm"],
            "kernels": st["kernels"]} for k, st in devs.items()})
    assert len(used) == ARGS.chips, \
        f"mirror bytes + fused dispatches on {len(used)} devices, " \
        f"want {ARGS.chips}: {sorted(used)}"

    m1 = cli.counters()
    for bad in ("leaf_fused_errors_total", "warmup_compile_errors_total",
                "leaf_host_routed_total"):
        assert m1.get(bad, 0) == m0.get(bad, 0), bad


if __name__ == "__main__":
    main()
