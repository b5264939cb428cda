"""One span tree per served query (ISSUE 27): a `query_range` over the HTTP
door yields one trace whose spans form a single tree under `http.request`,
on the monotonic clock, with the self times booked by the program into
`span_<name>_self_seconds_total` / `span_<name>_calls_total`, and mirrored
into a `jax.profiler` session as `filodb:<name>` annotations."""
import collections
import glob
import json
import os
import time
import urllib.request

import numpy as np
import pytest

from filodb_tpu.config import FilodbSettings
from filodb_tpu.core.partkey import PartKey
from filodb_tpu.standalone import DatasetConfig, FiloServer
from filodb_tpu.utils import metrics
from filodb_tpu.utils.metrics import registry

START_MS = 1_600_000_000_000
SERIES, SAMPLES, SHARDS = 2048, 720, 4
END_S = START_MS // 1000 + 7100
QUERY = "sum%20by%20(_ns_)(rate(request_total[5m]))"


class Rig:
    """One FiloServer on port 0 with four shards of counters, interpret-mode
    kernels, and a client that never asks the same grid twice (every
    request is a result-cache miss)."""

    def __init__(self):
        self.cfg = FilodbSettings()
        self.srv = FiloServer([DatasetConfig("prometheus", SHARDS)],
                              config=self.cfg, http_host="127.0.0.1",
                              http_port=0)
        self.srv.start()
        ts = START_MS + np.arange(SAMPLES, dtype=np.int64) * 10_000
        keys = [PartKey.make("request_total", {
            "_ws_": "demo", "_ns_": f"App-{i % 10}",
            "instance": f"Instance-{i}", "dc": f"DC{i % 2}"})
            for i in range(SERIES)]
        mapper = self.srv.mappers["prometheus"]
        spread = self.srv.spreads["prometheus"]
        shard_of = np.array([mapper.ingestion_shard(
            pk.shard_key_hash(), pk.partition_hash(),
            spread.spread_for(pk.shard_key())) for pk in keys])
        vals = np.cumsum(np.random.default_rng(27).random((SERIES, SAMPLES)),
                         axis=1)
        for sh in self.srv.memstore.shards_for("prometheus"):
            idx = np.flatnonzero(shard_of == sh.shard_num)
            assert idx.size, "every shard must hold series"
            sh.ingest_columns("prom-counter", [keys[i] for i in idx],
                              np.broadcast_to(ts, (idx.size, SAMPLES)),
                              {"count": vals[idx]}, offset=0)
        self.base = f"http://127.0.0.1:{self.srv.http.port}"
        self.asked = 0
        self.query()                # builds the mirrors, compiles

    def get(self, path, headers=None):
        req = urllib.request.Request(self.base + path, headers=headers or {})
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.read()

    def query(self, extra="", headers=None):
        self.asked += 1
        end = END_S - 60 * self.asked
        body = json.loads(self.get(
            f"/api/v1/query_range?query={QUERY}&start={end - 3600}"
            f"&end={end}&step=60{extra}", headers))
        assert body["status"] == "success", body
        self.tree(body["traceID"])      # returns once the tree is whole
        return body

    def tree(self, trace_id):
        """The request's tree.  `http.request` and `http.write` exit AFTER
        the socket write, so the client holds the reply a moment before
        the root is in the trace (and in the counters, and on the
        profiler's line): wait for it."""
        deadline = time.monotonic() + 10.0
        while True:
            data = json.loads(self.get(f"/admin/traces/{trace_id}"))["data"]
            if any(e["name"] == "http.request" for e in data["spans"]):
                return data["spans"], data
            assert time.monotonic() < deadline, "the root never landed"
            time.sleep(0.002)

    def counters(self):
        """The span families as /metrics names them, summed per family
        (as the benchmark's scrape does), at the registry's own precision
        (the text exposition prints six digits)."""
        names = {line.rpartition(" ")[0].split("{")[0]
                 for line in self.get("/metrics").decode().splitlines()}
        out = {}
        for name, _, val in registry.snapshot_samples():
            if name.startswith("span_"):
                assert name in names, name
                out[name] = out.get(name, 0.0) + val
        return out


@pytest.fixture(scope="module")
def rig():
    old = os.environ.get("FILODB_TPU_FUSED_INTERPRET")
    os.environ["FILODB_TPU_FUSED_INTERPRET"] = "1"
    r = Rig()
    try:
        yield r
    finally:
        r.srv.shutdown()
        if old is None:
            del os.environ["FILODB_TPU_FUSED_INTERPRET"]
        else:
            os.environ["FILODB_TPU_FUSED_INTERPRET"] = old


PARTS = ("leaf.enqueue_pack", "leaf.enqueue_jit")


def real(evs):
    """The spans proper: `kernel_dispatch` and the parts of
    `leaf.kernel_enqueue` (metrics.span_part) report durations that spans
    already measured, and book no self time."""
    return [e for e in evs if e["name"] not in ("kernel_dispatch",) + PARTS]


def children_of(evs):
    kids = collections.defaultdict(list)
    for e in evs:
        kids[e["parent_id"]].append(e)
    return kids


def test_one_request_is_one_tree_under_http_request(rig):
    evs, data = rig.tree(rig.query()["traceID"])
    by_id = {e["span_id"]: e for e in evs}
    assert len(by_id) == len(evs), "span ids are unique"
    roots = [e for e in evs if e["parent_id"] is None]
    assert [r["name"] for r in roots] == ["http.request"]
    assert {e["trace_id"] for e in evs} == {data["traceID"]}
    for e in evs:
        if e["parent_id"] is None:
            continue
        p = by_id[e["parent_id"]]       # every parent id resolves
        assert p["start_ns"] <= e["start_ns"], (p["name"], e["name"])
        assert e["start_ns"] + e["dur_ns"] <= p["start_ns"] + p["dur_ns"], \
            (p["name"], e["name"])
    names = {e["name"] for e in evs}
    assert {"http.route", "http.present", "http.encode", "http.write",
            "frontend.serve", "frontend.cache_lookup", "frontend.queue_wait",
            "query_parse", "query_plan", "execplan", "engine.present",
            "exec.ReduceAggregateExec", "exec.MultiSchemaPartitionsExec",
            "leaf.index_lookup", "leaf.page_check",
            "leaf.mirror_fresh", "leaf.counts_copy",
            "leaf.fused_prepare", "leaf.kernel_enqueue", "leaf.result_fetch",
            "leaf.present"} <= names
    # the padded values are cached: nobody reads the mirror's rows, so
    # no row is gathered out of it (ISSUE 34)
    assert "leaf.mirror_gather" not in names
    # the legacy path: the names entered since the innermost trace context
    assert any(e["span"] == "execplan" for e in evs)
    # one wall-clock anchor a trace, and events in start order
    assert data["anchor"]["unixNs"] > 1_600_000_000 * 10 ** 9
    starts = [e["start_ns"] for e in evs]
    assert starts == sorted(starts)


def test_self_times_of_the_tree_sum_to_the_roots_duration(rig):
    evs = real(rig.tree(rig.query()["traceID"])[0])
    kids = children_of(evs)
    self_ns = {e["span_id"]: e["dur_ns"] - sum(k["dur_ns"]
                                               for k in kids[e["span_id"]])
               for e in evs}
    assert all(v >= 0 for v in self_ns.values())
    root = next(e for e in evs if e["parent_id"] is None)
    assert sum(self_ns.values()) == pytest.approx(root["dur_ns"], rel=0.01)


@pytest.mark.parametrize("hoisted", [True, False],
                         ids=["exprfuse_hoists_the_leaves", "leaves_in_tree"])
def test_four_leaves_each_hold_enqueue_and_fetch(rig, hoisted):
    """With whole-expression compilation (the default, and what the
    benchmark's cells run) the engine runs every leaf's gather + preflight
    and one merged dispatch BEFORE the tree: `engine.prepare_leaves` holds
    one `leaf.prepare` a shard, `engine.dispatch_leaves` ONE enqueue and
    then ONE fetch for the four shards' working sets (ISSUE 36: they share
    a plan and a device, so they are one device program).  Without it each
    `exec.MultiSchemaPartitionsExec` holds its own."""
    rig.cfg.query.exprfuse_enabled = hoisted
    try:
        evs = real(rig.tree(rig.query()["traceID"])[0])
    finally:
        rig.cfg.query.exprfuse_enabled = True
    kids = children_of(evs)
    leaves = [e for e in evs if e["name"] == "exec.MultiSchemaPartitionsExec"]
    assert len(leaves) == SHARDS

    def under(e):
        out = []
        for k in kids[e["span_id"]]:
            out += [k["name"]] + under(k)
        return out

    if hoisted:
        prep = [e for e in evs if e["name"] == "leaf.prepare"]
        assert sorted(e["shard"] for e in prep) == ["0", "1", "2", "3"]
        assert all(by["name"] == "engine.prepare_leaves" for by in
                   (next(p for p in evs if p["span_id"] == e["parent_id"])
                    for e in prep))
        for e in prep:
            assert {"leaf.index_lookup", "leaf.mirror_fresh",
                    "leaf.counts_copy", "leaf.fused_prepare"} <= set(under(e))
        disp = next(e for e in evs if e["name"] == "engine.dispatch_leaves")
        got = collections.Counter(under(disp))
        assert got["leaf.kernel_enqueue"] == 1
        assert got["leaf.result_fetch"] == 1
        assert got["leaf.present"] == 1
        # phase A enqueues everything before phase B reads anything back
        enq = [e for e in evs if e["name"] == "leaf.kernel_enqueue"]
        fet = [e for e in evs if e["name"] == "leaf.result_fetch"]
        assert max(e["start_ns"] for e in enq) < min(e["start_ns"]
                                                     for e in fet)
        assert all(not under(e) for e in leaves)    # they pick the parked up
    else:
        for e in leaves:
            got = collections.Counter(under(e))
            assert got["leaf.kernel_enqueue"] == 1
            assert got["leaf.result_fetch"] == 1
            assert got["leaf.fused_prepare"] == 1


def test_a_miss_gathers_inside_fused_prepare(rig):
    """With no padded copy cached (a new snapshot generation, or the first
    query) the leaf reads its values and their vbase: two takes a leaf,
    each a `leaf.mirror_gather` where it runs, inside `leaf.fused_prepare`
    and beside `leaf.pad_values`; nothing reads `ts_off`."""
    from filodb_tpu.query.execbase import _FUSED_CACHE_LOCK, _FUSED_VALS_CACHE
    with _FUSED_CACHE_LOCK:
        _FUSED_VALS_CACHE.clear()
    evs = real(rig.tree(rig.query()["traceID"])[0])
    by_id = {e["span_id"]: e for e in evs}
    takes = [e for e in evs if e["name"] == "leaf.mirror_gather"]
    assert len(takes) == 2 * SHARDS
    assert {by_id[e["parent_id"]]["name"] for e in takes} \
        == {"leaf.fused_prepare"}
    assert sum(e["name"] == "leaf.pad_values" for e in evs) == SHARDS


def test_counters_match_the_tree(rig):
    before = rig.counters()
    evs = real(rig.tree(rig.query()["traceID"])[0])
    after = rig.counters()
    kids = children_of(evs)
    want_self = collections.Counter()
    want_calls = collections.Counter()
    for e in evs:
        flat = "span_" + e["name"].replace(".", "_")
        want_self[flat] += (e["dur_ns"] - sum(
            k["dur_ns"] for k in kids[e["span_id"]])) * 1e-9
        want_calls[flat] += 1
    for flat, calls in want_calls.items():
        assert after[flat + "_calls_total"] \
            - before.get(flat + "_calls_total", 0.0) == calls, flat
        got = after[flat + "_self_seconds_total"] \
            - before.get(flat + "_self_seconds_total", 0.0)
        assert got == pytest.approx(want_self[flat], rel=1e-6, abs=1e-9), flat
    # one family a span: the read-path spans a plain counter, the
    # older spans (hist=True) their histogram
    assert "span_http_request_seconds_total" in after
    assert "span_leaf_result_fetch_seconds_bucket" not in "".join(after)
    assert any(k.startswith("span_query_parse_seconds_bucket") for k in after)
    assert "span_query_parse_seconds_total" not in after


def test_enqueue_parts_stay_inside_kernel_enqueue(rig):
    """The packing and the jit call of an enqueue are child events of
    `leaf.kernel_enqueue` that leave its self time whole (the benchmark's
    kernel_enqueue_ms reads it), book seconds and calls but no self
    family, and ride beside the uploads-per-enqueue counters."""
    def fused():
        return tuple(registry.counter(c).value for c in (
            "fused_enqueues", "fused_enqueue_sets", "fused_enqueue_uploads"))

    before, fused0 = rig.counters(), fused()
    evs = rig.tree(rig.query()["traceID"])[0]
    after, fused1 = rig.counters(), fused()

    def delta(name):
        return after[name] - before.get(name, 0.0)

    enqueues = [e for e in evs if e["name"] == "leaf.kernel_enqueue"]
    assert len(enqueues) == 1           # one call for the request's leaves
    kids = children_of(evs)
    for enq in enqueues:
        mine = [k for k in kids[enq["span_id"]] if k["name"] in PARTS]
        assert [k["name"] for k in mine] == list(PARTS)
        assert sum(k["dur_ns"] for k in mine) <= enq["dur_ns"]
        assert all(k["start_ns"] >= enq["start_ns"] for k in mine)
    self_s = delta("span_leaf_kernel_enqueue_self_seconds_total")
    assert self_s == pytest.approx(
        sum(e["dur_ns"] for e in enqueues) * 1e-9, rel=1e-6)
    for part in PARTS:
        flat = "span_" + part.replace(".", "_")
        assert delta(flat + "_calls_total") == 1
        assert 0.0 < delta(flat + "_seconds_total") <= self_s
        assert flat + "_self_seconds_total" not in after
    # one dispatch for the request, carrying a working set a shard, and
    # of the plan only its rows go up, once
    assert tuple(b - a for a, b in zip(fused0, fused1)) == (1, SHARDS, 1)


def test_profiler_session_carries_the_spans(rig, tmp_path):
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1          # what benchmark/run.py traces with
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        evs = real(rig.tree(rig.query()["traceID"])[0])
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[-1]
    found = collections.Counter()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            names = [ev.name for ev in line.events]
            if "filodb:http.request" in names:
                # the connection's spans lie around the tree, on the same
                # line (and so may those of a later connection whose
                # thread took the id over)
                conn = [n for n in names if n.startswith("filodb:conn.")]
                found.update(n for n in names if n.startswith("filodb:")
                             and n not in conn)
                parts = [n for n in names if n.startswith("filodb-part:")]
    assert "filodb:conn.read_request" in conn
    assert sorted(parts) == sorted("filodb-part:" + p for p in PARTS)
    assert found["filodb:leaf.kernel_enqueue"] == 1
    assert found["filodb:leaf.result_fetch"] == 1
    assert found["filodb:http.request"] == 1
    # the request's whole tree is on that one thread's line
    assert sum(found.values()) == len(evs)


def test_snapshot_read_counts_torn_reads_and_lock_fallbacks(rig):
    shard = rig.srv.memstore.shards_for("prometheus")[0]
    store = next(iter(shard.stores.values()))
    torn = registry.counter("snapshot_read_torn")
    fell = registry.counter("snapshot_read_lock_fallbacks")
    t0, f0 = torn.value, fell.value
    writes = []

    def read_while_a_writer_lands_once():
        if not writes:
            writes.append(1)
            store.generation += 2       # a whole mutation, mid-read
        return "ok"

    assert shard.snapshot_read(store, read_while_a_writer_lands_once) == "ok"
    assert (torn.value - t0, fell.value - f0) == (1, 0)

    def read_while_writers_never_stop():
        store.generation += 2
        return "locked"

    assert shard.snapshot_read(store, read_while_writers_never_stop,
                               retries=3) == "locked"
    assert (torn.value - t0, fell.value - f0) == (4, 1)


def test_traceparent_is_accepted_on_queries(rig):
    tid = "4bf92f3577b34da6a3ce929d0e0e4736"
    body = rig.query(headers={
        "traceparent": f"00-{tid}-00f067aa0ba902b7-01"})
    assert body["traceID"] == tid
    evs, data = rig.tree(tid)
    assert data["traceID"] == tid and evs[0]["name"] == "http.request"
    assert data.get("verdict") == "completed"


def test_stats_phases_are_the_spans_clock(rig):
    body = rig.query("&stats=true")
    phases = body["stats"]["phases"]
    evs = real(rig.tree(body["traceID"])[0])
    dur = {n: sum(e["dur_s"] for e in evs if e["name"] == n)
           for n in ("query_parse", "query_plan", "frontend.queue_wait",
                     "engine.prepare_leaves", "engine.dispatch_leaves")}
    assert phases["parse_s"] == pytest.approx(dur["query_parse"], abs=2e-6)
    assert phases["plan_s"] == pytest.approx(dur["query_plan"], abs=2e-6)
    assert phases["queue_s"] == pytest.approx(dur["frontend.queue_wait"],
                                              abs=2e-6)
    # the leaves' hoisted work is in the phases (it was lost before):
    # host + device + transfer cover both engine spans
    hoisted = dur["engine.prepare_leaves"] + dur["engine.dispatch_leaves"]
    assert phases["exec_s"] + phases["device_s"] + phases["transfer_s"] \
        >= 0.95 * hoisted
    assert body["stats"]["devices"], "the per-kernel split came along"


def test_the_operators_plane_is_not_traced(rig):
    calls = registry.counter("span_http_request_calls")
    ids = len(metrics.collector.trace_ids())
    c0 = calls.value
    rig.get("/metrics")
    rig.get("/admin/jobs")
    rig.get("/healthz")
    assert calls.value == c0
    assert len(metrics.collector.trace_ids()) == ids


def test_spans_off_keeps_the_clock_and_books_nothing():
    c = registry.counter("span_off_probe_calls")
    metrics.set_spans_enabled(False)
    try:
        with metrics.trace_context("spans-off"), \
                metrics.span("off.probe") as sp:
            pass
    finally:
        metrics.set_spans_enabled(True)
    assert sp.dur_ns > 0 and c.value == 0
    assert metrics.collector.trace("spans-off") == []


def test_a_thread_books_its_spans_when_the_outermost_one_exits():
    """An exit touches no counter: the thread's outermost span books
    everything that exited under it, children before parents."""
    def val(name):
        return registry.counter(name).value

    with metrics.span("book.outer") as outer:
        with metrics.span("book.inner") as inner:
            pass
        assert val("span_book_inner_calls") == 0
    assert val("span_book_inner_calls") == 1
    assert val("span_book_outer_calls") == 1
    assert val("span_book_inner_self_seconds") == \
        pytest.approx(inner.dur_ns * 1e-9, rel=1e-9)
    assert val("span_book_outer_self_seconds") == \
        pytest.approx((outer.dur_ns - inner.dur_ns) * 1e-9, rel=1e-9)
    assert val("span_book_outer_seconds") == \
        pytest.approx(outer.dur_s, rel=1e-9)
    # a long-lived outermost span does not hoard: a full batch is booked
    # while it is still open
    with metrics.span("book.outer"):
        for _ in range(metrics._BOOK_AT):
            with metrics.span("book.inner"):
                pass
        assert val("span_book_inner_calls") == 1 + metrics._BOOK_AT
    assert val("span_book_outer_calls") == 2
