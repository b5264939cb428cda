"""Fleet self-observability (ISSUE 10): job registry, event journal,
health model / readiness, self-scrape meta-monitoring, and the
runtimeinfo/CLI satellites.

Models ref: HealthRoute.scala / ClusterApiRoute.scala shard-status
admin; Prometheus /-/healthy + /-/ready + meta-monitoring."""
import json
import time
import urllib.request

import numpy as np
import pytest

from filodb_tpu.config import FilodbSettings
from filodb_tpu.standalone import DatasetConfig, FiloServer
from filodb_tpu.utils.events import EventJournal, journal
from filodb_tpu.utils.health import (DEGRADED, FAILED, OK, SERVING,
                                     HealthEvaluator)
from filodb_tpu.utils.jobs import JobRegistry, jobs

START = 1_600_000_020_000
START_S = START // 1000


@pytest.fixture(autouse=True)
def _clean_registries():
    jobs.clear()
    # the health verdict reads the journal's last two minutes: a file that
    # ran before this one on the same worker and compiled ten flavors of
    # one kernel (tests/test_leaf_fused.py does) must not read as a
    # compile storm here
    journal.clear()
    yield
    jobs.clear()


# ------------------------------------------------------------ job registry

def test_job_tick_records_duration_and_streaks():
    reg = JobRegistry()
    h = reg.register("compact", interval_s=5.0, dataset="ds")
    with h.tick():
        h.set_progress("window 1/3")
        time.sleep(0.01)
    snap = h.snapshot()
    assert snap["runs"] == 1 and snap["errors"] == 0
    assert snap["consecutiveErrors"] == 0
    assert snap["lastDurationSeconds"] >= 0.01
    assert snap["progress"] == "window 1/3"
    assert snap["lastStartUnixSeconds"] > 0
    assert snap["lastEndUnixSeconds"] >= snap["lastStartUnixSeconds"]
    # an escaping exception marks the tick failed and re-raises
    with pytest.raises(RuntimeError):
        with h.tick():
            raise RuntimeError("boom")
    assert h.consecutive_errors == 1 and "boom" in h.last_error
    # streaks accumulate, success resets
    with pytest.raises(RuntimeError):
        with h.tick():
            raise RuntimeError("again")
    assert h.consecutive_errors == 2
    with h.tick():
        pass
    assert h.consecutive_errors == 0


def test_job_note_error_inside_tick_not_double_counted():
    """A loop that catches its own exceptions reports via note_error;
    the enclosing tick must count ONE run, failed."""
    reg = JobRegistry()
    h = reg.register("flush", dataset="ds")
    with h.tick():
        h.note_error("shard 3 flush failed")
    assert h.runs == 1
    assert h.errors == 1 and h.consecutive_errors == 1
    assert "shard 3" in h.last_error


def test_job_tick_skip_is_neutral():
    """An empty pass (every target in backoff) must not count as a
    success: a permanently broken critical job whose only failing
    target is backing off would otherwise oscillate its streak between
    0 and 1 and never flip /ready."""
    reg = JobRegistry()
    h = reg.register("skiptest", dataset="ds", critical=True)
    for _ in range(4):
        with h.tick():
            h.note_error("store down")     # attempted, failed
        with h.tick() as t:
            t.skip()                       # backoff pass: no work
    # skips neither reset the streak nor count as runs
    assert h.consecutive_errors == 4
    assert h.runs == 4
    # drop the exported streak gauge: the metrics registry is process-
    # wide, and a later self-scrape test would alert on this residue
    from filodb_tpu.utils.metrics import registry
    registry.gauge("job_consecutive_errors", job="skiptest",
                   dataset="ds").update(0)


def test_ruler_reload_unregisters_removed_group_jobs():
    """A removed group's job handle leaves the registry with it — a
    stale failing-group streak must not hold the health verdict
    degraded until process restart."""
    cfg = FilodbSettings()
    cfg.rules.enabled = True
    cfg.rules.groups = {"doomed": {"interval": 1, "rules": {
        "r": {"record": "x:y", "expr": "sum(rate(request_total[5m]))"}}}}
    srv = FiloServer([DatasetConfig("prometheus", num_shards=1)],
                     config=cfg)
    try:
        srv.ruler.evaluate_group("doomed", ts=time.time())
        h = jobs.get("ruler:doomed")
        assert h is not None
        h.note_error("induced streak")     # the group is failing
        ev = HealthEvaluator(phase=SERVING)
        assert ev.evaluate()["subsystems"]["jobs"]["status"] == DEGRADED
        srv.ruler.reload(groups=[])        # operator deletes the group
        assert jobs.get("ruler:doomed") is None
        assert ev.evaluate()["subsystems"]["jobs"]["status"] == OK
    finally:
        srv.shutdown()


def test_job_registry_bounded_and_idempotent():
    reg = JobRegistry()
    a = reg.register("x", dataset="d1")
    assert reg.register("x", dataset="d1") is a      # same handle back
    for i in range(reg.MAX_JOBS + 50):
        reg.register(f"j{i}")
    assert len(reg.snapshot()) <= reg.MAX_JOBS
    # overflow handles still work, they are just not retained
    extra = reg.register("overflow-job-xyz")
    with extra.tick():
        pass
    assert extra.runs == 1


def test_admin_jobs_route():
    srv = FiloServer([DatasetConfig("prometheus", num_shards=1)])
    try:
        h = jobs.register("probe", interval_s=1.0, dataset="prometheus")
        with h.tick():
            h.set_progress("probing")
        st, payload = srv.api.handle("GET", "/admin/jobs", {})
        assert st == 200
        by_name = {j["job"]: j for j in payload["data"]["jobs"]}
        assert by_name["probe"]["runs"] == 1
        assert by_name["probe"]["progress"] == "probing"
    finally:
        srv.shutdown()


# ------------------------------------------------------------ event journal

def test_journal_ring_bounded_with_monotonic_seqs():
    j = EventJournal(max_entries=64)
    for i in range(500):
        j.emit("tick", subsystem="t", i=i)
    evs = j.since(0)
    assert len(evs) == 64                      # bounded under a soak
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs) and seqs[-1] == 500
    # since_seq resumes exactly (exclusive), limit keeps the newest
    assert [e["seq"] for e in j.since(498)] == [499, 500]
    assert [e["seq"] for e in j.since(0, limit=3)] == [498, 499, 500]
    assert all(e["kind"] == "tick" for e in j.since(0, kind="tick"))
    assert j.since(0, kind="nope") == []


def test_journal_jsonl_sink(tmp_path):
    path = tmp_path / "events.jsonl"
    j = EventJournal(max_entries=8, path=str(path))
    j.emit("wal_segment_rotated", subsystem="wal", dataset="p",
           sealed_segments=2)
    j.emit("breaker_open", subsystem="peers", peer="10.0.0.1:9095")
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [ln["kind"] for ln in lines] == ["wal_segment_rotated",
                                           "breaker_open"]
    assert lines[0]["sealed_segments"] == 2
    assert lines[1]["seq"] == 2


def test_journal_emit_never_raises(tmp_path):
    j = EventJournal(max_entries=4, path=str(tmp_path / "nope" / "deep" /
                                             "x.jsonl"))
    # unwritable sink + unserializable field: emit still returns a seq
    class Weird:
        def __str__(self):
            return "weird"
    assert j.emit("k", field=Weird()) == 1


def test_subsystem_events_land_in_journal(tmp_path):
    """Wired emit sites: WAL rotation + prune and replay produce journal
    entries with their payload fields (the flight-recorder contract)."""
    from filodb_tpu.config import WalConfig
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.wal import WalManager
    seq0 = journal.next_seq
    cfg = WalConfig(enabled=True, segment_max_bytes=256, fsync=False)
    mgr = WalManager(str(tmp_path / "wal"), "prometheus", config=cfg)
    keys = None
    from filodb_tpu.ingest.generator import gauge_batch
    keys = gauge_batch(16, 1, start_ms=START).part_keys
    try:
        for b in range(6):
            ts = np.full((16, 1), START + b * 10_000, dtype=np.int64)
            vals = np.full((16, 1), float(b))
            mgr.append_grid(0, "gauge", list(keys), ts, {"value": vals})
    finally:
        mgr.close()
    # rotation events carry the sealed segment seqs
    rots = [e for e in journal.since(seq0 - 1)
            if e["kind"] == "wal_segment_rotated"]
    assert rots and rots[0]["dataset"] == "prometheus"
    # replay start/done pair with stats
    ms = TimeSeriesMemStore()
    mgr2 = WalManager(str(tmp_path / "wal"), "prometheus", config=cfg)
    try:
        mgr2.replay(ms)
    finally:
        mgr2.close()
    kinds = [e["kind"] for e in journal.since(seq0 - 1)]
    assert "wal_replay_started" in kinds and "wal_replay_done" in kinds
    done = [e for e in journal.since(seq0 - 1)
            if e["kind"] == "wal_replay_done"][-1]
    assert done["records"] == 6 and done["samples"] == 96


def test_admin_events_route_since_seq():
    srv = FiloServer([DatasetConfig("prometheus", num_shards=1)])
    try:
        seq = journal.emit("test_marker", subsystem="test", n=1)
        journal.emit("test_marker", subsystem="test", n=2)
        st, payload = srv.api.handle("GET", "/admin/events",
                                     {"since_seq": str(seq)})
        assert st == 200
        evs = payload["data"]["events"]
        assert all(e["seq"] > seq for e in evs)
        assert any(e.get("n") == 2 for e in evs)
        assert payload["data"]["nextSeq"] > seq
    finally:
        srv.shutdown()


# ------------------------------------------------------------- health model

def test_health_verdicts_fold_job_streaks():
    ev = HealthEvaluator(phase=SERVING)
    h = jobs.register("flush", dataset="p", critical=True)
    assert ev.evaluate()["status"] == OK
    h.note_error("disk full")
    tree = ev.evaluate()
    assert tree["status"] == DEGRADED
    assert tree["subsystems"]["jobs"]["status"] == DEGRADED
    ok, _ = ev.ready()
    assert ok                              # degraded still serves
    for _ in range(5):
        h.note_error("disk full")
    tree = ev.evaluate()
    assert tree["subsystems"]["jobs"]["status"] == FAILED
    ready, reason = ev.ready()
    assert not ready and "flush" in reason  # critical job failed -> 503
    h.note_ok()
    assert ev.ready()[0]


def test_health_peers_verdict_from_breakers():
    from filodb_tpu.parallel.breaker import breakers
    breakers.reset()
    breakers.configure(failure_threshold=1, open_base_s=30.0, jitter=0.0)
    try:
        ev = HealthEvaluator(phase=SERVING)
        br = breakers.get("10.0.0.9:9095")
        br.on_failure()                     # threshold 1 -> open
        tree = ev.evaluate()
        assert tree["subsystems"]["peers"]["status"] == DEGRADED
        assert tree["subsystems"]["peers"]["open"] == ["10.0.0.9:9095"]
        # open peers degrade but do NOT flip readiness (partials serve)
        assert ev.ready()[0]
    finally:
        breakers.configure()
        breakers.reset()


def test_ready_gated_on_phase():
    ev = HealthEvaluator(phase="booting")
    ok, reason = ev.ready()
    assert not ok and "booting" in reason
    ev.set_phase(SERVING)
    assert ev.ready()[0]
    # phase transitions land in the journal
    evs = [e for e in journal.since(0) if e["kind"] == "phase"]
    assert any(e["to"] == SERVING for e in evs)


# ------------------------------------------- readiness through a restart

def _rw_payload(n=8, k=4):
    from filodb_tpu.http import remotepb
    from filodb_tpu.utils import snappy
    series = []
    for i in range(n):
        labels = [("__name__", "restart_total"), ("_ws_", "demo"),
                  ("_ns_", "App-0"), ("inst", str(i))]
        samples = [(float(i + j), START + j * 10_000) for j in range(k)]
        series.append(remotepb.PromTimeSeries(labels, samples))
    return snappy.compress(remotepb.encode_write_request(series))


def test_ready_503_during_boot_replay_then_200_serving(tmp_path,
                                                       monkeypatch):
    """The acceptance restart test: a node restarting onto a WAL answers
    /ready with 503 WHILE the log replays (observed through the real
    route layer mid-replay) and flips to 200 once serving — with the
    whole sequence on the flight recorder."""
    from filodb_tpu.http.routes import PromHttpApi
    from filodb_tpu.wal import WalManager

    cfg = FilodbSettings()
    cfg.wal.enabled = True
    cfg.wal.dir = str(tmp_path / "wal")
    srv = FiloServer([DatasetConfig("prometheus", num_shards=2)],
                     config=cfg)
    try:
        st, _ = srv.api.handle("POST", "/api/v1/write", {}, _rw_payload())
        assert st == 204
    finally:
        srv.shutdown()

    # restart on the same WAL dir; probe /ready from INSIDE the replay
    # (the API is built before the boot replay runs, by design)
    box = {}
    orig_api_init = PromHttpApi.__init__

    def api_init(self, *a, **kw):
        orig_api_init(self, *a, **kw)
        box["api"] = self

    orig_replay = WalManager.replay

    def probed_replay(self, memstore, restart_points=None):
        api = box["api"]
        box["during_ready"] = api.handle("GET", "/ready", {})
        box["during_healthz"] = api.handle("GET", "/healthz", {})
        return orig_replay(self, memstore, restart_points)

    monkeypatch.setattr(PromHttpApi, "__init__", api_init)
    monkeypatch.setattr(WalManager, "replay", probed_replay)
    cfg2 = FilodbSettings()
    cfg2.wal.enabled = True
    cfg2.wal.dir = str(tmp_path / "wal")
    srv2 = FiloServer([DatasetConfig("prometheus", num_shards=2)],
                      config=cfg2, http_port=0)
    try:
        st, payload = box["during_ready"]
        assert st == 503 and payload["status"] == "unready"
        assert "replaying_wal" in payload["reason"]
        # liveness stayed 200 throughout (the Prometheus split)
        assert box["during_healthz"][0] == 200
        # not yet serving: constructed-but-unstarted stays unready
        assert srv2.api.handle("GET", "/ready", {})[0] == 503
        srv2.start()
        # ...and flips to 200 over the REAL socket once serving
        url = f"http://127.0.0.1:{srv2.http.port}/ready"
        with urllib.request.urlopen(url, timeout=30) as r:
            assert r.status == 200
        # the replayed data serves
        st, payload = srv2.api.handle(
            "GET", "/api/v1/query_range",
            {"query": "restart_total", "start": str(START_S),
             "end": str(START_S + 60), "step": "10"}, b"")
        assert st == 200 and len(payload["data"]["result"]) == 8
        # runtimeinfo reflects the WAL posture
        st, payload = srv2.api.handle("GET", "/api/v1/status/runtimeinfo",
                                      {})
        d = payload["data"]
        assert d["walEnabled"] is True and d["walReplayDone"] is True
        assert d["serverPhase"] == "serving"
        assert "startTime" in d and "serverTime" in d
        assert d["reloadConfigSuccess"] is True
    finally:
        srv2.shutdown()
    # the flight-recorder sequence of the restart
    kinds = [e["kind"] for e in journal.since(0)]
    assert "wal_replay_started" in kinds and "wal_replay_done" in kinds
    phases = [(e.get("frm"), e.get("to")) for e in journal.since(0)
              if e["kind"] == "phase"]
    assert ("booting", "replaying_wal") in phases
    assert any(to == "serving" for _f, to in phases)


# ------------------------------------------------ self-scrape meta-monitor

def _selfmon_server(interval_s=3600.0, rules_groups=None):
    cfg = FilodbSettings()
    cfg.selfmon.enabled = True
    cfg.selfmon.interval_s = interval_s     # manual scrape_once in tests
    if rules_groups is not None:
        cfg.rules.enabled = True
        cfg.rules.groups = rules_groups
    return FiloServer([DatasetConfig("prometheus", num_shards=2)],
                      config=cfg)


def test_selfmon_scrape_makes_metrics_promql_queryable():
    srv = _selfmon_server()
    try:
        from filodb_tpu.utils.metrics import registry
        # fresh names: the process-wide registry carries residue from
        # sibling tests, and counters only ever climb
        registry.counter("selfobs_probe",
                         dataset="prometheus").increment(7)
        registry.histogram("selfobs_probe_seconds",
                           dataset="prometheus").record(0.004)
        n = srv.selfmon.scrape_once()
        assert n > 0
        # query strictly AFTER the scrape timestamp: the instant API
        # floors to whole seconds and looks back, never forward
        now = int(time.time()) + 1
        # counter -> name_total, tagged with scrape identity
        st, p = srv.api.handle(
            "GET", "/api/v1/query",
            {"query": 'selfobs_probe_total{job="filodb",'
                      'dataset="prometheus"}', "time": str(now)})
        assert st == 200 and len(p["data"]["result"]) == 1
        row = p["data"]["result"][0]
        assert float(row["value"][1]) == 7.0
        assert row["metric"]["_ws_"] == "_self_"
        assert row["metric"]["instance"] == "local"
        # histogram -> _count/_sum/_bucket{le} (the rate(..._count[5m])
        # shape from the ISSUE)
        st, p = srv.api.handle(
            "GET", "/api/v1/query",
            {"query": "selfobs_probe_seconds_count", "time": str(now)})
        assert st == 200 and len(p["data"]["result"]) == 1
        assert float(p["data"]["result"][0]["value"][1]) == 1.0
        st, p = srv.api.handle(
            "GET", "/api/v1/query",
            {"query": 'selfobs_probe_seconds_bucket{le="+Inf"}',
             "time": str(now)})
        assert st == 200 and len(p["data"]["result"]) == 1
    finally:
        srv.shutdown()


def test_selfmon_label_collision_gets_exported_prefix():
    srv = _selfmon_server()
    try:
        h = jobs.register("victim", dataset="prometheus")
        with h.tick():
            pass
        srv.selfmon.scrape_once()
        now = int(time.time()) + 1
        st, p = srv.api.handle(
            "GET", "/api/v1/query",
            {"query": 'job_runs_total{job="filodb",'
                      'exported_job="victim"}', "time": str(now)})
        assert st == 200 and len(p["data"]["result"]) == 1
    finally:
        srv.shutdown()


def test_selfmon_alert_fires_through_frontend_end_to_end():
    """The acceptance e2e: an induced job error streak -> self-scraped
    `job_consecutive_errors` series -> ruler alert group evaluated
    through the ORDINARY frontend path -> firing at /api/v1/alerts."""
    # interval doubles as the per-eval deadline (ruler._planner_params);
    # 1 s sits at the edge of a cold-jit eval under a loaded suite, and
    # the deadline is not what this test verifies
    groups = {"self_monitoring": {
        "interval": 10,
        "rules": {"job_err": {
            "alert": "BackgroundJobFailing",
            "expr": 'max by (exported_job) '
                    '(job_consecutive_errors{job="filodb"}) > 2',
            "labels": {"severity": "page"},
        }}}}
    srv = _selfmon_server(rules_groups=groups)
    try:
        h = jobs.register("victim", dataset="prometheus")
        for _ in range(3):
            h.note_error("induced failure")
        srv.selfmon.scrape_once()
        # evaluate strictly AFTER the scrape timestamp (the eval ts
        # floors to whole seconds and the lookback is backward-only)
        ok = srv.ruler.evaluate_group("self_monitoring",
                                      ts=time.time() + 1)
        assert ok
        st, p = srv.api.handle("GET", "/api/v1/alerts", {})
        assert st == 200
        # filter to the induced instance: the process-wide metrics
        # registry may carry other tests' streak gauges
        mine = [a for a in p["data"]["alerts"]
                if a["labels"].get("exported_job") == "victim"]
        assert len(mine) == 1
        a = mine[0]
        assert a["labels"]["alertname"] == "BackgroundJobFailing"
        assert a["state"] == "firing"      # no `for:` -> fires at once
        # recovery clears it: streak resets, next scrape + eval resolve
        h.note_ok()
        srv.selfmon.scrape_once()
        assert srv.ruler.evaluate_group("self_monitoring",
                                        ts=time.time() + 2)
        st, p = srv.api.handle("GET", "/api/v1/alerts", {})
        assert not [a for a in p["data"]["alerts"]
                    if a["labels"].get("exported_job") == "victim"]
    finally:
        srv.shutdown()


def test_selfmon_tenant_accounted_but_scan_exempt():
    from filodb_tpu.utils.usage import INTERNAL_WORKSPACES, usage
    assert "_self_" in INTERNAL_WORKSPACES
    assert usage.admit("_self_", "selfmon", warn_limit=1,
                       fail_limit=1) is None


def test_suppressed_errors_counter_satellite():
    """log_error_once sites also increment
    suppressed_errors_total{site,class} on EVERY call (the log line is
    rate-limited; the counter is not)."""
    from filodb_tpu.utils.metrics import log_error_once, registry
    c = registry.counter("suppressed_errors",
                         **{"site": "test_site", "class": "ValueError"})
    v0 = c.value
    log_error_once("test_site", ValueError("x"))
    log_error_once("test_site", ValueError("y"))   # rate-limited log,
    assert c.value == v0 + 2                       # counted twice
    assert 'suppressed_errors_total{class="ValueError",site="test_site"}' \
        in registry.expose_prometheus()


# ------------------------------------------------------------ CLI satellite

@pytest.fixture(scope="module")
def live_server():
    srv = FiloServer([DatasetConfig("prometheus", num_shards=1)],
                     http_port=0)
    srv.start()
    yield srv
    srv.shutdown()


def test_cli_health_jobs_events(live_server, capsys):
    from filodb_tpu.cli import main
    host = f"127.0.0.1:{live_server.http.port}"
    h = jobs.register("cli-probe", dataset="prometheus")
    with h.tick():
        h.set_progress("cli visibility")
    seq = journal.emit("cli_marker", subsystem="test", n=41)
    journal.emit("cli_marker", subsystem="test", n=42)

    assert main(["health", "--host", host]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["data"]["status"] in ("ok", "degraded")
    assert "jobs" in out["data"]["subsystems"]

    assert main(["health", "--host", host, "--ready"]) == 0
    assert json.loads(capsys.readouterr().out)["status"] == "ready"

    assert main(["jobs", "--host", host]) == 0
    out = capsys.readouterr().out
    assert "cli-probe" in out and "cli visibility" in out

    assert main(["events", "--host", host, "--since-seq", str(seq)]) == 0
    lines = [json.loads(ln)
             for ln in capsys.readouterr().out.splitlines()]
    assert all(ev["seq"] > seq for ev in lines)
    assert any(ev.get("n") == 42 for ev in lines)

    # --kind filters
    assert main(["events", "--host", host, "--kind", "cli_marker"]) == 0
    lines = [json.loads(ln)
             for ln in capsys.readouterr().out.splitlines()]
    assert lines and all(ev["kind"] == "cli_marker" for ev in lines)


def test_http_healthz_ready_over_socket(live_server):
    port = live_server.http.port
    for path, want in (("/healthz", 200), ("/ready", 200),
                       ("/__health", 200)):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as r:
            assert r.status == want
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/api/v1/status/health",
            timeout=30) as r:
        doc = json.loads(r.read())
    assert doc["data"]["phase"] == "serving"
    assert set(doc["data"]["subsystems"]) >= {"jobs", "peers", "wal",
                                              "shards", "mirror"}
