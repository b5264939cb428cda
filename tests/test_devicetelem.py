"""Device telemetry (ISSUE 18): per-chip kernel ledger, HBM occupancy
model, compile-cache observability, and the health / CLI / ruler
surfaces (utils/devicetelem.py).

The load-bearing invariants:
  - parity by construction: the ledger's per-(device, kernel) seconds
    sum to QueryStats.device_seconds — locally, bottom-up merged, and
    over the wire;
  - the ring is bounded and the per-device counters survive concurrent
    dispatch;
  - HBM gauges reconcile with MirrorPlacer bookings delta-for-delta;
  - an injected recompile storm is attributable (shape + origin in the
    ledger) and flips the health `device` subsystem to degraded;
  - a ruler alert on `device_hbm_booked_bytes` fires end-to-end through
    the `_self_` self-scrape.
"""
import threading
import time

import pytest

from filodb_tpu.config import FilodbSettings
from filodb_tpu.query.rangevector import QueryStats
from filodb_tpu.standalone import DatasetConfig, FiloServer
from filodb_tpu.utils import devicetelem
from filodb_tpu.utils.devicetelem import (DeviceTelemetry, telem,
                                          watched_call)
from filodb_tpu.utils.events import journal
from filodb_tpu.utils.health import DEGRADED, OK, SERVING, HealthEvaluator
from filodb_tpu.utils.metrics import exec_tally, registry, trace_context


@pytest.fixture(autouse=True)
def _clean_telem():
    telem.clear()
    devicetelem.set_enabled(True)
    yield
    telem.clear()
    devicetelem.set_enabled(True)


# ------------------------------------------------------------------ parity

def test_ledger_feeds_exec_tally_in_lockstep():
    """record_dispatch(kind='kernel') feeds the per-thread exec tally's
    device_s AND device_calls with the same seconds, so the per-device
    breakdown can never drift from the scalar (parity by construction)."""
    snap = exec_tally.snapshot()
    try:
        telem.record_dispatch("fused_run", device="chipA",
                              shape="S4xT8", seconds=0.5)
        telem.record_dispatch("fused_run", device="chipA", seconds=0.25)
        telem.record_dispatch("mirror_gather", device="chipB", seconds=0.125)
        assert exec_tally.device_s == pytest.approx(0.875)
        assert exec_tally.device_calls == {
            ("chipA", "fused_run"): [0.75, 2],
            ("chipB", "mirror_gather"): [0.125, 1]}
        split = sum(c[0] for c in exec_tally.device_calls.values())
        assert split == pytest.approx(exec_tally.device_s)
        # transfers/compiles never feed the tally (note_transfer and the
        # compile path own their attribution) — no double count
        telem.record_dispatch("mirror_upload_full", device="chipA",
                              seconds=9.0, kind="transfer", note=False)
        assert exec_tally.device_s == pytest.approx(0.875)
    finally:
        exec_tally.snapshot()
        exec_tally.restore(snap, 0.0)


def test_stats_device_calls_merge_and_wire_parity():
    """Bottom-up merge and the serialize round trip both preserve the
    seconds-sum == device_seconds invariant, and ?stats=true renders the
    per-chip table."""
    from filodb_tpu.parallel import serialize
    s1 = QueryStats(device_seconds=0.5,
                    device_calls={"chipA|fused_run": [0.5, 2]})
    s2 = QueryStats(device_seconds=0.25,
                    device_calls={"chipA|fused_run": [0.125, 1],
                                  "chipB|mirror_gather": [0.125, 1]})
    s1.merge(s2)
    assert s1.device_seconds == pytest.approx(0.75)
    assert s1.device_calls == {"chipA|fused_run": [0.625, 3],
                               "chipB|mirror_gather": [0.125, 1]}
    assert sum(c[0] for c in s1.device_calls.values()) \
        == pytest.approx(s1.device_seconds)
    # over the wire: the generic dataclass codec ships the new field
    rt = serialize.loads(serialize.dumps(s1))
    assert rt.device_calls == s1.device_calls
    assert rt.device_seconds == pytest.approx(s1.device_seconds)
    # ?stats=true shape: device -> kernel -> {seconds, dispatches}
    d = s1.to_dict()["devices"]
    assert d["chipA"]["fused_run"] == {"seconds": 0.625, "dispatches": 3}
    assert d["chipB"]["mirror_gather"]["dispatches"] == 1


def test_kill_switch_skips_ledger_but_never_stats():
    """set_enabled(False) must not change QueryStats.device_seconds —
    stats correctness is not an observability option."""
    snap = exec_tally.snapshot()
    try:
        devicetelem.set_enabled(False)
        telem.record_dispatch("fused_run", device="chipA", seconds=0.5)
        assert exec_tally.device_s == pytest.approx(0.5)
        assert exec_tally.device_calls[("chipA", "fused_run")] == [0.5, 1]
        snap_t = telem.snapshot()
        assert snap_t["devices"] == {} and snap_t["recent"] == []
        assert not snap_t["enabled"]
    finally:
        devicetelem.set_enabled(True)
        exec_tally.snapshot()
        exec_tally.restore(snap, 0.0)


# ------------------------------------------------------------------ ledger

def test_ring_bounded_newest_first():
    t = DeviceTelemetry(max_entries=16)
    for i in range(100):
        t.record_dispatch(f"k{i % 3}", device="chipA",
                          shape=f"S{i}", seconds=0.001, note=False)
    snap = t.snapshot(recent=50)
    assert snap["ledgerSeq"] == 100
    assert snap["ledgerCapacity"] == 16
    assert len(snap["recent"]) == 16
    seqs = [e["seq"] for e in snap["recent"]]
    assert seqs == sorted(seqs, reverse=True) and seqs[0] == 100
    # cumulative counters are NOT ring-bounded
    assert snap["devices"]["chipA"]["dispatches"] == 100
    # filters
    assert all(e["kernel"] == "k0" for e in t.recent(limit=5, kind="")
               if e["kernel"] == "k0")
    only = t.recent(limit=100, device="chipA")
    assert len(only) == 16
    assert t.recent(limit=100, device="nosuch") == []


def test_concurrent_dispatch_keeps_counters_consistent():
    t = DeviceTelemetry(max_entries=4096)
    n_threads, per_thread = 8, 250

    def pump(i):
        for _ in range(per_thread):
            t.record_dispatch("k", device=f"chip{i % 2}",
                              seconds=0.001, bytes_in=10, note=False)

    threads = [threading.Thread(target=pump, args=(i,))
               for i in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    snap = t.snapshot()
    total = n_threads * per_thread
    assert snap["ledgerSeq"] == total
    assert sum(d["dispatches"] for d in snap["devices"].values()) == total
    assert sum(d["bytesIn"] for d in snap["devices"].values()) == total * 10
    busy = sum(d["busySeconds"] for d in snap["devices"].values())
    assert busy == pytest.approx(total * 0.001)
    per_kernel = sum(d["kernels"]["k"]["count"]
                     for d in snap["devices"].values())
    assert per_kernel == total


# ----------------------------------------------------------- HBM occupancy

def test_hbm_gauges_reconcile_with_placer_bookings():
    """Every MirrorPlacer booking delta lands in the telemetry occupancy
    model with the same sign and magnitude — the gauge==booking
    invariant /admin/devices depends on."""
    import jax

    from filodb_tpu.core.devicecache import placer
    dev = jax.local_devices()[0]
    base_p = placer.booked(dev)
    base_t = telem.hbm_booked(dev)
    base_hot = telem.hbm_booked(dev, "hot")
    base_cold = telem.hbm_booked(dev, "cold")
    placer.book(dev, 1 << 20, region="hot")
    placer.book(dev, 2 << 20, region="cold")
    try:
        assert placer.booked(dev) - base_p == 3 << 20
        assert telem.hbm_booked(dev) - base_t == 3 << 20
        assert telem.hbm_booked(dev, "hot") - base_hot == 1 << 20
        assert telem.hbm_booked(dev, "cold") - base_cold == 2 << 20
        g = registry.gauge("device_hbm_booked_bytes",
                           device=str(dev), region="hot")
        assert g.value == telem.hbm_booked(dev, "hot")
    finally:
        placer.book(dev, -(1 << 20), region="hot")
        placer.book(dev, -(2 << 20), region="cold")
    assert placer.booked(dev) - base_p == 0
    assert telem.hbm_booked(dev) - base_t == 0


def test_hbm_high_water_journaled():
    telem.hbm_book("chipHW", "hot", 8 << 20)
    evs = [e for e in journal.since(0, kind="device_hbm_high_water")
           if e.get("device") == "chipHW"]
    assert evs and evs[-1]["bytes"] == 8 << 20
    # gauges clamp at zero on release races
    telem.hbm_book("chipHW", "hot", -(64 << 20))
    assert telem.hbm_booked("chipHW", "hot") == 0


# ------------------------------------------------- compiles + health flip

def test_compile_storm_attributable_and_flips_health():
    """An injected recompile storm (new shapes defeating the jit trace
    cache) lands per-event in the ledger with shape + origin query id,
    fills jit_compile_seconds, and flips the health `device` subsystem
    to degraded while sustained."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: x * 2.0)
    origin = "cafebabe" * 4
    count_before = registry.counter("jit_compile_events",
                                    fn="storm_fn").value
    with trace_context(origin):
        for i in range(12):
            x = jnp.zeros((i + 17,))
            res = watched_call("storm_fn", fn, f"S{i + 17}",
                               lambda x=x: fn(x))
            assert res.shape == (i + 17,)
        # same shape again: a cache hit, not a compile
        watched_call("storm_fn", fn, "S17",
                     lambda: fn(jnp.zeros((17,))))
    try:
        compiles = telem.recent(limit=100, kind="compile")
        mine = [e for e in compiles if e["kernel"] == "storm_fn"]
        assert len(mine) == 12
        assert all(e["origin"] == origin for e in mine)
        assert {e["shape"] for e in mine} == {f"S{i + 17}"
                                              for i in range(12)}
        assert registry.counter("jit_compile_events",
                                fn="storm_fn").value - count_before == 12
        evs = [e for e in journal.since(0, kind="jit_compile")
               if e.get("kernel") == "storm_fn"]
        assert len(evs) == 12 and all(e["origin"] == origin for e in evs)
        ev = HealthEvaluator(phase=SERVING)
        dv = ev.evaluate()["subsystems"]["device"]
        assert dv["status"] == DEGRADED
        assert "compile_storm" in dv["reasons"]
        assert dv["recentCompiles"] >= 12
    finally:
        # the storm's journal residue must not degrade later tests'
        # health verdicts (RECENT_WINDOW_S outlives this file)
        journal.clear()
    assert HealthEvaluator(phase=SERVING) \
        ._device_verdict()["status"] == OK


def test_watched_call_disabled_is_passthrough():
    devicetelem.set_enabled(False)
    calls = []
    res = watched_call("k", object(), "S1", lambda: calls.append(1) or 7)
    assert res == 7 and calls == [1]
    assert telem.recent(limit=10) == []


# ------------------------------------------------------------- HTTP route

def _server(selfmon=False, rules_groups=None):
    cfg = FilodbSettings()
    if selfmon:
        cfg.selfmon.enabled = True
        cfg.selfmon.interval_s = 3600.0    # manual scrape_once in tests
    if rules_groups is not None:
        cfg.rules.enabled = True
        cfg.rules.groups = rules_groups
    return FiloServer([DatasetConfig("prometheus", num_shards=2)],
                      config=cfg)


def test_admin_devices_route():
    srv = _server()
    try:
        telem.record_dispatch("probe_kernel", device="chipZ",
                              shape="S4xT8", seconds=0.01,
                              origin="deadbeef", note=False)
        telem.record_dispatch("probe_compile", device="chipZ",
                              kind="compile", note=False)
        telem.hbm_book("chipZ", "hot", 12345)
        st, p = srv.api.handle("GET", "/admin/devices", {})
        assert st == 200 and p["status"] == "success"
        dev = p["data"]["devices"]["chipZ"]
        assert dev["dispatches"] == 2
        assert dev["compiles"] == 1
        assert dev["hbm"]["hot"] == 12345
        assert dev["kernels"]["probe_kernel"]["count"] == 1
        kernels = [e["kernel"] for e in p["data"]["recent"]]
        assert "probe_kernel" in kernels
        # filters
        st, p = srv.api.handle("GET", "/admin/devices",
                               {"kind": "compile", "recent": "50"})
        assert st == 200
        assert all(e["kind"] == "compile" for e in p["data"]["recent"])
        st, p = srv.api.handle("GET", "/admin/devices",
                               {"device": "nosuch"})
        assert st == 200 and p["data"]["recent"] == []
        st, _ = srv.api.handle("GET", "/admin/devices", {"recent": "x"})
        assert st == 400
    finally:
        srv.shutdown()


# -------------------------------------------------------- ruler alert e2e

def test_hbm_alert_fires_through_self_scrape_end_to_end():
    """The conf/example-filodb.conf device_telemetry alert group, proven
    live: HBM booking -> device_hbm_booked_bytes gauge -> `_self_`
    scrape -> ruler eval through the ordinary frontend -> firing at
    /api/v1/alerts; release resolves it."""
    groups = {"device_telemetry": {
        "interval": 10,
        "rules": {"hbm_pressure": {
            "alert": "DeviceHbmPressure",
            "expr": 'max by (device) '
                    '(device_hbm_booked_bytes{job="filodb"}) '
                    '> 1500000',
            "labels": {"severity": "page"},
        }}}}
    srv = _server(selfmon=True, rules_groups=groups)
    try:
        telem.hbm_book("chipAlert", "hot", 2_000_000)
        srv.selfmon.scrape_once()
        assert srv.ruler.evaluate_group("device_telemetry",
                                        ts=time.time() + 1)
        st, p = srv.api.handle("GET", "/api/v1/alerts", {})
        assert st == 200
        mine = [a for a in p["data"]["alerts"]
                if a["labels"].get("device") == "chipAlert"]
        assert len(mine) == 1
        assert mine[0]["labels"]["alertname"] == "DeviceHbmPressure"
        assert mine[0]["state"] == "firing"
        # release drops the gauge; the next scrape + eval resolves
        telem.hbm_book("chipAlert", "hot", -2_000_000)
        srv.selfmon.scrape_once()
        assert srv.ruler.evaluate_group("device_telemetry",
                                        ts=time.time() + 2)
        st, p = srv.api.handle("GET", "/api/v1/alerts", {})
        assert not [a for a in p["data"]["alerts"]
                    if a["labels"].get("device") == "chipAlert"]
    finally:
        srv.shutdown()


# ----------------------------------------------------- snapshot semantics

def test_snapshot_includes_hbm_only_devices_and_decays_ewma():
    telem.hbm_book("chipIdle", "cold", 4096)
    snap = telem.snapshot()
    assert snap["devices"]["chipIdle"]["hbm"]["cold"] == 4096
    assert snap["devices"]["chipIdle"]["dispatches"] == 0
    # a busy burst reads nonzero utilization, and the snapshot decays it
    # toward idle without needing further traffic
    telem.record_dispatch("k", device="chipBusy", seconds=3.0, note=False)
    u0 = telem.snapshot()["devices"]["chipBusy"]["utilEwma"]
    assert u0 > 0.0
    with telem._lock:
        telem._devices["chipBusy"].last_unix_s -= 120.0
    u1 = telem.snapshot()["devices"]["chipBusy"]["utilEwma"]
    assert u1 < u0
