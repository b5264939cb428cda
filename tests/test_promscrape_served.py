"""Counters as Prometheus scrapes them through the served path (ISSUE 37):
the six panels of `promscrape-counters-262k.open` over the HTTP door against
the configuration's plain f64 reference (`benchmark/references/
scrape_offsets.py`), on seeded data at 2,048 series x 240 samples over 4
shards, every series at its own scrape offset, interpret-mode kernels; which
route and which kernel variant each leaf took; that a request's four leaves
still share ONE plan and ONE device call although each shard's mirror has a
base of its own; and what one late sample does: the shard's leaves leave the
fused path, counted, and the answers are still the reference's.

Tolerance 2e-5 (the cell's limit), relative, on every cell of every response;
readings here 2e-7 to 5e-7."""
import time

import numpy as np
import pytest

import histrig
import ts128rig
from histrig import bench_json, bench_module

CONFIG, CELL = "promscrape-counters-262k", "promscrape-counters-262k.open"
SERIES, SAMPLES = ts128rig.SERIES, ts128rig.SAMPLES
TOL = 2e-5
SEEDS = (3500001, 2_147_483_693)
PANELS = range(6)


class ScrapeRig(ts128rig.Ts128Rig):
    """`ts128rig.Ts128Rig` holding this configuration: one `FiloServer` on
    port 0, loaded by the configuration's loader (`scrape_offsets`, after its
    question to the program), the reference's tables in the client's."""
    CONFIG, CELL = CONFIG, CELL

    def delta_over(self, reqs):
        """Ask `reqs`; -> name -> what /metrics moved by meanwhile."""
        # a request's spans are booked when its handler thread leaves the
        # outermost one, after the client has its body: let the last go
        time.sleep(0.3)
        before = self.samples()
        for req in reqs:
            (err, why), _ = self.ask(req)
            assert why is None and err <= TOL, (req["params"]["query"], err)
        time.sleep(0.3)
        after = self.samples()
        return lambda name: after.get(name, 0.0) - before.get(name, 0.0)


@pytest.fixture(scope="module", autouse=True)
def interpret_kernels():
    with histrig.environ(FILODB_TPU_FUSED_INTERPRET="1"):
        yield


@pytest.fixture(scope="module", params=SEEDS)
def rig(request):
    r = ScrapeRig(request.param)
    yield r
    r.close()
    # the requests compiled a dozen flavors of `fused_run`: their journal
    # residue must not read as a compile storm in a later file's health
    # verdict (utils/health: ten of one kernel inside 120 s)
    from filodb_tpu.utils.events import journal
    journal.clear()


def test_the_cell_is_its_unphased_twin_in_all_but_the_timestamps():
    cfg, twin = (bench_json("configs", c) for c in
                 (CONFIG, "promperf-counters-262k"))
    mine = {"name", "source", "loader", "reference", "scrape_offsets",
            "assumed", "on_device"}
    assert {k for k in cfg if cfg[k] != twin.get(k)} == mine
    assert cfg["assumed"][-len(twin["assumed"]):] == twin["assumed"]
    assert len(cfg["source"]) < 200 and cfg["reduced"].keys() == {"series"}
    assert bench_json("workloads", CELL)["traffic"] == bench_json(
        "workloads", "promperf-counters-262k.open")["traffic"]
    offsets = bench_module("loaders", "scrape_offsets").scrape_offsets
    a, b, c = (offsets(s, cfg["scrape_ms"], 4096) for s in (7, 7, 8))
    assert (a == b).all() and (a != c).any()
    assert 0 <= a.min() and a.max() < cfg["scrape_ms"] and len(set(a)) > 3000


def test_every_series_lies_at_its_own_scrape_offset(rig):
    assert len(rig.per_shard) == 4 and sum(rig.per_shard) == SERIES
    want = np.sort(bench_module("loaders", "scrape_offsets").scrape_offsets(
        rig.seed, rig.cfg["scrape_ms"], SERIES))
    got = []
    for sh in rig.srv.memstore.shards_for(rig.cfg["dataset"]):
        store = sh.stores[rig.cfg["schema"]]
        n = store.num_series
        assert (store.counts[:n] == SAMPLES).all()
        assert (np.diff(store.ts[:n, :SAMPLES], axis=1)
                == rig.cfg["scrape_ms"]).all()
        got.append(store.ts[:n, 0] - rig.cfg["start_ms"])
    np.testing.assert_array_equal(np.sort(np.concatenate(got)), want)


@pytest.mark.parametrize("panel", PANELS)
def test_served_panels_match_the_f64_reference(rig, panel):
    req = rig.open(0)[panel]
    (err, why), body = rig.ask(req)
    assert why is None, why
    assert err <= TOL, (req["params"]["query"], err)
    assert len(body["data"]["result"]) == (10, 1, 2, 10, 1, 10)[panel]
    assert body["stats"]["cache"]["result"] == "miss"


def test_every_leaf_is_one_phased_fused_dispatch_over_the_mirror(rig):
    from filodb_tpu.query.execbase import (_FUSED_CACHE_LOCK,
                                           _FUSED_PLAN_CACHE)
    rig.forget_results()
    delta = rig.delta_over(rig.open(0))     # every working set, once
    with _FUSED_CACHE_LOCK:
        _FUSED_PLAN_CACHE.clear()           # a plan is no server's
    delta = rig.delta_over(rig.open(1))
    assert delta("leaf_fused_kernel_total") == 6 * 4
    assert delta("leaf_phase_fused_total") == 6 * 4
    # each shard's mirror has its own base (its earliest sample) and still
    # the four leaves of a request share ONE plan and ride ONE device call
    bases = {sh.stores[rig.cfg["schema"]].device_mirror.base_ms
             for sh in rig.srv.memstore.shards_for(rig.cfg["dataset"])}
    assert len(bases) > 1
    assert delta("fused_enqueues_total") == 6
    assert delta("fused_enqueue_sets_total") == 6 * 4
    assert delta('fused_cache_lookups_total{cache="plan",result="miss"}') == 1
    assert delta("span_leaf_build_plan_calls_total") == 1
    for fam in ("leaf_general_path_total", "leaf_offgrid_total",
                "leaf_host_routed_total", "leaf_host_gather_total",
                "leaf_fused_errors_total", "span_leaf_pad_values_calls_total",
                'leaf_selection_fills_total{cause="generation"}',
                'leaf_selection_fills_total{cause="range"}',
                'mirror_gather_takes_total{array="phase"}'):
        assert delta(fam) == 0, fam
    after = rig.samples()
    for sh in range(4):
        assert after['device_mirror_phase_rows{shard="%d"}' % sh] \
            >= rig.per_shard[sh] - 2
        assert after['device_mirror_offgrid_rows{shard="%d"}' % sh] == 0
    assert after["span_mirror_phase_detect_calls_total"] >= 4


def test_the_estimate_of_phased_rows_comes_from_four_scalars(rig):
    """`SelectionFacts.estimate` on rows whose first and last timestamps
    differ by their offsets: one row stands for all inside the span that
    every row covers, and the array formula answers outside it."""
    from filodb_tpu.core.blockstore import estimate_samples
    from filodb_tpu.core.shard import SelectionFacts
    sh = rig.srv.memstore.shards_for(rig.cfg["dataset"])[0]
    store = sh.stores[rig.cfg["schema"]]
    rows = np.arange(store.num_series)
    facts = SelectionFacts(store, rows)
    cnt, first, first_hi, extent = facts.uniform
    assert (cnt, extent) == (SAMPLES, (SAMPLES - 1) * 10_000)
    assert 0 < first_hi - first < 10_000
    for span in ((first_hi, first + extent), (first + 600_000,
                                              first + 1_500_000),
                 (first, first + extent + 9_999), (first - 5, first + 7)):
        want = estimate_samples(facts.counts, facts.first, facts.last, *span)
        assert abs(facts.estimate(*span) - want) <= 1, span


def test_one_late_sample_sends_its_shards_leaves_to_the_general_path(rig):
    """The last test of the file: it breaks shard 0's grid for good."""
    from filodb_tpu.core.partkey import PartKey
    cfg = rig.cfg
    grid = bench_module("loaders", "grid")
    key = PartKey.make(cfg["metric"], {
        lab: grid.label_value(spec, 0) for lab, spec in cfg["labels"].items()})
    ds = cfg["dataset"]
    shard = rig.srv.memstore.shards_for(ds)[
        rig.srv.mappers[ds].ingestion_shard(
            key.shard_key_hash(), key.partition_hash(),
            rig.srv.spreads[ds].spread_for(key.shard_key()))]
    store = shard.stores[cfg["schema"]]
    newest = int(store.ts[:store.num_series, :SAMPLES].max())
    # one scrape of one target, 5 ms late, past every window of the traffic
    assert shard.ingest_columns(
        cfg["schema"], [key], np.array([[newest + 10_005]]),
        {cfg["column"]: np.array([[1e9]])}, offset=99) == 1
    rig.forget_results()
    delta = rig.delta_over(rig.open(2))
    assert delta("leaf_offgrid_total") == 6
    assert delta("leaf_general_path_total") == 6
    assert delta("leaf_fused_kernel_total") == 6 * 3
    assert delta("leaf_phase_fused_total") == 6 * 3
    after = rig.samples()
    assert after['device_mirror_offgrid_rows{shard="%d"}'
                 % shard.shard_num] == 1
