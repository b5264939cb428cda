"""Counters from a fleet that churns through the served path (ISSUE 42): the
six panels of `promchurn-counters-262k.open` over the HTTP door against the
configuration's plain f64 reference (`benchmark/references/
churned_scrapes.py`), on seeded data at 2,048 live series x 240 samples over
4 shards (three replacements of 1%, four periods of outages: 2,108 series
stored), interpret-mode kernels; which route and which kernel variant each
leaf took (every one the ragged phased kernel, a request's four in ONE device
call); and, with nothing absent, that store, reference and answers are
`promscrape-counters-262k`'s bit for bit.

Tolerance 2e-5 (the cell's limit), relative, on every cell of every response;
readings here 2e-7 to 5e-7."""
import numpy as np
import pytest

import histrig
import test_promscrape_served as twin
import ts128rig
from histrig import bench_json, bench_module

CONFIG, CELL = "promchurn-counters-262k", "promchurn-counters-262k.open"
TWIN = "promscrape-counters-262k"
SERIES, SAMPLES = ts128rig.SERIES, ts128rig.SAMPLES
TOL = twin.TOL
SEEDS = (4200001, 2_147_483_777)
PANELS = range(6)


class ChurnRig(twin.ScrapeRig):
    CONFIG, CELL = CONFIG, CELL


@pytest.fixture(scope="module", autouse=True)
def interpret_kernels():
    with histrig.environ(FILODB_TPU_FUSED_INTERPRET="1"):
        yield


def _forget_compiles():
    # as test_promscrape_served: a dozen flavors of `fused_run` must not read
    # as a compile storm in a later file's health verdict
    from filodb_tpu.utils.events import journal
    journal.clear()


@pytest.fixture(scope="module", params=SEEDS)
def rig(request):
    r = ChurnRig(request.param)
    yield r
    r.close()
    _forget_compiles()


def test_the_cell_is_its_twin_in_all_but_which_samples_exist():
    cfg, other = (bench_json("configs", c) for c in (CONFIG, TWIN))
    mine = {"name", "source", "loader", "reference", "churn", "outages",
            "assumed", "guarantees", "on_device", "reduced"}
    assert {k for k in cfg if cfg[k] != other.get(k)} == mine
    assert cfg["reduced"].keys() == {"series"} and len(cfg["source"]) <= 200
    kept = [a for a in other["assumed"] if not a.startswith("every scrape")]
    assert cfg["assumed"][-len(kept):] == kept
    assert bench_json("workloads", CELL)["traffic"] == bench_json(
        "workloads", TWIN + ".open")["traffic"]
    # at the cell's own size: 290,975 series, a fifth of them short
    mod = bench_module("loaders", "churned_scrapes")
    target, first, end, (series, start, length) = mod.lives(5, cfg)
    assert len(target) == 290_975
    short = int(((end - first) < cfg["samples"]).sum())
    assert 56_000 < short < 58_000
    holed = len(set(series.tolist()))
    assert 15_000 < holed <= 12 * 1_310
    assert 68_000 < len(set(series.tolist())
                        | set(np.flatnonzero(end - first < 720).tolist())) \
        < 73_000


def test_every_series_holds_the_samples_of_its_life(rig):
    mod = bench_module("loaders", "churned_scrapes")
    target, first, end, holes = mod.lives(rig.seed, rig.cfg)
    N = SERIES + 3 * 20
    assert len(target) == N and sum(rig.per_shard) == N
    mask = mod.existing(first, end, holes, 0, N, SAMPLES)
    got = []
    for sh in rig.srv.memstore.shards_for(rig.cfg["dataset"]):
        store = sh.stores[rig.cfg["schema"]]
        got.append(store.counts[:store.num_series])
    got = np.sort(np.concatenate(got))
    np.testing.assert_array_equal(got, np.sort(mask.sum(axis=1)))
    assert (got < SAMPLES).sum() > 100


@pytest.mark.parametrize("panel", PANELS)
def test_served_panels_match_the_f64_reference(rig, panel):
    req = rig.open(0)[panel]
    (err, why), body = rig.ask(req)
    assert why is None, why
    assert err <= TOL, (req["params"]["query"], err)
    assert len(body["data"]["result"]) == (10, 1, 2, 10, 1, 10)[panel]
    assert body["stats"]["cache"]["result"] == "miss"


def test_every_leaf_is_one_ragged_phased_dispatch_over_a_placed_mirror(rig):
    from filodb_tpu.query.execbase import (_FUSED_CACHE_LOCK,
                                           _FUSED_PLAN_CACHE)
    rig.forget_results()
    delta = rig.delta_over(rig.open(0))     # every working set, once
    with _FUSED_CACHE_LOCK:
        _FUSED_PLAN_CACHE.clear()           # a plan is no server's
    delta = rig.delta_over(rig.open(1))
    for fam in ("leaf_fused_kernel_total", "leaf_phase_fused_total",
                "leaf_ragged_fused_total", "fused_enqueue_sets_total"):
        assert delta(fam) == 6 * 4, fam
    # the shards place on ONE base row: a request's leaves share one plan
    # and ride one device call
    assert delta("fused_enqueues_total") == 6
    assert delta('fused_cache_lookups_total{cache="plan",result="miss"}') == 1
    for fam in ("leaf_general_path_total", "leaf_offgrid_total",
                "leaf_host_routed_total", "leaf_host_gather_total",
                "leaf_fused_errors_total", "span_leaf_pad_values_calls_total"):
        assert delta(fam) == 0, fam
    after = rig.samples()
    placed = 0
    for sh in range(4):
        assert after['device_mirror_offgrid_rows{shard="%d"}' % sh] == 0
        placed += after['device_mirror_placed_rows{shard="%d"}' % sh]
    assert 100 < placed < 260               # 120 short rows, 40 outages
    assert after["device_mirror_rows_placed_total"] >= placed
    assert after["span_mirror_place_slots_calls_total"] >= 8


def test_a_range_that_ends_before_the_newest_series_was_born_pads_nothing(
        rig):
    """The last replacements start at scrape 180 of 240; an open eleven steps
    back ends before it, and the lookup leaves those 20 series out of it.
    The leaf still reads the working set of ALL the selector's series, the
    one every other range reads (no take, no pad), and drops the rows left
    out by their group: the lookup's part, its facts and its groups are made
    once a shard, and the answers are the reference's (`delta_over`)."""
    rig.delta_over(rig.open(0))
    rig.forget_results()
    reqs = rig.open(22)                 # two phases: eleven steps back
    assert reqs[0]["params"]["end"] * 1000 < rig.cfg["start_ms"] \
        + 180 * rig.cfg["scrape_ms"]
    delta = rig.delta_over(reqs)
    assert delta("leaf_ragged_fused_total") == 6 * 4
    assert delta('leaf_selection_fills_total{cause="range"}') == 4
    assert delta("span_leaf_group_ids_calls_total") == 4 * 4   # groupings
    for fam in ("span_leaf_pad_values_calls_total",
                "mirror_gather_takes_total",
                "leaf_general_path_total", "leaf_host_gather_total"):
        assert delta(fam) == 0, fam
    # ... and again, the same range: nothing is looked up, estimated,
    # grouped or padded anew
    rig.forget_results()
    delta = rig.delta_over(reqs)
    assert delta("leaf_ragged_fused_total") == 6 * 4
    for fam in ("span_leaf_pad_values_calls_total",
                "span_leaf_group_ids_calls_total",
                "span_leaf_pad_groups_calls_total",
                'leaf_selection_fills_total{cause="range"}',
                'leaf_selection_fills_total{cause="generation"}',
                "leaf_general_path_total", "leaf_host_gather_total"):
        assert delta(fam) == 0, fam


def test_requests_that_miss_together_build_a_working_set_once(rig):
    """A new snapshot makes every request in flight miss its padded values
    at once: one leaf a shard builds them (`execbase.fused_values`), the
    others wait for it and share them."""
    import time
    from concurrent.futures import ThreadPoolExecutor
    from filodb_tpu.query.execbase import (_FUSED_CACHE_LOCK,
                                           _FUSED_VALS_CACHE)
    rig.delta_over(rig.open(0))
    with _FUSED_CACHE_LOCK:
        _FUSED_VALS_CACHE.clear()
    rig.forget_results()
    time.sleep(0.3)
    before = rig.samples()
    with ThreadPoolExecutor(6) as pool:
        for (err, why), _ in pool.map(rig.ask, rig.open(0)):
            assert why is None and err <= TOL
    time.sleep(0.3)
    after = rig.samples()
    pads = after["span_leaf_pad_values_calls_total"] \
        - before["span_leaf_pad_values_calls_total"]
    assert pads == 4, pads              # not one a request and shard


def test_a_paging_attempt_that_finds_nothing_keeps_the_padded_values(rig):
    """A query that starts before a late-born row's first sample asks the
    column store for the row's history (the floor is unknown: a series that
    ended, was evicted and came back has its own there) and writes how far
    it asked.  That bookkeeping moves the store's generation and none of
    its samples: the mirror hands its arrays on (`data_gen` stays) and the
    fused leaf's padded working sets stand."""
    rig.delta_over(rig.open(0))
    mirrors = []
    for sh in rig.srv.memstore.shards_for(rig.cfg["dataset"]):
        store = sh.stores[rig.cfg["schema"]]
        store.set_paged(0, floor=0)
        mirrors.append((store, store.device_mirror,
                        store.device_mirror.snapshot()))
    rig.forget_results()
    delta = rig.delta_over(rig.open(0))
    assert delta("leaf_ragged_fused_total") == 6 * 4
    assert delta('leaf_selection_fills_total{cause="generation"}') == 4
    for fam in ("span_leaf_pad_values_calls_total",
                "mirror_gather_takes_total", "device_mirror_full_uploads"):
        assert delta(fam) == 0, fam
    for store, mirror, was in mirrors:
        snap = mirror.snapshot()
        assert snap.gen == store.generation > was.gen
        assert snap.data_gen == was.data_gen and snap.cols is was.cols


def test_a_scrape_after_the_build_is_placed_incrementally(rig):
    """The last test of the file: it appends to two rows of one shard."""
    rig.delta_over(rig.open(0))         # the mirrors are built
    sh = rig.srv.memstore.shards_for(rig.cfg["dataset"])[1]
    store = sh.stores[rig.cfg["schema"]]
    mirror = store.device_mirror
    n = store.num_series
    short = int(np.flatnonzero(store.counts[:n] < SAMPLES)[0])
    rows = np.array([0 if short else 1, short])
    last = store.ts[rows, store.counts[rows] - 1]
    # the next scrape of a full row, and one three scrapes later of a short
    newer = last + np.array([1, 3]) * rig.cfg["scrape_ms"]
    from filodb_tpu.utils.metrics import registry
    inc = registry.counter("device_mirror_incremental").value
    with sh._write_locked("test"):
        store.append_grid(rows, newer[:, None],
                          {rig.cfg["column"]: np.array([[1e9], [2e9]])})
        assert mirror.ensure_fresh(store)
    assert registry.counter("device_mirror_incremental").value == inc + 1
    snap = mirror.snapshot()
    assert snap.interval == rig.cfg["scrape_ms"]
    ts = np.asarray(snap.ts_off)[rows].astype(np.int64) + snap.base_ms
    vals = np.asarray(snap.cols[rig.cfg["column"]])[rows]
    for i in range(2):
        slot = int(np.flatnonzero(ts[i] == newer[i])[0])
        assert np.isfinite(vals[i, slot])
        assert np.isnan(vals[i, slot + 1:]).all()
    # ... and the panels still answer from the fused leaf (the appended
    # scrapes lie past every window of the traffic)
    rig.forget_results()
    delta = rig.delta_over(rig.open(2))
    assert delta("leaf_ragged_fused_total") == 6 * 4
    assert delta("leaf_general_path_total") == 0


def test_with_nothing_absent_the_deployment_is_its_twin_bit_for_bit(
        monkeypatch):
    seed = SEEDS[0]
    whole = ts128rig.small_config

    def nothing_absent(config=CONFIG, **over):
        cfg = whole(config, **over)
        if config == CONFIG:
            cfg = dict(cfg, churn=dict(cfg["churn"], percent=0),
                       outages=dict(cfg["outages"], percent=0))
        return cfg

    monkeypatch.setattr(ts128rig, "small_config", nothing_absent)
    from filodb_tpu.utils.metrics import registry
    mine, other = ChurnRig(seed), twin.ScrapeRig(seed)
    # (the loader's question placed three rows of its own; the mirrors
    # build at the first request)
    placed = registry.counter("device_mirror_rows_placed").value
    try:
        assert mine.per_shard == other.per_shard
        for j, fold, _ in mine.plan.tables():
            np.testing.assert_array_equal(
                mine.ref.table(mine.plan.panels[j], fold),
                other.ref.table(other.plan.panels[j], fold))
        for panel in (0, 4, 5):
            (_, why), a = mine.ask(mine.open(0)[panel])
            (_, why2), b = other.ask(other.open(0)[panel])
            assert why is None and why2 is None
            assert a["data"]["result"] == b["data"]["result"]
        # equal counts: the mirror's cheap cases, no placement
        assert registry.counter("device_mirror_rows_placed").value == placed
        after = mine.samples()
        for sh in range(4):
            assert after['device_mirror_placed_rows{shard="%d"}' % sh] == 0
    finally:
        mine.close()
        other.close()
        _forget_compiles()
