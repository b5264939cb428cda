"""The fused leaf's three caches (ISSUE 35): `execbase._FusedCache`, LRU,
bounded by bytes alone, so that they hold what a chip's deployment holds.
By hand on entries of known weight, each case once for every cache's name;
then behind the HTTP door of the 32-shard rig (`ts128rig`): a second open on
the same grid misses nothing, a budget of three working sets still answers
right, a new snapshot generation drops its mirror's older entries, and the
leaves of a request share one plan build."""
import time

import numpy as np
import pytest

import histrig
import ts128rig
from filodb_tpu.query import execbase
from filodb_tpu.query.execbase import _FUSED_CACHE_LOCK, _FusedCache
from filodb_tpu.utils.metrics import registry

CACHES = ("plan", "values", "groups")
TOL = 2e-5


def lookups(cache, result):
    return registry.counter("fused_cache_lookups", cache=cache,
                            result=result).value


def evictions(cache, cause):
    return registry.counter("fused_cache_evictions", cache=cache,
                            cause=cause).value


def gauge(name, cache):
    return registry.gauge(name, cache=cache).value


def cache_of(name, budget, **kw):
    """A cache under `name`'s counters whose entries weigh what they say."""
    return _FusedCache(name, lambda entry: entry[0], lambda: budget, **kw)


# ---- by hand


@pytest.mark.parametrize("name", CACHES)
def test_32_working_sets_under_a_budget_that_holds_them_all_stay(name):
    cache = cache_of(name, 32 * 25)
    gone = evictions(name, "bytes")
    for shard in range(32):
        cache.insert((shard, 1, "count", b"rows"), (25, shard))
    assert len(cache) == 32 and evictions(name, "bytes") == gone
    assert gauge("fused_cache_entries", name) == 32
    assert gauge("fused_cache_bytes", name) == 32 * 25
    hits, misses = lookups(name, "hit"), lookups(name, "miss")
    for shard in range(32):
        assert cache.lookup((shard, 1, "count", b"rows")) == (25, shard)
    assert (lookups(name, "hit") - hits, lookups(name, "miss") - misses) \
        == (32, 0)
    assert cache.lookup((99, 1, "count", b"rows")) is None
    assert lookups(name, "miss") - misses == 1


@pytest.mark.parametrize("name", CACHES)
def test_under_a_budget_that_holds_3_the_oldest_go_and_the_newest_stays(name):
    cache = cache_of(name, 3 * 25)
    gone = evictions(name, "bytes")
    for shard in range(32):
        cache.insert((shard, 1), (25, shard))
        assert (shard, 1) in cache              # the newest always stays
        assert sum(e[0] for e in cache.values()) <= 3 * 25
    assert list(cache) == [(29, 1), (30, 1), (31, 1)]
    assert evictions(name, "bytes") - gone == 29
    # a hit makes an entry the newest: the next insert takes the oldest
    assert cache.lookup((29, 1)) == (25, 29)
    cache.insert((32, 1), (25, 32))
    assert list(cache) == [(31, 1), (29, 1), (32, 1)]
    # one entry heavier than the whole budget is kept, alone
    cache.insert((33, 1), (1000, 33))
    assert list(cache) == [(33, 1)]
    assert gauge("fused_cache_entries", name) == 1
    assert gauge("fused_cache_bytes", name) == 1000


@pytest.mark.parametrize("name", CACHES)
def test_a_new_generation_drops_its_mirrors_older_entries(name):
    cache = cache_of(name, 1 << 20)
    by_gen, by_bytes = evictions(name, "generation"), evictions(name, "bytes")
    cache.insert((7, 1, "count", b"a"), (10, "a"))
    cache.insert((7, 1, "count", b"b"), (10, "b"))
    cache.insert((8, 1, "count", b"a"), (10, "other mirror"))
    cache.insert((7, 2, "count", b"a"), (10, "a again"))
    assert list(cache) == [(8, 1, "count", b"a"), (7, 2, "count", b"a")]
    assert evictions(name, "generation") - by_gen == 2
    assert evictions(name, "bytes") == by_bytes
    # a plan is no mirror's: its cache knows no generation
    plans = cache_of(name, 1 << 20, generations=False)
    plans.insert(("plan", b"ts", 0, 60), (10, "plan"))
    plans.insert(("plan", b"ts", 5, 60), (10, "plan"))
    assert len(plans) == 2 and evictions(name, "generation") - by_gen == 2
    assert execbase._FUSED_PLAN_CACHE._generations is False


def test_the_three_caches_have_no_fixed_count():
    with _FUSED_CACHE_LOCK:
        for cache in (execbase._FUSED_PLAN_CACHE, execbase._FUSED_VALS_CACHE,
                      execbase._FUSED_GROUP_CACHE):
            assert isinstance(cache, _FusedCache)
    # the group cache's budget follows the values cache's
    assert execbase._FUSED_GROUP_CACHE._budget() \
        == execbase._fused_vals_budget() // 16


# ---- behind the door


@pytest.fixture(scope="module", autouse=True)
def interpret_kernels():
    with histrig.environ(FILODB_TPU_FUSED_INTERPRET="1"):
        yield


@pytest.fixture(scope="module")
def rig():
    r = ts128rig.Ts128Rig(3500007)
    yield r
    r.close()


def ask_open(rig, n):
    for req in rig.open(n):
        (err, why), _ = rig.ask(req)
        assert why is None and err <= TOL, (req["params"]["query"], err, why)
    time.sleep(0.3)         # the last request's spans are booked behind it


@pytest.mark.parametrize("name", CACHES)
def test_the_first_insert_of_a_key_stays_and_insert_says_what_is_held(name):
    """Two threads that missed one key both build: the second insert gets
    the first's entry back and leaves the cache as it was, so both go on
    with ONE object (a request's leaves ride one device call only while
    they hold the same plan object, ISSUE 36)."""
    cache = cache_of(name, 100)
    first, second = (25, "first"), (25, "second")
    assert cache.insert(("k", 1), first) is first
    held = gauge("fused_cache_bytes", name)
    assert cache.insert(("k", 1), second) is first
    assert cache.lookup(("k", 1)) is first and len(cache) == 1
    assert gauge("fused_cache_bytes", name) == held == 25


def misses():
    return {c: lookups(c, "miss") for c in CACHES}


def test_a_second_open_on_the_same_grid_misses_no_cache(rig):
    ask_open(rig, 0)
    with _FUSED_CACHE_LOCK:
        assert len(execbase._FUSED_VALS_CACHE) >= rig.populated == 30
        assert len(execbase._FUSED_GROUP_CACHE) >= 4 * rig.populated
    before, hits = misses(), lookups("values", "hit")
    rig.forget_results()            # or the frontend answers from its cache
    ask_open(rig, 0)
    assert misses() == before
    assert lookups("values", "hit") - hits == 6 * rig.populated


def test_the_leaves_of_a_request_share_one_plan_build(rig):
    with _FUSED_CACHE_LOCK:
        # a plan is no shard's and no server's: another test file's rig
        # in this process may have built this grid's already
        execbase._FUSED_PLAN_CACHE.clear()
    built = registry.counter("span_leaf_build_plan_calls").value
    before, hits = lookups("plan", "miss"), lookups("plan", "hit")
    (err, why), _ = rig.ask(rig.open(1)[0])
    assert why is None and err <= TOL
    time.sleep(0.3)
    assert lookups("plan", "miss") - before == 1
    assert lookups("plan", "hit") - hits == rig.populated - 1
    assert registry.counter("span_leaf_build_plan_calls").value - built == 1
    with _FUSED_CACHE_LOCK:
        assert all(k[0] == "plan" for k in execbase._FUSED_PLAN_CACHE)


def test_two_requests_that_miss_one_grids_plan_at_once_build_it_once(
        rig, monkeypatch):
    """The six panels of an open share a grid and are in flight together:
    when two of them miss the plan cache at once, ONE builds
    (`execbase.fused_plan`: the other waits for it off the interpreter
    lock; since ISSUE 44, where a plan of 721 windows costs a build a
    hundred hand-offs of that lock), and every leaf of BOTH requests holds
    that one object: each request is still one device call of 30 working
    sets."""
    import threading
    from filodb_tpu.ops import pallas_fused as pf
    with _FUSED_CACHE_LOCK:
        execbase._FUSED_PLAN_CACHE.clear()
    real = pf.build_plan

    def build_plan(*a, **kw):
        time.sleep(1.0)             # the other request reaches its miss
        return real(*a, **kw)

    monkeypatch.setattr(pf, "build_plan", build_plan)
    time.sleep(0.3)
    enq = registry.counter("fused_enqueues").value
    sets = registry.counter("fused_enqueue_sets").value
    built = registry.counter("span_leaf_build_plan_calls").value
    reqs, out = rig.open(2)[:2], []
    threads = [threading.Thread(target=lambda r=r: out.append(rig.ask(r)))
               for r in reqs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert len(out) == 2 and all(why is None and err <= TOL
                                 for (err, why), _ in out)
    time.sleep(0.3)
    assert registry.counter("span_leaf_build_plan_calls").value - built == 1
    assert registry.counter("fused_enqueues").value - enq == 2
    assert registry.counter("fused_enqueue_sets").value - sets \
        == 2 * rig.populated
    with _FUSED_CACHE_LOCK:
        assert len(execbase._FUSED_PLAN_CACHE) == 1


def test_a_budget_of_three_working_sets_answers_right_and_keeps_the_newest(
        rig, monkeypatch):
    with _FUSED_CACHE_LOCK:
        # the caches are the process's: another test file's rig may have
        # left working sets far larger than this one's
        execbase._FUSED_VALS_CACHE.clear()
    rig.forget_results()
    ask_open(rig, 0)
    with _FUSED_CACHE_LOCK:
        largest = max(execbase._vals_nbytes(v)
                      for v in execbase._FUSED_VALS_CACHE.values())
    monkeypatch.setattr(execbase, "_FUSED_VALS_CACHE_BYTES", 3 * largest)
    with _FUSED_CACHE_LOCK:
        execbase._FUSED_VALS_CACHE.clear()  # a cache evicts when it inserts
    gone = evictions("values", "bytes")
    missed = lookups("values", "miss")
    ask_open(rig, 2)
    with _FUSED_CACHE_LOCK:
        held = sum(execbase._vals_nbytes(v)
                   for v in execbase._FUSED_VALS_CACHE.values())
        assert 1 <= len(execbase._FUSED_VALS_CACHE) and held <= 3 * largest
    assert evictions("values", "bytes") - gone >= rig.populated - 3
    # 30 working sets through room for three: every leaf pads again
    assert lookups("values", "miss") - missed >= 5 * rig.populated


def test_an_append_moves_the_generation_and_the_mirrors_entries_go(rig):
    from filodb_tpu.core.partkey import PartKey
    ask_open(rig, 0)
    cfg = rig.cfg
    grid = histrig.bench_module("loaders", "grid")
    ds = cfg["dataset"]
    mapper, spread = rig.srv.mappers[ds], rig.srv.spreads[ds]
    keys = [PartKey.make(cfg["metric"], {
        lab: grid.label_value(spec, i) for lab, spec in cfg["labels"].items()})
        for i in range(cfg["series"])]
    num = mapper.ingestion_shard(keys[0].shard_key_hash(),
                                 keys[0].partition_hash(),
                                 spread.spread_for(keys[0].shard_key()))
    mine = [k for k in keys if mapper.ingestion_shard(
        k.shard_key_hash(), k.partition_hash(),
        spread.spread_for(k.shard_key())) == num]
    shard = rig.srv.memstore.shards_for(ds)[num]
    mirror = shard.stores[cfg["schema"]].device_mirror
    with _FUSED_CACHE_LOCK:
        old = [k for k in execbase._FUSED_VALS_CACHE if k[0] == mirror.serial]
    assert len(old) == 1
    # one scrape more for every series of that shard (the grid stays
    # shared), behind every window the traffic asks for: no answer moves
    newest = cfg["start_ms"] + cfg["samples"] * cfg["scrape_ms"]
    got = shard.ingest_columns(
        cfg["schema"], mine, np.full((len(mine), 1), newest, np.int64),
        {cfg["column"]: np.full((len(mine), 1), 1e9)}, offset=1)
    assert got == len(mine) == rig.per_shard[num]
    by_gen = evictions("values", "generation")
    rig.forget_results()
    ask_open(rig, 0)
    with _FUSED_CACHE_LOCK:
        new = [k for k in execbase._FUSED_VALS_CACHE if k[0] == mirror.serial]
        groups = [k for k in execbase._FUSED_GROUP_CACHE
                  if k[0] == mirror.serial]
    assert len(new) == 1 and new[0][1] != old[0][1]
    assert all(k[1] == new[0][1] for k in groups)
    assert evictions("values", "generation") - by_gen == 1
