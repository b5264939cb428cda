"""The reduce node above the one fetch (ISSUE 39): `_reduce_aligned` merges
every partial of a request in ONE `ufunc.at` over the partials' rows stacked
in child order (two for `min`, whose components combine differently; a call a
partial for `hist_sum`, which keeps its fold), from a row index remembered by
the children's tokens, and `present_partial` finishes the merged block in
NumPy on the host.

What is held here: the merged block is BIT-equal to a plain fold, one partial
and one row at a time, kept in this file; the merged key order is the fold's;
`reduce_merge_calls_total` counts the calls; the layout memo hits, misses and
stands aside as the tokens say; the NumPy presenter is `ops/agg.present`; and
behind the HTTP door, with `ops/agg.present` made to raise, every panel of the
counters and gauges cells at 4 shards and of the 32-shard cell still answers
as the benchmark's f64 reference does, one merge call a request."""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

import histrig
import ts128rig
from filodb_tpu.ops import agg as agg_ops
from filodb_tpu.query import execbase as eb
from filodb_tpu.query.rangevector import RangeVectorKey
from filodb_tpu.utils.metrics import registry

OPS = ("sum", "count", "avg", "min", "max", "stddev", "stdvar", "group",
       "hist_sum")
W, BUCKETS = 7, 8
KEYS = [RangeVectorKey.make({"_ns_": f"App-{i}", "dc": f"DC{i % 2}"})
        for i in range(80)]
LES = 2.0 ** np.arange(1, BUCKETS + 1)
_UFUNCS = {"sum": np.add, "min": np.minimum, "max": np.maximum}
_INIT = {"sum": 0.0, "min": np.inf, "max": -np.inf}


def _comp(op, rng, G):
    """A leaf's [G, W, C] partial in ops/agg.AGGREGATORS' layout, with
    windows in which a group has no series."""
    n = rng.integers(0, 4, (G, W)).astype(np.float64)      # series present
    x = rng.normal(50.0, 30.0, (G, W))
    if op in ("sum", "avg"):
        return np.stack([np.where(n > 0, x * n, 0.0), n], -1)
    if op == "count":
        return n[..., None]
    if op in ("stddev", "stdvar"):
        # n series of mean m and variance v: well conditioned in f32 too
        m, v = rng.normal(0.0, 1.0, (G, W)), rng.uniform(0.5, 2.0, (G, W))
        return np.stack([m * n, (m * m + v) * n, n], -1)
    if op in ("min", "max"):
        return np.stack([np.where(n > 0, x, np.inf if op == "min"
                                  else -np.inf), (n > 0) * 1.0], -1)
    if op == "group":
        return np.where(n > 0, 1.0, -np.inf)[..., None]
    buckets = np.cumsum(rng.random((G, W, BUCKETS)) * 9.0, -1)
    return np.concatenate([buckets * (n[..., None] > 0), n[..., None]], -1)


def _partial(op, keys, comp, token):
    return eb.AggPartial(op, list(keys), np.arange(W) * 60_000, comp=comp,
                         bucket_les=LES if op == "hist_sum" else None,
                         cache_token=token)


def _choose(rng, pool, n):
    return [KEYS[i] for i in rng.choice(pool, n, replace=False)]


def _children(op, layout, rng):
    """-> the reduce node's children (a None is an empty shard) for one of
    the ways a request's partials reach the node."""
    shards = {"one": 1, "thirty": 30}.get(layout, 4)
    counts = [(3, 11, 40, 17, 80)[s % 5] if layout == "thirty" else 9
              for s in range(shards)]
    if layout == "disjoint":
        keysets = [KEYS[9 * s:9 * s + 9] for s in range(shards)]
    else:
        keysets = [_choose(rng, 80 if layout == "thirty" else 12, n)
                   for n in counts]
    if layout == "one_shard_only":
        keysets = [[k for k in ks if k != KEYS[3]] for ks in keysets]
        keysets[2] = keysets[2][:4] + [KEYS[3]] + keysets[2][4:]
    comps = [_comp(op, rng, len(ks)) for ks in keysets]
    if layout in ("block_views", "mix", "thirty", "one_shard_only"):
        # as pf.FusedDispatch hands them on: views of ONE block whose sets
        # lie by shape, not in child order (here: reversed, pad rows between)
        held = range(shards) if layout != "mix" else (1, 3)
        block = np.full((sum(len(comps[s]) + 2 for s in held),)
                        + comps[0].shape[1:], 7.25)
        lo = 0
        for s in reversed(held):
            block[lo:lo + len(comps[s])] = comps[s]
            comps[s] = block[lo:lo + len(comps[s])]
            lo += len(comps[s]) + 2
        if layout == "mix":
            comps[0] = comps[0].astype(np.float32)     # widens exactly
    parts = [_partial(op, ks, c, ("agg", op, ("_ns_", "dc"), (),
                                  ("rows", layout, s)))
             for s, (ks, c) in enumerate(zip(keysets, comps))]
    if layout == "none_among":
        parts[1:1] = [None]
    return parts


def _fold(parts):
    """The plain reduce: one partial after another, one row after another."""
    parts = [p for p in parts if p is not None]
    gkeys = list(dict.fromkeys(k for p in parts for k in p.group_keys))
    C = parts[0].comp.shape[-1]
    combs = agg_ops.combiners_for(parts[0].op, C)
    out = np.empty((len(gkeys), W, C))
    for i, comb in enumerate(combs):
        out[..., i] = _INIT[comb]
    for p in parts:
        for row, k in zip(np.asarray(p.comp, np.float64), p.group_keys):
            g = gkeys.index(k)
            for i, comb in enumerate(combs):
                out[g, :, i] = _UFUNCS[comb](out[g, :, i], row[:, i])
    return gkeys, out


def _counter(name, **tags):
    return registry.counter(name, **tags).value


LAYOUTS = ("block_views", "separate", "mix", "one", "none_among", "disjoint",
           "one_shard_only", "thirty")


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("op", OPS)
def test_the_merged_partial_is_bit_equal_to_the_plain_fold(op, layout):
    rng = np.random.default_rng([OPS.index(op), LAYOUTS.index(layout)])
    parts = _children(op, layout, rng)
    gkeys, want = _fold(parts)
    calls = _counter("reduce_merge_calls")
    for again in range(2):          # the layout built, then remembered
        got = eb.reduce_partials(parts)
        assert got.group_keys == gkeys
        assert got.comp.dtype == np.float64
        assert np.array_equal(got.comp, want, equal_nan=True)
        assert got.op == op and got.cache_token == ("red",) + tuple(
            p.cache_token for p in parts if p is not None)
    assert (got.bucket_les is LES) == (op == "hist_sum")
    # one call for every component where one combiner serves them all
    # (`max` combines (max, max)); `min` is (min, max): one a component;
    # `hist_sum` keeps PR 31's fold, a call a partial (execbase.py says why)
    merged = sum(p is not None for p in parts)
    assert _counter("reduce_merge_calls") - calls == 2 * {
        "min": 2, "hist_sum": merged}.get(op, 1)


@pytest.mark.parametrize("op", OPS)
def test_the_layout_is_remembered_by_the_childrens_tokens(op):
    rng = np.random.default_rng(OPS.index(op))
    parts = _children(op, "block_views", rng)
    for s, p in enumerate(parts):
        p.cache_token = ("agg", op, (), (), ("memo", op, s))
    hit = lambda: _counter("reduce_layout", result="hit")      # noqa: E731
    miss = lambda: _counter("reduce_layout", result="miss")    # noqa: E731
    h, m = hit(), miss()
    first = eb.reduce_partials(parts)
    assert (hit() - h, miss() - m) == (0, 1)
    second = eb.reduce_partials(parts)
    assert (hit() - h, miss() - m) == (1, 1)
    assert second.group_keys == first.group_keys
    assert second.group_keys is not first.group_keys    # the caller's own
    assert np.array_equal(second.comp, first.comp, equal_nan=True)
    # a new keys epoch or row set is a new token: other keys, another layout
    moved = _children(op, "separate", rng)
    for s, p in enumerate(moved):
        p.cache_token = parts[s].cache_token
    moved[2].cache_token = ("agg", op, (), (), ("memo", op, "epoch 2"))
    third = eb.reduce_partials(moved)
    assert (hit() - h, miss() - m) == (1, 2)
    assert third.group_keys == _fold(moved)[0]
    # any child without a token: nothing looked up, nothing remembered
    parts[1].cache_token = None
    held = len(eb._REDUCE_LAYOUTS)
    fourth = eb.reduce_partials(parts)
    assert (hit() - h, miss() - m) == (1, 2)
    assert fourth.cache_token is None and len(eb._REDUCE_LAYOUTS) == held
    assert np.array_equal(fourth.comp, first.comp, equal_nan=True)


def test_the_layouts_are_bounded_and_the_oldest_go_first():
    rng = np.random.default_rng(5)
    parts = _children("sum", "separate", rng)
    eb.reduce_partials(parts)
    kept = eb._reduced_token(parts)
    for n in range(eb._REDUCE_LAYOUTS_MAX + 3):
        parts[0].cache_token = ("agg", "sum", (), (), ("bound", n))
        eb.reduce_partials(parts)
        if n % 50 == 0:
            eb._merge_layout(parts[:0], kept)    # read: stays the newest
    assert len(eb._REDUCE_LAYOUTS) == eb._REDUCE_LAYOUTS_MAX
    assert kept in eb._REDUCE_LAYOUTS
    assert ("red", ("agg", "sum", (), (), ("bound", 0))) + kept[2:] \
        not in eb._REDUCE_LAYOUTS


@pytest.mark.parametrize("op", ("sum", "min", "hist_sum"))
def test_two_threads_merging_the_same_tokens_get_equal_answers(op):
    rng = np.random.default_rng(11)
    parts = _children(op, "thirty", rng)
    for s, p in enumerate(parts):
        p.cache_token = ("agg", op, (), (), ("threads", op, s))
    gkeys, want = _fold(parts)
    start, got = threading.Barrier(2), [None, None]

    def merge(i):
        start.wait()
        got[i] = [eb.reduce_partials(parts) for _ in range(5)]

    threads = [threading.Thread(target=merge, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for p in got[0] + got[1]:
        assert p.group_keys == gkeys
        assert np.array_equal(p.comp, want, equal_nan=True)
    assert len({id(eb._merge_layout(parts, eb._reduced_token(parts))[1])
                for _ in range(3)}) == 1        # the first insert stayed


# ------------------------------------------------- presentation on the host

PRESENTED = OPS[:-1]


def _merged(op, rng, f32):
    """A merged [G, W, C] block with empty groups and empty windows."""
    comp = eb.reduce_partials(_children(op, "separate", rng)).comp
    comp = np.concatenate([comp, _comp(op, rng, 3)])
    empty = {"min": (np.inf, 0.0), "max": (-np.inf, 0.0),
             "group": (-np.inf,)}.get(op, (0.0,) * comp.shape[-1])
    comp[[1, 5]] = empty                # groups nobody reported into
    comp[:, [0, 4]] = empty             # windows before the first sample
    return comp.astype(np.float32).astype(np.float64) if f32 else comp


@pytest.mark.parametrize("op", PRESENTED)
def test_the_numpy_presenter_is_the_devices(op):
    rng = np.random.default_rng(OPS.index(op) + 100)
    comp = _merged(op, rng, f32=True)
    part = eb.AggPartial(op, KEYS[:len(comp)], np.arange(W), comp=comp,
                         cache_token=("agg", op, (), (), ("present",)))
    block = eb.present_partial(part)
    got = block.values
    assert got.dtype == np.float64 and got.shape == comp.shape[:2]
    assert block.keys == part.group_keys
    assert block.cache_token == part.cache_token
    # the device's program in f64 (the tests run with x64): exact, but for
    # the variance, whose multiply and subtract XLA may fuse into one
    # rounding
    want = np.asarray(agg_ops.present(op, jnp.asarray(comp)))
    assert want.dtype == np.float64
    if op.startswith("std"):
        np.testing.assert_allclose(got, want, rtol=1e-12, equal_nan=True)
    else:
        assert np.array_equal(got, want, equal_nan=True)
    assert np.isnan(got[[1, 5]]).all() and np.isnan(got[:, [0, 4]]).all()
    assert not np.isnan(got).all()
    # ... and as the served path ran it until now, in f32: the same NaN
    # pattern, the values within f32 rounding
    dev = np.asarray(agg_ops.present(op, jnp.asarray(comp, jnp.float32)))
    assert dev.dtype == np.float32
    assert np.array_equal(np.isnan(got), np.isnan(dev))
    np.testing.assert_allclose(
        got, dev, rtol=1e-6, atol=1e-5 if op.startswith("std") else 0.0)
    if op in ("sum", "count", "min", "max", "group"):
        assert np.array_equal(got, dev, equal_nan=True)


@pytest.mark.parametrize("op", PRESENTED)
def test_the_presenter_keeps_what_f32_would_round(op):
    """f64 sums that f32 cannot hold reach the response unrounded."""
    rng = np.random.default_rng(OPS.index(op) + 200)
    comp = _merged(op, rng, f32=False)
    got = eb.present_partial(eb.AggPartial(
        op, KEYS[:len(comp)], np.arange(W), comp=comp)).values
    want = np.asarray(agg_ops.present(op, jnp.asarray(comp)))
    np.testing.assert_allclose(got, want, rtol=1e-15, equal_nan=True)
    with np.errstate(all="raise"):      # no warning from an empty cell
        eb._present_comp(op, comp)


def test_an_unknown_op_is_refused():
    with pytest.raises(ValueError):
        eb._present_comp("median", np.zeros((1, 1, 2)))


# ------------------------------------------------------ behind the HTTP door

CELLS = {
    "counters-4sh": ("promperf-counters-262k",
                     "promperf-counters-262k.open", 2e-5),
    "gauges-4sh": ("tsdev-gauges-262k", "tsdev-gauges-262k.open", 3e-6),
    "counters-32sh": (ts128rig.CONFIG, ts128rig.CELL, 2e-5),
}
SEED = 2_147_483_777


@pytest.fixture(scope="module")
def interpret_kernels():
    with histrig.environ(FILODB_TPU_FUSED_INTERPRET="1"):
        yield


@pytest.fixture(scope="module")
def no_device_presenter():
    """`ops/agg.present` raises: what still answers does not call it."""
    def refuse(op, partial):
        raise AssertionError("agg_ops.present called on the served path")
    kept, agg_ops.present = agg_ops.present, refuse
    yield
    agg_ops.present = kept


@pytest.fixture(scope="module", params=sorted(CELLS))
def rig(request, interpret_kernels, no_device_presenter):
    config, cell, tol = CELLS[request.param]
    r = type("Rig", (ts128rig.Ts128Rig,), {"CONFIG": config, "CELL": cell})(
        SEED)
    r.tol = tol
    yield r
    r.close()
    from filodb_tpu.utils.events import journal
    journal.clear()         # compiles of a dozen flavors: no later file's


@pytest.mark.parametrize("panel", range(6))
def test_served_panels_answer_as_the_reference_without_the_device_presenter(
        rig, panel):
    req = rig.open(0)[panel]
    (err, why), body = rig.ask(req)
    assert why is None, why
    assert err <= rig.tol, (req["params"]["query"], err)
    assert body["stats"]["cache"]["result"] == "miss"


def test_a_request_is_one_merge_call_and_one_remembered_layout(rig):
    rig.forget_results()
    for req in rig.open(0):     # every working set, grouping and layout
        rig.ask(req)
    time.sleep(0.3)             # spans are booked after the body is sent
    before = rig.samples()
    for req in rig.open(1):
        (err, why), _ = rig.ask(req)
        assert why is None and err <= rig.tol, (req["params"]["query"], err)
    time.sleep(0.3)
    after = rig.samples()
    delta = lambda name: after.get(name, 0.0) - before.get(name, 0.0)  # noqa: E731,E501
    assert delta("reduce_merge_calls_total") == 6
    assert delta('reduce_layout_total{result="hit"}') == 6
    assert delta('reduce_layout_total{result="miss"}') == 0
    assert delta("span_exec_ReduceAggregateExec_calls_total") == 6
    assert delta("leaf_fused_kernel_total") == 6 * rig.populated
    assert delta("fused_enqueues_total") == 6
