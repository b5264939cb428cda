"""`histogram_quantile(q, sum [by (..)](rate(h[5m])))` finished on the device
(ISSUE 51): the cross-shard `hist_sum` merge and the quantile as the epilogue
of the fused leaves' one device call (`pf._run_hist_quantile`), against the
host path it replaces (the per-leaf `_present_hist`, `reduce_partials`' fold,
`present_partial`, `ops/hist._histogram_quantile_np`) AND against an f64
oracle written here, at the shape of `histdev-64b-4k.quantiles`: four working
sets of unequal rows, 64 buckets, 720 samples a series, 61 windows of `[5m]`
a minute apart; 1, 2 and 10 merged groups; q 0.5, 0.9, 0.99, 0 and 1.

The data holds what the epilogue's rules are for: a group one shard lacks, a
window with too few samples for a rate (NaN for every group), one series
whose counts FALL from a bucket to the next (the running maximum), a reset in
the range, mass in the first bucket and in the top one, and (the second
scheme) a `+Inf` top bucket.  Kernels interpreted, on the CPU.

Then the declines: each shape the epilogue turns away answers by the host
path, to the bit of the answer the parent's code gives, and books its reason.
"""
import numpy as np
import pytest

import jax

import histrig
from filodb_tpu.ops import hist as hist_ops
from filodb_tpu.ops import pallas_fused as pf
from filodb_tpu.query import execbase, exprfuse, fusedbatch
from filodb_tpu.query.rangevector import RangeVectorKey
from filodb_tpu.utils.metrics import registry

B, T, W, STEP, RANGE = 64, 720, 61, 10_000, 300_000
SERIES = (5, 4, 3, 3)           # the four shards' series: unequal rows
QS = (0.5, 0.9, 0.99, 0.0, 1.0)
TOL = 2e-4                      # the benchmark's own limit is 5e-4
GEOMETRIC = 2.0 ** np.arange(1, B + 1)              # the cell's scheme
INF_TOP = np.append(2.0 ** np.arange(1, B), np.inf)

# the groupings: (name, merged groups, a series' group from (shard, series))
GROUPINGS = {
    "ungrouped": (1, lambda shard, s: 0),
    "by-dc": (2, lambda shard, s: s % 2),
    # ten namespaces, each on two of the four shards (the gateway's
    # spread): a shard lacks most groups, no two shards hold the same ones
    "by-ns": (10, lambda shard, s: (3 * shard + s) % 10),
}


def _data(seed, les):
    """Per shard [S, T, B] f64 cumulative bucket counters on one 10 s grid,
    a reset included, and the timestamp row."""
    rng = np.random.default_rng(seed)
    ts = np.arange(T, dtype=np.int64) * STEP
    shards = []
    for shard, S in enumerate(SERIES):
        # a series' latencies fall around its own bucket; series 0 of
        # shard 0 in the FIRST bucket, series 1 of shard 1 up to the TOP
        centre = rng.integers(8, 40, size=S).astype(float)
        if shard == 0:
            centre[0] = 0.0
        if shard == 1:
            centre[1] = B - 1.0
        spread = rng.uniform(1.5, 4.0, size=S)
        weight = np.exp(-0.5 * ((np.arange(B)[None, :] - centre[:, None])
                                / spread[:, None]) ** 2)
        weight /= weight.sum(axis=1, keepdims=True)
        hits = rng.poisson(40.0 * weight[:, None, :], size=(S, T, B))
        counts = np.cumsum(np.cumsum(hits, axis=2), axis=1).astype(float)
        if shard == 2:
            # a broken exporter: one bucket of one series counts a third
            # of what the bucket below it counts (the running maximum)
            b = int(centre[0]) + 1
            counts[0, :, b] = np.floor(counts[0, :, b - 1] / 3.0)
        if shard == 3:
            # a restarted process: all buckets of a series back to zero
            at = T // 2 + 7
            counts[1, at:] -= counts[1, at - 1]
        shards.append(counts)
    return ts, shards


def _corrected(raw):
    """Counter correction along time, as the mirror stores the rows: what
    a row fell from is added to everything after the fall."""
    fell = np.where(raw[:, 1:] < raw[:, :-1], raw[:, :-1], 0.0)
    return raw + np.concatenate(
        [np.zeros_like(raw[:, :1]), np.cumsum(fell, axis=1)], axis=1)


def _wends(ts):
    # the first window holds ONE sample: no rate, NaN for every group
    return ts[0] + 5_000 + np.arange(W, dtype=np.int64) * 60_000


def _oracle_rate(ts, corrected, wends):
    """f64 PromQL rate of dense rows [R, T] on the shared grid -> [R, W],
    NaN where a window holds fewer than two samples: the extrapolation of
    tests/oracle.extrapolated_rate, a window at a time."""
    out = np.full((corrected.shape[0], len(wends)), np.nan)
    for w, wend in enumerate(wends):
        inside = np.flatnonzero((ts >= wend - RANGE + 1) & (ts <= wend))
        if len(inside) < 2:
            continue
        t1, t2 = float(ts[inside[0]]), float(ts[inside[-1]])
        v1, v2 = corrected[:, inside[0]], corrected[:, inside[-1]]
        sampled = (t2 - t1) / 1000.0
        avg = sampled / (len(inside) - 1)
        delta = v2 - v1
        start = np.full(len(v1), (t1 - (wend - RANGE)) / 1000.0)
        with np.errstate(divide="ignore", invalid="ignore"):
            zero = sampled * (v1 / delta)
        start = np.where((delta > 0) & (v1 >= 0) & (zero < start), zero,
                         start)
        end = (wend - t2) / 1000.0
        extrap = sampled + np.where(start < avg * 1.1, start, avg / 2) \
            + (end if end < avg * 1.1 else avg / 2)
        out[:, w] = delta * (extrap / sampled) / (RANGE / 1000.0)
    return out


def _oracle_quantile(q, buckets, les):
    """f64 histogram_quantile of ONE cell's cumulative buckets [B], the
    Prometheus rule in plain Python."""
    if np.isnan(buckets).any():
        return np.nan
    cum = np.maximum.accumulate(buckets)
    total = cum[-1]
    if not total > 0:
        return np.nan
    rank = q * total
    b = next((i for i in range(len(cum)) if cum[i] >= rank), len(cum) - 1)
    if np.isinf(les[b]):
        return les[np.isfinite(les)].max()
    lo_le = (les[b] if les[b] <= 0 else 0.0) if b == 0 else les[b - 1]
    lo_cnt = 0.0 if b == 0 else cum[b - 1]
    width = cum[b] - lo_cnt
    frac = (rank - lo_cnt) / width if width > 0 else 0.0
    return lo_le + (les[b] - lo_le) * frac


class Case:
    """One scheme's four prepared histogram leaves under one grouping, as
    leafexec hands them to fusedbatch: FusedCalls with (group, bucket)
    slots, keys and tokens; and the f64 oracle's merged bucket sums."""

    def __init__(self, grouping, les, seed=51, holes=False, tag=None):
        # `tag`: what the calls' keys and tokens say of the rows in place
        # of `holes` (one series set, asked while a hole is in its range
        # and after the hole has rolled out: the tokens are the same)
        tag = holes if tag is None else tag
        self.les = les
        self.merged, group_of = GROUPINGS[grouping]
        ts, shards = _data(seed, les)
        wends = _wends(ts)
        self.wends = wends
        plan = pf.build_plan(ts, wends, RANGE)
        keys = [RangeVectorKey.make({"g": str(g)})
                for g in range(self.merged)]
        self.calls, first_seen = [], []
        truth = np.zeros((self.merged, W, B))
        seen = np.zeros(self.merged, bool)
        for shard, raw in enumerate(shards):
            S = raw.shape[0]
            flat = np.moveaxis(raw, 2, 1).reshape(S * B, T)
            rows = _corrected(flat)
            of = np.asarray([group_of(shard, s) for s in range(S)])
            local = sorted(set(of.tolist()), key=of.tolist().index)
            first_seen += [g for g in local if g not in first_seen]
            gids = np.asarray([local.index(g) for g in of])
            rate = _oracle_rate(ts, rows, wends).reshape(S, B, W)
            np.add.at(truth, of, np.moveaxis(rate, 1, 2))
            seen[of] = True
            slots = (gids[:, None] * B + np.arange(B)[None, :]).reshape(-1)
            if holes:
                # missed scrapes, all of a series' buckets together: one
                # series a shard for ten minutes, and every series of
                # merged group 0 for the same ten (no present series)
                out = np.zeros(S, bool)
                out[0] = True
                rows.reshape(S, B, T)[out, :, 100 + 40 * shard:
                                      160 + 40 * shard] = np.nan
                rows.reshape(S, B, T)[of == 0, :, 200:260] = np.nan
            base = rows[:, :1].astype(np.float32).astype(np.float64)
            self.calls.append(fusedbatch.FusedCall(
                plan=plan,
                values=pf.pad_values((rows - base).astype(np.float32),
                                     base[:, 0].astype(np.float32), plan),
                groups=pf.pad_groups(slots, S * B, len(local) * B),
                gkeys=[keys[g] for g in local], wends=wends, fn="rate",
                op="sum", precorrected=True, interpret=True, ragged=holes,
                num_series=S * B, bucket_les=les, num_buckets=B,
                cache_key=("case", grouping, seed, les.tobytes(), shard,
                           tag),
                cache_token=execbase.agg_token(
                    "hist_sum", ("g",), (),
                    ("case", grouping, seed, les.tobytes(), shard, tag))))
        assert seen.all()
        self.keys = [keys[g] for g in first_seen]
        self.truth = truth[first_seen]                  # [G, W, B] f64

    def host(self, q):
        """Today's path: the sets' blocks read back, presented a leaf,
        folded, presented, the NumPy quantile."""
        parts = fusedbatch.finish_fused_calls(self.calls)
        block = execbase.present_partial(execbase.reduce_partials(parts))
        assert list(block.keys) == self.keys
        return np.asarray(hist_ops.histogram_quantile(
            q, block.values, np.asarray(block.bucket_les)))

    def device(self, q):
        hq = fusedbatch.HistQuantileCall(q, list(range(len(self.calls))))
        parts = fusedbatch.finish_fused_calls(self.calls, [hq])
        assert hq.block is not None, "declined"
        assert all(p.op == "hist_sum" and p.comp is None for p in parts)
        assert list(hq.block.keys) == self.keys
        return hq.block

    def oracle(self, q):
        return np.asarray([[_oracle_quantile(q, self.truth[g, w], self.les)
                            for w in range(W)]
                           for g in range(len(self.keys))])


@pytest.fixture(scope="module")
def cases():
    made = {}

    def case(grouping, scheme):
        if (grouping, scheme) not in made:
            made[grouping, scheme] = Case(
                grouping, GEOMETRIC if scheme == "geometric" else INF_TOP)
        return made[grouping, scheme]
    return case


def _rel(a, b):
    """Largest relative error of the cells both hold; the NaN cells must
    be the same cells."""
    assert np.array_equal(np.isnan(a), np.isnan(b))
    ok = ~np.isnan(b)
    return float(np.max(np.abs(a[ok] - b[ok])
                        / np.maximum(np.abs(b[ok]), 1e-300), initial=0.0))


@pytest.mark.parametrize("q", QS)
@pytest.mark.parametrize("scheme", ["geometric", "inf-top"])
@pytest.mark.parametrize("grouping", list(GROUPINGS))
def test_the_epilogue_answers_as_the_host_path_and_the_f64_oracle(
        cases, grouping, scheme, q):
    case = cases(grouping, scheme)
    block = case.device(q)
    got = np.asarray(block.values)
    assert got.dtype == np.float64 and got.shape == (case.merged, W)
    assert block.cache_token == ("red",) + tuple(
        fc.cache_token for fc in case.calls)
    assert np.array_equal(block.wends, case.wends)
    # the first window holds one sample: no present series, NaN
    assert np.isnan(got[:, 0]).all() and not np.isnan(got[:, 1:]).any()
    assert _rel(got, case.host(q)) <= TOL
    assert _rel(got, case.oracle(q)) <= TOL


@pytest.mark.parametrize("q", [0.5, 0.99])
@pytest.mark.parametrize("grouping", ["ungrouped", "by-ns"])
def test_ragged_rows_merge_by_the_kernels_own_counts(grouping, q):
    """Rows with missed scrapes run the ragged kernel, whose present counts
    ride its second output: the epilogue masks and merges by them (the
    bucket-0 slot's count is the series'), as `_present_hist` and the fold
    do on the host.  A (group, window) no series of any shard is present
    in is NaN on both paths (`_rel` holds the NaN cells to be the same
    cells)."""
    case = Case(grouping, GEOMETRIC, holes=True)
    got, want = np.asarray(case.device(q).values), case.host(q)
    assert _rel(got, want) <= TOL
    absent = np.isnan(got[:, 1:])
    assert absent[0].any() and not absent.all(axis=1).any()


def test_the_data_holds_what_the_rules_are_for(cases):
    """A rank in the first bucket and in the top one, counts that fall
    from a bucket to the next, a +Inf top bucket that is reached, a group
    a shard lacks: the cases above are not vacuous."""
    case = cases("by-ns", "geometric")
    assert any(len(fc.gkeys) < case.merged for fc in case.calls)
    assert len({tuple(fc.gkeys) for fc in case.calls}) == len(case.calls)
    whole = cases("ungrouped", "geometric")
    cum = whole.truth[0, 1:]
    assert (cum[:, 0] > 0).all()                   # mass in the first
    assert (cum[:, -1] > cum[:, -2]).all()         # and in the top bucket
    fell = cases("by-dc", "geometric").truth[:, 1:]
    assert (np.diff(fell, axis=2) < 0).any()       # the running maximum
    top = cases("ungrouped", "inf-top")
    assert np.all(top.oracle(1.0)[0, 1:] == INF_TOP[-2])
    assert np.all(np.asarray(top.device(1.0).values)[0, 1:] == INF_TOP[-2])
    first = np.asarray(whole.device(0.0).values)[0, 1:]
    assert np.all(first == 0.0)                    # rank 0: from 0


def test_p50_p90_p99_of_one_grouping_run_one_program(cases):
    case = cases("by-dc", "geometric")
    case.device(0.5)
    size = pf._run_hist_quantile._cache_size()
    for q in (0.9, 0.99, 0.25):
        case.device(q)
    assert pf._run_hist_quantile._cache_size() == size


def test_a_warm_epilogue_call_transfers_nothing_from_the_host(cases):
    """inv, perm, `q` and `les` stay on the device after a first call,
    found again by their content: the jit call of a repeated request runs
    under a guard that forbids implicit host-to-device transfers, and
    books no upload; another q is one more put, of the scalar."""
    case = cases("by-ns", "geometric")
    case.device(0.9)
    before = registry.counter("fused_enqueue_uploads").value
    with jax.transfer_guard_host_to_device("disallow"):
        case.device(0.9)
    assert registry.counter("fused_enqueue_uploads").value == before
    case.device(0.875)
    assert registry.counter("fused_enqueue_uploads").value == before + 1


@pytest.mark.parametrize("first", ["ragged-first", "dense-first"])
def test_one_reduce_token_asked_ragged_and_dense(first):
    """The flavor moves under one token: a request served while a column
    had a hole (the ragged kernel, present cells by its counts), then the
    same series once the hole has rolled out of the store (the dense one,
    present cells by the windows' validity), and the other way round.  The
    merge layout is remembered by the token, which names neither flavor,
    and its device copy by its content: both answers are the host path's,
    and the dense one is not NaN."""
    flavors = [True, False] if first == "ragged-first" else [False, True]
    for holes in flavors:
        case = Case("by-ns", GEOMETRIC, holes=holes, tag="rolling")
        got, want = np.asarray(case.device(0.9).values), case.host(0.9)
        assert _rel(got, want) <= TOL
        assert not np.isnan(got[:, 1:]).all(axis=1).any()
        if not holes:
            assert not np.isnan(got[:, 1:]).any()


def test_the_traced_quantile_is_the_numpy_twin_in_f64():
    """`_histogram_quantile_jax` as the epilogue calls it (a traced `q`,
    the buckets moved last) against `_histogram_quantile_np`, same
    precision, random cumulative buckets with flat and falling stretches,
    empty cells, a non-positive first `le` and a +Inf top: equal to a few
    ulps, NaN for NaN."""
    rng = np.random.default_rng(7)
    buckets = np.cumsum(rng.poisson(3.0, size=(6, 9, 40)) *
                        (rng.random((6, 9, 40)) < 0.6), axis=1).astype(float)
    buckets[1, 3:5] -= 2.0                    # falls: the running maximum
    buckets[2] = 0.0                          # empty: NaN
    traced = jax.jit(lambda q, b, les: hist_ops._histogram_quantile_jax(
        q, jax.numpy.moveaxis(b, 1, -1), les))
    for les in (np.append(np.linspace(-1.0, 6.0, 8), np.inf),
                np.linspace(0.5, 4.5, 9)):
        for q in (0.0, 0.3, 0.5, 0.99, 1.0):
            want = hist_ops._histogram_quantile_np(
                q, np.moveaxis(buckets, 1, 2), les)
            got = np.asarray(traced(
                jax.numpy.float64(q), jax.numpy.asarray(buckets),
                jax.numpy.asarray(les)))
            assert np.array_equal(np.isnan(got), np.isnan(want))
            np.testing.assert_allclose(got, want, rtol=1e-12, equal_nan=True)


# -------------------------------------------------------------- the declines


def _declined():
    return {dict(tags).get("reason"): c.value
            for (name, tags), c in list(registry._counters.items())
            if name == "hist_device_quantile_declined"}


def _count(name):
    return registry.counter(name).value


@pytest.fixture(scope="module")
def rig():
    with histrig.environ(FILODB_TPU_FUSED_INTERPRET="1"):
        r = histrig.HistRig(3100001)
        yield r
        r.close()


def _engine(rig):
    return rig.srv.api.engines[rig.cfg["dataset"]]


def _range(rig):
    req = rig.open(0)[0]["params"]
    return float(req["start"]), float(req["step"]), float(req["end"])


def _values(res):
    assert not res.error, res.error
    block, = res.blocks
    return list(block.keys), np.asarray(block.values)


def _ask(rig, promql):
    start, step, end = _range(rig)
    return _engine(rig).query_range(promql, start, step, end)


def _host_answer(rig, promql, monkeypatch):
    """The parent's answer: the same request with the recognition off."""
    with monkeypatch.context() as m:
        m.setattr(exprfuse, "_hist_quantiles", lambda ep, calls: [])
        return _values(_ask(rig, promql))


INNER = "sum by (_ns_)(rate(http_latency[5m]))"


@pytest.mark.parametrize("promql,reason", [
    (f"histogram_quantile(1.5, {INNER})", "quantile"),
    (f"histogram_quantile(-0.1, {INNER})", "quantile"),
    (f"histogram_quantile(scalar(vector(0.9)), {INNER})", "quantile"),
    (f"histogram_max_quantile(0.9, {INNER})", "function"),
], ids=["q-over-1", "q-under-0", "q-not-constant", "max-quantile"])
def test_a_quantile_the_epilogue_turns_away_takes_the_host_path(
        rig, monkeypatch, promql, reason):
    want_keys, want = _host_answer(rig, promql, monkeypatch)
    before, done = _declined(), _count("hist_device_quantiles")
    merges = _count("reduce_merge_calls")
    keys, got = _values(_ask(rig, promql))
    assert keys == want_keys and np.array_equal(got, want, equal_nan=True)
    after = _declined()
    assert after.get(reason, 0) - before.get(reason, 0) == 1
    assert _count("hist_device_quantiles") == done
    assert _count("reduce_merge_calls") == merges + histrig.SHARDS


def test_the_epilogue_answers_the_plain_request_and_the_host_twin_agrees(
        rig, monkeypatch):
    promql = f"histogram_quantile(0.9, {INNER})"
    want_keys, want = _host_answer(rig, promql, monkeypatch)
    done, merges = _count("hist_device_quantiles"), \
        _count("reduce_merge_calls")
    before = _declined()
    keys, got = _values(_ask(rig, promql))
    assert _count("hist_device_quantiles") == done + 1
    assert _count("reduce_merge_calls") == merges
    assert _declined() == before
    assert keys == want_keys
    assert _rel(got, want) <= TOL


def test_a_child_that_is_not_fused_declines(rig, monkeypatch):
    """One leaf's fused preflight gives nothing (the general path): its
    reduce takes the host path, the other leaves still ride one call."""
    from filodb_tpu.query import leafexec
    promql = f"histogram_quantile(0.9, {INNER})"
    real = leafexec.MultiSchemaPartitionsExec._build_fused

    def unfused_on_shard_2(self, data, stats):
        return None if self.shard == 2 else real(self, data, stats)
    monkeypatch.setattr(leafexec.MultiSchemaPartitionsExec, "_build_fused",
                        unfused_on_shard_2)
    want_keys, want = _host_answer(rig, promql, monkeypatch)
    before, done = _declined(), _count("hist_device_quantiles")
    keys, got = _values(_ask(rig, promql))
    assert keys == want_keys and np.array_equal(got, want, equal_nan=True)
    assert _declined().get("child", 0) - before.get("child", 0) == 1
    assert _count("hist_device_quantiles") == done


def test_mixed_bucket_schemes_decline(rig, monkeypatch):
    """One leaf carries another scheme (what `_align_hist_schemes` rebuckets
    for): recognised from the leaves' `bucket_les`, host path."""
    from filodb_tpu.query import leafexec
    promql = f"histogram_quantile(0.9, {INNER})"
    real = leafexec.MultiSchemaPartitionsExec._build_fused

    def other_scheme_on_shard_1(self, data, stats):
        fc = real(self, data, stats)
        if self.shard == 1 and isinstance(fc, fusedbatch.FusedCall):
            fc.bucket_les = np.asarray(fc.bucket_les) * 1.5
        return fc
    monkeypatch.setattr(leafexec.MultiSchemaPartitionsExec, "_build_fused",
                        other_scheme_on_shard_1)
    want_keys, want = _host_answer(rig, promql, monkeypatch)
    before, done = _declined(), _count("hist_device_quantiles")
    keys, got = _values(_ask(rig, promql))
    assert keys == want_keys and np.array_equal(got, want, equal_nan=True)
    assert _declined().get("scheme", 0) - before.get("scheme", 0) == 1
    assert _count("hist_device_quantiles") == done


def test_children_on_another_dispatch_decline(rig, monkeypatch):
    """Two of the four leaves hold a plan object of their own (what two
    devices, or two grids, come to): two device calls, so no call holds
    the reduce's children and the fold stays on the host."""
    from filodb_tpu.query import leafexec
    promql = f"histogram_quantile(0.9, {INNER})"
    real = leafexec.MultiSchemaPartitionsExec._build_fused
    twins = {}

    def own_plan_on_odd_shards(self, data, stats):
        fc = real(self, data, stats)
        if self.shard % 2 and isinstance(fc, fusedbatch.FusedCall):
            fc.plan = twins.setdefault(
                id(fc.plan), fc.plan._replace(resident={}))
        return fc
    monkeypatch.setattr(leafexec.MultiSchemaPartitionsExec, "_build_fused",
                        own_plan_on_odd_shards)
    want_keys, want = _host_answer(rig, promql, monkeypatch)
    before, done = _declined(), _count("hist_device_quantiles")
    calls = _count("fused_enqueues")
    keys, got = _values(_ask(rig, promql))
    assert _count("fused_enqueues") == calls + 2
    assert keys == want_keys and np.array_equal(got, want, equal_nan=True)
    assert _declined().get("dispatch", 0) - before.get("dispatch", 0) == 1
    assert _count("hist_device_quantiles") == done


def test_quantiles_that_share_their_leaves_decline(rig, monkeypatch):
    """p90 / p50 of one grouping in ONE request: the second quantile's
    leaves are the first's (fusedbatch's dedup computes them once), so no
    call holds one reduce's children alone: both on the host."""
    promql = (f"histogram_quantile(0.9, {INNER}) / "
              f"histogram_quantile(0.5, {INNER})")
    want_keys, want = _host_answer(rig, promql, monkeypatch)
    before, done = _declined(), _count("hist_device_quantiles")
    keys, got = _values(_ask(rig, promql))
    assert keys == want_keys and np.array_equal(got, want, equal_nan=True)
    assert _declined().get("dispatch", 0) - before.get("dispatch", 0) == 2
    assert _count("hist_device_quantiles") == done


def test_a_batch_keeps_the_host_path(rig, monkeypatch):
    """`query_range_batch`: its panels share working sets and calls (p50 and
    p90 of one grouping are ONE set there), so every histogram quantile of
    a batch is the host's, booked `batch`."""
    start, step, end = _range(rig)
    promqls = [f"histogram_quantile({q}, {INNER})" for q in (0.5, 0.9)]
    want = [_host_answer(rig, p, monkeypatch) for p in promqls]
    before, done = _declined(), _count("hist_device_quantiles")
    results = _engine(rig).query_range_batch(promqls, start, step, end)
    for res, (want_keys, want_vals) in zip(results, want):
        keys, got = _values(res)
        assert keys == want_keys
        assert np.array_equal(got, want_vals, equal_nan=True)
    assert _declined().get("batch", 0) - before.get("batch", 0) == 2
    assert _count("hist_device_quantiles") == done


def test_a_cancelled_query_dispatches_nothing(rig):
    """The kill-token contract (PR 13) holds for the epilogue: a query
    cancelled before its leaves' dispatch enqueues no device call, asks
    for no epilogue and surfaces `query_canceled`."""
    from filodb_tpu.promql.parser import (TimeStepParams,
                                          query_range_to_logical_plan)
    from filodb_tpu.query.activequeries import CancellationToken
    eng = _engine(rig)
    start, step, end = _range(rig)
    plan = query_range_to_logical_plan(
        f"histogram_quantile(0.9, {INNER})",
        TimeStepParams(start, step, end))
    real_ctx, real_finish = eng._ctx, exprfuse.finish_prepared
    token = CancellationToken()

    def ctx_with_token(params=None):
        ctx = real_ctx(params)
        ctx.cancel = token
        return ctx

    def cancel_then_finish(calls, quantiles=()):
        assert quantiles, "the tree was not recognised"
        token.cancel("test")
        return real_finish(calls, quantiles)
    enq, asked = _count("fused_enqueues"), \
        _count("span_leaf_hist_epilogue_calls")
    with pytest.MonkeyPatch.context() as m:
        m.setattr(eng, "_ctx", ctx_with_token)
        m.setattr(exprfuse, "finish_prepared", cancel_then_finish)
        res = eng.exec_logical_plan(plan)
    assert res.error and res.error.startswith("query_canceled"), res.error
    assert _count("fused_enqueues") == enq
    assert _count("span_leaf_hist_epilogue_calls") == asked
