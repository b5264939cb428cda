"""ISSUE 48: the over_time kinds' band in tiles.

`sum/avg/count_over_time` are one product of the values with the band
band[t, w] = 1{first[w] <= t <= last[w]}.  The resident form holds it whole
(four [Tq, Wp] matrices and their difference: 12.1 MB at a 13-hour row under
one tile of windows); the tiled form (`_band_dot`) makes 512 columns of it at
a time from two compares in the kernel.  `band_form` chooses from the shape
alone: the form that fits the larger series block, the resident one where
both fit the same, so a plan the resident form took whole blocks of keeps
its program (held here against the parent commit's lowered text) and its
answers bit for bit.  Interpret mode on the CPU; the chip's compiler sees
the same programs in tests/test_chip_compile.py."""
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from filodb_tpu.ops import pallas_fused as pf
from filodb_tpu.utils.metrics import registry

STEP = 10_000
HOUR = 3_600_000
T_13H = 4_736       # tsbscpu-gauges-40k: 13 h 9 min 20 s of a 10 s interval


def _tsbs_plan(off_s=17, T=T_13H, windows=13):
    """TSBS double-groupby's grid: 13 window ends an hour apart, `[1h]`,
    the last `off_s` whole seconds before the newest sample."""
    ts = np.arange(T, dtype=np.int64) * STEP
    wends = ts[-1] - off_s * 1000 - np.arange(windows)[::-1] * HOUR
    return ts, wends, pf.build_plan(ts, wends, HOUR)


def _oracle(ts, vals, wends, range_ms, fn, gids, G):
    """f64: per series the sum (or mean) of the samples that are there in
    (wend - range, wend], summed by group over the series that hold one."""
    out = np.zeros((G, len(wends)))
    for w, we in enumerate(wends):
        lo = np.searchsorted(ts, we - range_ms, side="right")
        hi = np.searchsorted(ts, we, side="right")
        seg = vals[:, lo:hi].astype(np.float64)
        n = (~np.isnan(seg)).sum(axis=1)
        x = np.nansum(seg, axis=1)
        if fn == "avg_over_time":
            x = x / np.maximum(n, 1)
        out[:, w] = np.bincount(gids[n > 0], weights=x[n > 0], minlength=G)
    return out


@pytest.mark.parametrize("fn", ["avg_over_time", "sum_over_time"])
@pytest.mark.parametrize("Gp", [8, 24, 64, 256, 1024, 2048, 4096])
def test_a_thirteen_hour_row_gets_a_block_at_every_group_count(fn, Gp):
    """Before this form `pick_block(4736, 128, Gp, ..)` was None at every
    group count (12.92 MiB at the smallest block) and the leaf took the
    general XLA path."""
    bs, tiled = pf.band_form(T_13H, 128, Gp, fn)
    assert tiled and bs == pf.pick_block(T_13H, 128, Gp, fn)
    assert bs == (128 if Gp <= 2048 else 64)
    assert pf.vmem_estimate(T_13H, 128, Gp, fn, bs=bs,
                            tiled=True) <= pf.VMEM_BUDGET
    # the resident form alone: no block
    assert pf.vmem_estimate(T_13H, 128, Gp, fn, bs=32) > pf.VMEM_BUDGET
    # ragged rows and rows on a phase grid too
    assert pf.pick_block(T_13H, 128, Gp, fn, True) is not None
    assert pf.pick_block(T_13H, 128, Gp, fn, phased=True) is not None


@pytest.mark.parametrize("Gp", [8, 24, 1024])
def test_an_hour_long_row_keeps_the_resident_band_and_its_block(Gp):
    """Tp 768 x Wp 128, the gauges cell's plan: 256 rows, the band whole,
    as before the tiled form existed; and the gather kinds have no band."""
    for fn in ("avg_over_time", "sum_over_time", "count_over_time"):
        assert pf.band_form(768, 128, Gp, fn) == (256, False)
        assert pf.band_form(640, 128, Gp, fn) == (256, False)   # trimmed
    assert pf.band_form(T_13H, 128, Gp, "rate_family")[1] is False
    assert pf.band_form(T_13H, 128, Gp, "last_over_time")[1] is False


def _lowered(fn, ragged=False, phased=False):
    """`_run`'s lowered text for one working set of 512 rows at the
    hour-long cells' plan (720 samples, 61 windows of `[5m]` a minute
    apart, 24 groups), kernels interpreted, x64 off as on the chip."""
    ts = np.arange(720, dtype=np.int64) * STEP
    wends = ts[-1] - np.arange(61, dtype=np.int64)[::-1] * 60_000
    plan = pf.build_plan(ts, wends, 300_000)
    flags = pf._flavor(plan, fn, True, True, ragged, phased)
    sds = jax.ShapeDtypeStruct
    st = (sds((512, plan.Tp), jnp.float32), sds((512, 1), jnp.float32),
          (sds((512, 1), jnp.int32),))
    if phased:
        st += (sds((512, 1), jnp.float32),)
    rows = sds((plan.prows if phased else plan.rows).shape, jnp.float32)
    with jax.enable_x64(False):
        return pf._run.lower((st,), None, rows, None, num_groups=(24,),
                             **flags._asdict()).as_text()


@pytest.mark.parametrize("fn,ragged,phased,parent", [
    ("avg_over_time", False, False,
     "eb5b9601ac3711960b6c23526d45f94693d5cebb"),
    ("sum_over_time", False, False,
     "085970513040b8f78885774d9036f206d0170953"),
    ("sum_over_time", True, False,
     "de8efee7435ef9af36f9d6a00dad77cc4f818d9b"),
    ("avg_over_time", False, True,
     "041764e5aa7aaa9fcb2307e22f2288d66019c695"),
    ("rate", False, False, "aeb2d68283867a046ac6fdf077e0eb2a1b3cab4c"),
    # ISSUE 50 (a ragged set stored whole rows first is two calls of
    # `_run_set`): the dense and phased rate cells' flavors, and the ragged
    # launch of a set that is not split, against 50f37bf's
    ("increase", False, False, "ebd8ac90ea09cc13d19783708ce8043d6f45c6e3"),
    ("rate", False, True, "b3ee258a40305844b7e6def20e0edd85c8a23b73"),
    ("increase", False, True, "993bc83c458e9c05e77a5ae8e208ebe01fddafc1"),
    ("rate", True, True, "8d57e7786ee250529babe1929fa9cb27b36aaf1a"),
    ("rate", True, False, "fe0010c99b4bb8bfb263c42dbfc7ea1131076be6"),
    ("sum_over_time", True, True,
     "88b0c7e32daea850fc4704c6073b9a7fcb8015d1"),
], ids=["avg", "sum", "sum-ragged", "avg-phased", "rate", "increase",
        "rate-phased", "increase-phased", "rate-ragged-phased",
        "rate-ragged", "sum-ragged-phased"])
def test_the_hour_long_plan_runs_the_parents_program(fn, ragged, phased,
                                                     parent):
    """The program of a plan the resident form fits is the parent
    commit's, to the character: the lowered text of `_run` at the gauges
    cell's shape hashes to what 80f42bb's did (recorded from a checkout of
    it; the rate cells' from 50f37bf's, which are 80f42bb's too where both
    are listed), so its answers are the parent's bit for bit on any
    machine."""
    text = _lowered(fn, ragged, phased)
    assert hashlib.sha1(text.encode()).hexdigest() == parent


_SHAPES_OWN = pf.band_form.__wrapped__


def _force(monkeypatch, tiled):
    """Every band kind's set under one form, whatever its shape."""
    real = _SHAPES_OWN

    def forced(Tp, Wp, Gp, kind="rate_family", *a, **k):
        bs, _ = real(Tp, Wp, Gp, kind, *a, **k)
        if pf._selects_by_gather(kind):
            return bs, False
        return (bs or 32), tiled
    monkeypatch.setattr(pf, "band_form", forced)
    jax.clear_caches()


def _rows(S, T, ragged, seed=11):
    rng = np.random.default_rng(seed)
    vals = rng.uniform(0, 100, (S, T))
    if ragged:
        vals[rng.random(vals.shape) < 0.05] = np.nan
        vals[2, :T // 2] = np.nan
        vals[5] = np.nan
    return vals


@pytest.mark.parametrize("fn", ["sum_over_time", "avg_over_time",
                                "count_over_time"])
@pytest.mark.parametrize("ragged,phased", [(False, False), (True, False),
                                           (False, True), (True, True)],
                         ids=["dense", "ragged", "phased", "ragged-phased"])
@pytest.mark.parametrize("trimmed", [False, True], ids=["row", "trimmed"])
def test_the_tiled_band_selects_what_the_resident_one_does(
        monkeypatch, fn, ragged, phased, trimmed):
    """Every flavor of the band kinds, forced under either form at a shape
    both fit (1,152 columns: two whole tiles of band and a rest of 128):
    the same windows' samples, so sums within f32's rounding of one
    another (another order of sums) and counts exactly equal; on a trimmed
    plan the tiles are read off the turned block, parked."""
    T, S, G = 1_152, 40, 7
    ts = np.arange(T, dtype=np.int64) * STEP
    last = T - 1 - (260 if trimmed else 3)
    wends = ts[last] + 4_000 \
        - np.arange(20 if trimmed else 37)[::-1] * 300_000
    plan = pf.build_plan(ts, wends, 600_000)
    assert (plan.Tq < plan.Tp) == trimmed
    vals = _rows(S, T, ragged)
    vb = np.nan_to_num(vals[:, 0]).astype(np.float32)
    rebased = (vals - vb[:, None]).astype(np.float32)
    gids = (np.arange(S) % G).astype(np.int32)
    phase = np.random.default_rng(3).integers(1, STEP, S) if phased else None
    if fn == "count_over_time" and not (ragged or phased):
        pytest.skip("a dense count on one shared row is host math")
    outs = {}
    for tiled in (False, True):
        _force(monkeypatch, tiled)
        before = registry.counter("fused_band_tiles").value
        sums, counts = pf.fused_rate_groupsum(
            rebased, vb, gids, plan, G, fn, interpret=True, ragged=ragged,
            phase=phase)
        outs[tiled] = (np.asarray(sums, np.float64), counts)
        booked = registry.counter("fused_band_tiles").value - before
        assert booked == (-(-plan.Tq // pf._BAND_COLS) if tiled else 0)
    monkeypatch.undo()
    jax.clear_caches()
    np.testing.assert_array_equal(outs[True][1], outs[False][1])
    scale = np.abs(outs[False][0]).max() or 1.0
    assert np.abs(outs[True][0] - outs[False][0]).max() <= 2e-6 * scale


@pytest.mark.parametrize("fn", ["avg_over_time", "sum_over_time"])
def test_double_groupby_at_the_published_row_length(fn):
    """A shard's leaf of `tsbscpu-gauges-40k.double-groupby` in small: rows
    of 4,736 samples, 13 windows of an hour, a group a series, through the
    program's entry for one fused leaf, against the f64 oracle; the band in
    ten tiles, and every time of the plan exact in f32 (47,350,000 ms off
    the row's first sample is 8 x 125 x k, k under 2^24)."""
    ts, wends, plan = _tsbs_plan()
    assert (plan.Tp, plan.Tq, plan.W, plan.exact) == (T_13H, T_13H, 13, True)
    assert plan.n1[0, :13].tolist() == [360.0] * 13
    S = 48
    rng = np.random.default_rng(48)
    vals = np.clip(50 + np.cumsum(rng.standard_normal((S, T_13H)), axis=1),
                   0, 100)      # (long stretches at the floor and the roof)
    vb = vals[:, 0].astype(np.float32)
    gids = np.arange(S, dtype=np.int32)
    before = registry.counter("fused_band_tiles").value
    sums, counts = pf.fused_rate_groupsum(
        (vals - vb[:, None]).astype(np.float32), vb, gids, plan, S, fn,
        interpret=True)
    assert registry.counter("fused_band_tiles").value - before == 10
    want = _oracle(ts, vals, wends, HOUR, fn, gids, S)
    # (of the largest cell: a mean near 0 is the difference of a window's
    # rebased sum and the row's base, each of size 50)
    assert np.abs(np.asarray(sums, np.float64) - want).max() \
        <= 5e-6 * np.abs(want).max()
    assert (counts == 1).all()


@pytest.mark.parametrize("off_s", [0, 1, 17, 539, 549])
def test_a_thirteen_hour_plan_is_exact_at_every_phase_of_the_cell(off_s):
    """Window ends whole seconds off the 10 s grid: every time the kernel
    reads is a multiple of 1,000 ms under 2^24 x 8, so f32 holds it and
    `leaf_inexact_times_total` stays 0 in the cell."""
    _, wends, plan = _tsbs_plan(off_s)
    assert plan.exact and plan.Tq == plan.Tp
    assert (plan.n1[0, :13] == 360).all()
    # odd milliseconds that far out are not f32's
    ts = np.arange(T_13H, dtype=np.int64) * STEP
    assert not pf.build_plan(ts, wends + 1, HOUR).exact


def test_gauges_at_grafanas_six_hours_fuse_under_the_tiled_band():
    """ROADMAP B0 (1): `*_over_time` over rows of 2,304 samples under 721
    windows (Tp 2,304 x Wp 768) diverted to the general path for the five
    resident matrices, 35 MB.  The tiled form fits 64 rows a block at a
    dashboard's group counts, and answers as the f64 oracle; past 512 groups
    the accumulators of 768 windows leave no room and the leaf is declined
    by name, as before."""
    T, W = 2_304, 721
    assert pf.band_form(T, 768, 24, "avg_over_time") == (64, True)
    assert pf.band_form(T, 768, 256, "sum_over_time") == (64, True)
    assert pf.pick_block(T, 768, 1024, "avg_over_time") is None
    ts = np.arange(T, dtype=np.int64) * STEP
    wends = ts[-1] - 3_000 - np.arange(W, dtype=np.int64)[::-1] * 30_000
    plan = pf.build_plan(ts, wends, 300_000)
    assert plan.exact and plan.t1.shape[1] == 768
    S, G = 40, 10
    vals = _rows(S, T, False, seed=6)
    vb = vals[:, 0].astype(np.float32)
    gids = (np.arange(S) % G).astype(np.int32)
    sums, counts = pf.fused_rate_groupsum(
        (vals - vb[:, None]).astype(np.float32), vb, gids, plan, G,
        "avg_over_time", interpret=True)
    want = _oracle(ts, vals, wends, 300_000, "avg_over_time", gids, G)
    np.testing.assert_allclose(np.asarray(sums, np.float64), want, rtol=2e-5)
    assert (counts == S // G).all()
    with pytest.raises(ValueError, match="exceeds VMEM budget"):
        jax.eval_shape(lambda v, b, g, r: pf._run(
            ((v, b, (g,)),), None, r, None, num_groups=(1024,),
            **pf._flavor(plan, "avg_over_time", True, False, False)
            ._asdict()),
            jax.ShapeDtypeStruct((256, T), jnp.float32),
            jax.ShapeDtypeStruct((256, 1), jnp.float32),
            jax.ShapeDtypeStruct((256, 1), jnp.int32),
            jax.ShapeDtypeStruct(plan.rows.shape, jnp.float32))
