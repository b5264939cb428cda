"""ISSUE 46: a kernel instance loads the columns a plan's windows reach.

A kernel instance computes over `plan.Tq` columns (the slots the windows
reach, in whole 128-lane tiles) from a column `c0` that the launch derives
from the plan's uploaded rows: data, not a compile key, and any column: the
instance loads one tile more from the tile edge below c0 and turns it.
Interpret mode on the CPU; the chip's compiler sees the same programs in
tests/test_chip_compile.py."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from filodb_tpu.ops import pallas_fused as pf
from filodb_tpu.utils.metrics import registry

STEP = 10_000
T = 720                                     # two hours of a 10 s scrape


def _hour_plan(end_slot=T - 1, off_ms=4_000, windows=61, T=T):
    """A dashboard's hour: 61 windows of `[5m]` a minute apart, the last
    ending `off_ms` past slot `end_slot` (off the grid, so a phased row's
    slot is the shared one or the one before it, by its phase)."""
    ts = np.arange(T, dtype=np.int64) * STEP
    wends = ts[end_slot] + off_ms - np.arange(windows)[::-1] * 60_000
    return ts, pf.build_plan(ts, wends, 300_000)


def _rows(S, T, ragged, seed=7):
    rng = np.random.default_rng(seed)
    vals = np.cumsum(rng.integers(1, 50, (S, T)).astype(np.float64), axis=1)
    if ragged:
        vals[rng.random(vals.shape) < 0.08] = np.nan
        for s in range(0, S, 5):            # rows that start late, end early
            a = int(rng.integers(300, 600))
            if s % 2:
                vals[s, :a] = np.nan
            else:
                vals[s, a:] = np.nan
        vals[3] = np.nan
    return vals.astype(np.float32)


def _phases(S, phased, seed=9):
    if not phased:
        return None
    phase = np.random.default_rng(seed).integers(1, STEP, S)
    phase[0], phase[1] = 0, STEP - 1
    return phase


def _whole_row(plan):
    """The same plan with the block forced to the row: c0 0, Tq = Tp."""
    return plan._replace(Tq=plan.Tp, c0=(0, 0), resident={})


def _cols_read():
    return registry.counter("fused_columns_read").value


# (fn, agg, ragged, phased, buckets): every flavor of the kernel
FLAVORS = [
    ("rate", "sum", False, False, 1),
    ("rate", "sum", False, True, 1),
    ("rate", "sum", True, True, 1),
    ("rate", "sum", True, False, 1),
    ("increase", "sum", True, True, 1),
    ("delta", "sum", False, True, 1),
    ("sum_over_time", "sum", False, False, 1),
    ("avg_over_time", "avg", True, True, 1),
    ("count_over_time", "sum", True, False, 1),
    ("sum_over_time", "sum", False, True, 1),
    ("last_over_time", "sum", False, False, 1),
    ("last_over_time", "sum", True, True, 1),
    ("rate", "min", False, False, 1),       # the per-series run
    ("rate", "max", True, True, 1),
    ("rate", "sum", False, False, 16),      # histogram rows: (group, bucket)
]


@pytest.mark.parametrize("fn,agg,ragged,phased,buckets", FLAVORS, ids=[
    "rate-dense", "rate-phased", "rate-ragged-phased", "rate-ragged-shared",
    "increase-ragged-phased", "delta-phased", "sum_ot-band",
    "avg_ot-ragged-phased", "count_ot-ragged", "sum_ot-phased", "last_ot",
    "last_ot-ragged-phased", "rate-min-per-series",
    "rate-max-ragged-phased", "rate-histogram"])
def test_a_trimmed_launch_answers_as_the_whole_row_bit_for_bit(
        fn, agg, ragged, phased, buckets):
    """Windows over the newest hour reach the last 391 of a row's 768
    columns: the launch loads 640 from column 128, computes over the 512
    from column 256 and returns, sums and counts, what the launch over the
    whole row returns, to the bit.  (The dense gather kinds on one shared
    row keep the row, `_flavor`: the last test of this file turns theirs.)"""
    ts, plan = _hour_plan()
    assert (plan.Tp, plan.Tq, plan.c0) == (768, 512, (256, 256))
    kind = fn if fn in pf.OVER_TIME_FNS else "rate_family"
    light = pf._selects_by_gather(kind) and not ragged and not phased
    S = 48
    vals = _rows(S * buckets, T, ragged)
    G = 4
    gids = (np.arange(S) % G).astype(np.int32)
    if buckets > 1:                         # leafexec's (group, bucket) slots
        gids = (gids[:, None] * buckets + np.arange(buckets)).reshape(-1)
        G *= buckets
    vbase = np.full(len(vals), 1000.0, np.float32)
    phase = _phases(len(vals), phased)
    got = []
    for p, cols in ((plan, 768 if light else 640),           # loaded
                    (_whole_row(plan), 768)):
        prepared = pf.pad_inputs(vals, vbase, gids, p, G, phase=phase)
        before = _cols_read()
        comp = pf.fused_leaf_agg(p, prepared, gids, G, fn, agg,
                                 precorrected=True, interpret=True,
                                 ragged=ragged)
        assert _cols_read() - before == cols
        got.append(np.asarray(comp))
    assert got[0].shape == got[1].shape and got[0].shape[:2] == (G, plan.W)
    assert np.array_equal(got[0], got[1], equal_nan=True)
    assert np.isfinite(got[0][..., -1]).all() and got[0][..., -1].any()


@pytest.mark.parametrize("fn,ragged,phased", [
    ("rate", False, False), ("delta", False, True),
    ("last_over_time", False, False), ("rate", True, True),
    ("sum_over_time", False, True), ("last_over_time", True, False)],
    ids=["rate-dense", "delta-phased", "last_ot", "rate-ragged-phased",
         "sum_ot-phased", "last_ot-ragged"])
def test_a_looped_gather_reads_the_turned_block(fn, ragged, phased):
    """300 windows of `[40s]` 20 s apart over 605 of 1,400 slots: three
    window tiles over five row tiles is past the pairs a gather visits
    unrolled, so it loops over tiles of a REF, and the
    values it reads in place on dense rows are the turned block's, parked
    like a computed array (`parked(..., turned=True)`).  The whole row's
    answers, to the bit."""
    T = 1400
    ts = np.arange(T, dtype=np.int64) * STEP
    wends = ts[-51] - 3_000 - np.arange(300)[::-1] * 20_000
    plan = pf.build_plan(ts, wends, 40_000)
    assert (plan.Tp, plan.Tq, plan.c0) == (1408, 640, (746, 746))
    assert pf.gather_loops(plan.Tq, 384) and plan.tile_visits[0] < 15
    kind = fn if fn in pf.OVER_TIME_FNS else "rate_family"
    assert pf.parked(kind, ragged, phased, False, True, True) \
        == pf.parked(kind, ragged, phased, False, True) + (not ragged)
    # a group a row: the ragged rate family gets a larger block of rows at
    # 768 columns loaded than at the row's 1,408 (`pick_block`), and a
    # group of several rows would add them in another order
    S = G = 40
    vals = _rows(S, T, ragged)
    gids = np.arange(S, dtype=np.int32)
    got = [pf.fused_rate_groupsum(vals, np.zeros(S, np.float32), gids, p, G,
                                  fn, precorrected=True, interpret=True,
                                  ragged=ragged, phase=_phases(S, phased))
           for p in (plan, _whole_row(plan))]
    for a, b in zip(*got):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(got[0][1]).any()


@pytest.mark.parametrize("fn,ragged,phased", [
    ("rate", False, False), ("rate", True, True), ("sum_over_time", False,
                                                   False)],
    ids=["rate-dense", "rate-ragged-phased", "sum_ot-band"])
def test_a_plan_that_reaches_the_row_runs_the_rows_program(fn, ragged, phased):
    """Windows over the whole row: Tq == Tp, c0 0, and `_run` traces what
    it traces when no Tq is named at all (the program before a block
    followed the windows): no prefetched scalar, no slice of the
    timestamps, no slot moved."""
    ts = np.arange(T, dtype=np.int64) * STEP
    plan = pf.build_plan(ts, ts[-1] - np.arange(115)[::-1] * 60_000, 300_000)
    assert (plan.Tq, plan.c0) == (plan.Tp, (0, 0))
    flags = pf._flavor(plan, fn, True, True, ragged, phased)
    assert flags.Tq == plan.Tp
    vals = jnp.zeros((256, plan.Tp), jnp.float32)
    st = (vals, vals[:, :1], (jnp.zeros((256, 1), jnp.int32),)) \
        + ((vals[:, :1],) if phased else ())
    rows = plan.prows if phased else plan.rows

    def program(**kw):
        return str(jax.make_jaxpr(lambda r, t: pf._run(
            (st,), None, r, t, num_groups=(8,), **kw))(rows, plan.tsrow))

    named = program(**flags._asdict())
    assert named == program(**flags._replace(Tq=None)._asdict())
    assert "dynamic_slice" not in named
    # ... and a trimmed plan's program does differ, in the block alone
    _, hour = _hour_plan()
    flags = pf._flavor(hour, fn, True, True, ragged, phased)
    trimmed = program(**flags._asdict())
    if flags.Tq == hour.Tp:         # a dense gather kind: the row's program
        assert trimmed == named
        trimmed = program(**flags._replace(Tq=hour.Tq)._asdict())
    assert trimmed != named and "640" in trimmed and "512" in trimmed


@pytest.mark.parametrize("first", [256, 257])
@pytest.mark.parametrize("phased", [False, True], ids=["shared-row",
                                                       "phase-grid"])
def test_a_first_slot_on_a_tile_edge_keeps_the_slot_before_it(first, phased):
    """The earliest window's first slot on a tile's first column (256) or
    its second (257): the columns computed over start AT the slot before
    it, which a row of a phase grid takes for its own first (255, the
    last lane of the tile below, which is then the tile loaded from; 256,
    a tile's first), and the answers are the whole row's."""
    T = 1024
    ts = np.arange(T, dtype=np.int64) * STEP
    # 40 windows of [5m] a minute apart; the first opens just before
    # slot `first` (wstart = ts[first] - 5,999 ms)
    wends = ts[first] - 6_000 + 300_000 + np.arange(40) * 60_000
    plan = pf.build_plan(ts, wends, 300_000)
    assert int(plan.prows[pf._PI1, 0]) == first
    assert plan.Tq == 384 and plan.c0 == (first - 1,) * 2
    assert first + 29 + 39 * 6 < plan.c0[0] + 384
    S, G = 40, 4
    vals = _rows(S, T, ragged=True, seed=first)
    vals[:, :first] = np.where(np.arange(S)[:, None] % 3 == 0, np.nan,
                               vals[:, :first])
    gids = (np.arange(S) % G).astype(np.int32)
    phase = _phases(S, phased)
    got = [pf.fused_rate_groupsum(vals, np.zeros(S, np.float32), gids, p, G,
                                  "rate", precorrected=True, interpret=True,
                                  ragged=True, phase=phase)
           for p in (plan, _whole_row(plan))]
    for a, b in zip(*got):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    if phased:
        # some row did take the slot before the first window's first
        early = phase > plan.prows[pf._PS1, 0]
        assert early.any() and not early.all()


def test_the_block_follows_the_newest_sample_without_a_new_program():
    """A dashboard whose `end` walks along the row (an open a minute
    earlier than the last, as the benchmark's opens; a server whose
    newest column advances): the first slot crosses tile edges, c0 moves
    with it, Tq and with it the launch's signature stay."""
    sigs, c0s = set(), set()
    for end in range(T - 1, T - 250, -5):
        for off in (0, 5_000):
            _, plan = _hour_plan(end, off)
            flags = pf._flavor(plan, "rate", True, True, True, True)
            sigs.add(pf._run_shape_sig(
                ((np.zeros((256, plan.Tp)),),), plan, (8,), flags.kind, True,
                True, flags.steps, flags.Tq))
            c0s.add(plan.c0)
            lo = int(plan.prows[pf._PI1, :plan.W].min()) - 1
            hi = int(plan.prows[pf._PI2, :plan.W].max())
            assert plan.c0[1] <= lo and hi < plan.c0[1] + plan.Tq
            assert plan.c0[1] == min(lo, plan.Tp - plan.Tq)
    assert len(sigs) == 1 and "xT512x" in sigs.pop()
    # ... at every place in a tile
    assert len({c[1] % 128 for c in c0s}) > 30


def test_a_kernel_that_corrects_resets_itself_takes_the_row():
    """`with_drops` (a counter whose rows are not corrected before the
    kernel): the corrections up to a slot are the row's prefix sum, so
    the flavor keeps the whole row whatever the windows reach."""
    _, plan = _hour_plan()
    assert pf._flavor(plan, "rate", False, True, False).Tq == plan.Tp
    assert pf._flavor(plan, "rate", True, True, False, True).Tq == 512
    assert pf._flavor(plan, "delta", False, True, True).Tq == 512
    vals = _rows(24, T, False)
    vals[::3, 500:] *= 0.5                  # resets inside the windows
    gids = (np.arange(24) % 3).astype(np.int32)
    before = _cols_read()
    sums, counts = pf.fused_rate_groupsum(
        vals, np.zeros(24, np.float32), gids, plan, 3, "rate",
        precorrected=False, interpret=True)
    assert _cols_read() - before == 768
    assert np.isfinite(np.asarray(sums)).all() and (np.asarray(sums) > 0).all()


@pytest.mark.parametrize("kind,ragged,phased", [
    ("rate_family", False, False), ("rate_family", True, True),
    ("sum_over_time", False, False)])
def test_a_narrower_block_never_gets_a_smaller_series_block(kind, ragged,
                                                            phased):
    """`pick_block` and `vmem_estimate` see Tq: the estimate falls with
    the block's width, so a shape that fit at the row's width fits at the
    plan's.  The ragged phased rate family keeps 128 rows at 640 and at
    512 columns as at 768 (256 do not fit), so its f32 group sums are
    grouped as they were."""
    for Tq in (512, 640, 768):
        assert pf.vmem_estimate(Tq, 128, 24, kind, ragged, phased=phased) \
            <= pf.vmem_estimate(768, 128, 24, kind, ragged, phased=phased)
        assert pf.pick_block(Tq, 128, 24, kind, ragged, phased=phased) \
            == pf.pick_block(768, 128, 24, kind, ragged, phased=phased)


@pytest.mark.parametrize("fn", ["rate", "delta", "last_over_time"])
def test_the_dense_gather_kinds_keep_the_row_and_turn_when_asked(fn):
    """Dense rows on one shared row under a gather kind: the flavor's Tq
    is the row's (the turn costs that kernel more than the tiles give:
    `_flavor`), so their launch is the program it was.  The kernel itself
    turns their block like any other's when a caller names a Tq: the
    row's answers, to the bit."""
    ts, plan = _hour_plan()
    flags = pf._flavor(plan, fn, True, True, False)
    assert flags.Tq == plan.Tp > plan.Tq
    S, G = 40, 4
    vals = _rows(S, T, False)
    gids = (np.arange(S) % G).astype(np.int32)
    prepared = pf.pad_inputs(vals, np.zeros(S, np.float32), gids, plan, G)
    got = [np.asarray(pf.run_kernel(
        prepared.vals_p, prepared.vbase_p, prepared.gids_p, plan.rows,
        num_groups=8, **flags._replace(Tq=Tq)._asdict()))
        for Tq in (plan.Tq, plan.Tp, None)]
    assert np.array_equal(got[0], got[1]) and np.array_equal(got[1], got[2])
    assert got[0][:G, :plan.W].any()
