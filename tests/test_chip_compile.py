"""Compile rehearsal for the attached chip: the kernels of the served query
path, at the sizes chip_smoke.py runs them, handed to the installed TPU
compiler for a v5e that is DESCRIBED, not attached.

Nothing executes here — a passing compile says the chip's compiler accepts
the program (tiling, scoped VMEM, HBM fit), not that it is right or fast.
The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and every xdist worker imports
every test file), and the compiles run in this process.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from filodb_tpu.ops import pallas_fused as pf

HBM_BYTES = 16 * 1000 ** 3          # one v5e chip
# the shape of the six hour-long cells (720 samples a series; chip_smoke.py's
# 110 windows, the cells ask 61: one tile of 128 either way) ...
T, W, STEP_MS, RANGE_MS = 720, 110, 10_000, 300_000
# ... and of promperf6h-counters-82k.open (ISSUE 44): six hours at a panel's
# own resolution, 2,304 samples a series under 721 windows of `[40s]`
T_6H, W_6H, RANGE_6H_MS = 2_304, 721, 40_000
S_FLAGSHIP, S_SHARD = 1_048_576, 262_144
S_6H = (24_600, 24_400, 16_400, 16_500)     # 81,920 series on four shards


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_runtime():
    """The runtime the chip has, around one compile: x64 off (conftest turns
    it on for the CPU conformance math; the served path on a TPU is f32 and
    Mosaic lowers no 64-bit types), and the persistent cache off (a compile
    for a described device is written to it but cannot be read back without
    a chip)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _plan(range_ms=RANGE_MS, windows=W, samples=T, every_ms=None):
    ts_row = np.arange(samples, dtype=np.int64) * STEP_MS
    # the windows spread over the row (a minute apart at the hour-long
    # cells' 720 x 110, 30 s at the six-hour cell's 2,304 x 721), so that
    # they reach it and the row block is the row (plan.Tq == plan.Tp);
    # `every_ms`: a request's own step, which may reach less
    every = every_ms or max(samples // windows, 1) * STEP_MS
    wends = ts_row[-1] - np.arange(windows, dtype=np.int64)[::-1] * every
    return pf.build_plan(ts_row, wends, range_ms)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _compile_run(one_chip, S, G, fn, ragged=False, panels=1, phased=False,
                 range_ms=RANGE_MS, steps=None, windows=W, samples=T,
                 every_ms=None):
    """Lower + compile pallas_fused._run exactly as a FusedDispatch calls
    it (interpret=False).  `S` and `G` may be tuples: one working set
    each, all in the one program.  `phased`: working sets on a phase
    grid, each with its [Sp, 1] phase column, over the plan's 16 rows.
    `range_ms`: the windows' width, from which the ragged rate family's
    fills take their reach, which must come out as `steps`."""
    plan = _plan(range_ms, windows, samples, every_ms)
    # precorrected (no drop correction in the kernel), as the mirror serves
    flags = pf._flavor(plan, fn, True, False, ragged, phased)
    Ss = S if isinstance(S, tuple) else (S,)
    Gs = G if isinstance(G, tuple) else (G,) * len(Ss)
    assert steps is None or flags.steps == steps
    with_ts = ragged and flags.kind == "rate_family"
    sets = tuple(
        (_sds((pf.pad_series_count(s), plan.Tp), jnp.float32, one_chip),
         _sds((pf.pad_series_count(s), 1), jnp.float32, one_chip),
         (_sds((pf.pad_series_count(s), 1), jnp.int32, one_chip),) * panels)
        + ((_sds((pf.pad_series_count(s), 1), jnp.float32, one_chip),)
           if phased else ())
        for s in Ss)
    args = [sets,
            _sds((panels * len(Ss),), jnp.int32, one_chip) if panels > 1
            else None,
            _sds((plan.prows if phased else plan.rows).shape, jnp.float32,
                 one_chip),
            _sds(plan.tsrow.shape, jnp.float32, one_chip) if with_ts
            else None]
    return pf._run.lower(
        *args, num_groups=tuple(pf.pad_group_count(g * panels) for g in Gs),
        **flags._asdict()).compile()


def _check(compiled, pallas: bool):
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
             + ma.output_size_in_bytes)
    print(f"args={ma.argument_size_in_bytes} temp={ma.temp_size_in_bytes} "
          f"out={ma.output_size_in_bytes}")
    assert total < HBM_BYTES, f"{total} bytes do not fit one v5e chip"
    if pallas:
        assert "tpu_custom_call" in compiled.as_text()
    return ma


@pytest.mark.parametrize("S,G,fn,ragged,panels", [
    (S_FLAGSHIP, 1000, "rate", False, 1),
    (S_SHARD, 1000, "rate", False, 1),
    (S_SHARD, 1000, "rate", True, 1),
    (S_SHARD, 1000, "delta", True, 1),
    (S_SHARD, 1000, "sum_over_time", False, 1),
    (S_SHARD, 1000, "sum_over_time", True, 1),
    (S_SHARD, 1000, "avg_over_time", False, 1),
    (S_SHARD, 8192, "rate", False, 1),
    (S_SHARD, 10, "rate", False, 3),        # multi-panel: 3 gid columns
    # histdev-64b-4k's largest shard: 1,235 series x 64 buckets are kernel
    # rows, 10 groups x 64 buckets are (group, bucket) slots
    (1_235 * 64, 10 * 64, "rate", False, 1),
    # one request's working sets in ONE program (ISSUE 36): the 4-shard
    # cells' four, mixed group counts; two sets of three panels each
    ((79_042, 78_244, 52_281, 52_577), (10, 10, 1, 20), "rate", False, 1),
    ((79_042, 52_281), 10, "sum_over_time", True, 3),
], ids=["rate-1M", "rate-262k", "rate-ragged", "delta-ragged", "sum_ot",
        "sum_ot-ragged", "avg_ot", "rate-G8192", "rate-3panels",
        "rate-hist64", "rate-4sets", "sum_ot-ragged-2sets-3panels"])
def test_fused_kernel_compiles_for_v5e(one_chip, chip_runtime,
                                       S, G, fn, ragged, panels):
    compiled = _compile_run(one_chip, S, G, fn, ragged, panels)
    ma = _check(compiled, pallas=True)
    if isinstance(S, tuple):
        # every set keeps its own Pallas call inside the one program
        assert compiled.as_text().count("custom_call_target=\"tpu_custom_call\"") \
            == len(S)
    if S == S_FLAGSHIP:
        # the two [Sp, 1] column operands (vbase_p, gids_p) tile to 1 KiB
        # per row: recorded, not repaired here (ISSUE 24)
        assert ma.temp_size_in_bytes >= pf.pad_series_count(S) * 1024


@pytest.mark.parametrize("S,G,fn,ragged,panels", [
    # promscrape-counters-262k.open's one program a request: four working
    # sets, each row at the base row plus its target's scrape offset
    ((79_042, 78_244, 52_281, 52_577), (10, 10, 1, 20), "rate", False, 1),
    (S_SHARD, 1000, "rate", True, 1),
    (S_SHARD, 1000, "sum_over_time", False, 1),
    (S_SHARD, 1000, "avg_over_time", True, 1),
    (S_SHARD, 1000, "count_over_time", False, 3),
    (S_SHARD, 1000, "last_over_time", False, 1),
    (S_SHARD, 1000, "last_over_time", True, 1),
    # promchurn-counters-262k.open's one program a request (ISSUE 42): the
    # four shards' placed working sets (290,975 rows: the series that came
    # and went beside the live ones), NaN where a row holds no sample
    ((87_700, 86_800, 58_100, 58_400), (10, 10, 1, 20), "rate", True, 1),
    ((87_700, 86_800, 58_100, 58_400), (10, 10, 1, 20), "increase", True, 1),
], ids=["rate-4sets", "rate-ragged", "sum_ot", "avg_ot-ragged",
        "count_ot-3panels", "last_ot", "last_ot-ragged",
        "rate-ragged-4sets", "increase-ragged-4sets"])
def test_phased_kernel_compiles_for_v5e(one_chip, chip_runtime,
                                        S, G, fn, ragged, panels):
    """The phased variant (rows on a phase grid: a slot a row, by one
    compare of its phase with the window's slack): the gathers take an
    index a (row, window), which Mosaic must lower as it lowers the shared
    index; sums and present counts come back as a pair."""
    compiled = _compile_run(one_chip, S, G, fn, ragged, panels, phased=True)
    _check(compiled, pallas=True)
    n_sets = len(S) if isinstance(S, tuple) else 1
    assert compiled.as_text().count(
        "custom_call_target=\"tpu_custom_call\"") == n_sets


CHURN_ROWS = (72_744,) * 4      # 4 sets of 73,728 x 768 on the row ladder


@pytest.mark.parametrize("steps,range_ms", [(5, RANGE_MS), (10, 7_000_000)],
                         ids=["5steps", "10steps"])
@pytest.mark.parametrize("S,G,fn,phased", [
    (S_SHARD, 1000, "rate", False),
    (S_SHARD, 1000, "delta", False),
    (CHURN_ROWS, (10, 10, 1, 20), "rate", True),
], ids=["rate-ragged", "delta-ragged", "rate-ragged-phased-4sets"])
def test_ragged_rate_fills_compile_at_a_windows_reach_and_the_rows(
        one_chip, chip_runtime, S, G, fn, phased, steps, range_ms):
    """The ragged rate family's fills at the reach of `[5m]` windows
    (5 doubling steps, what promchurn-counters-262k.open runs) and at the
    reach of the row (10: a range past 512 slots), whose f32 NaN-carrying
    shifts and selects and the one band product Mosaic must lower
    (interpret mode has hidden a lowering failure of this branch before)."""
    compiled = _compile_run(one_chip, S, G, fn, True, phased=phased,
                            range_ms=range_ms, steps=steps)
    _check(compiled, pallas=True)
    assert compiled.as_text().count(
        "custom_call_target=\"tpu_custom_call\"") \
        == (len(S) if isinstance(S, tuple) else 1)


@pytest.mark.parametrize("phased,Gp", [(True, 24), (False, 24),
                                       (True, 1000), (False, 1000)])
def test_the_vmem_estimate_covers_mosaics_scoped_allocation(
        one_chip, chip_runtime, monkeypatch, phased, Gp):
    """`vmem_estimate`'s bound for the ragged rate family against what
    Mosaic really takes at the block `pick_block` gives: a limit far too
    small makes the compiler name the kernel's scoped allocation (its
    temporaries; the pipeline's double-buffered blocks it allocates
    apart), and the estimate must hold that plus the two buffers each of
    the values and of the band, and stay inside the budget.  Printed
    (-s): 5.2 MiB phased, 3.9 on one shared row at 128 rows, where three
    carriers over ten steps took 6.8 and 5.9."""
    import re

    from jax.experimental.pallas import tpu as pltpu
    plan = _plan()
    Wp = plan.t1.shape[1]
    bs = pf.pick_block(plan.Tp, Wp, pf.pad_group_count(Gp), "rate_family",
                       True, phased=phased)
    # 128 rows at the cell's few groups; 1,000 groups on a phase grid
    # leave room for 64 beside the band's two buffers
    assert bs == (64 if phased and Gp == 1000 else 128)
    jax.clear_caches()      # a trace kept from another test holds no limit
    real = pf.pl.pallas_call
    monkeypatch.setattr(
        pf.pl, "pallas_call", lambda *a, **k: real(
            *a, compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=2 << 20), **k))
    with pytest.raises(Exception) as err:       # noqa: PT011 — XLA's own
        _compile_run(one_chip, 72_744, Gp, "rate", True, phased=phased)
    jax.clear_caches()      # ... and this one's must not outlive it
    said = re.search(r"Scoped allocation with size ([0-9.]+)([MK])",
                     str(err.value))
    if said is None:
        pytest.skip("the compiler did not name its scoped allocation")
    scoped = float(said.group(1)) * (1 << 20 if said.group(2) == "M"
                                     else 1 << 10)
    values = 2 * bs * plan.Tp * 4
    band = 2 * plan.Tp * Wp * 4
    estimate = pf.vmem_estimate(plan.Tp, Wp, pf.pad_group_count(Gp),
                                "rate_family", True, bs=bs, phased=phased)
    print(f"bs={bs} scoped={scoped / 2 ** 20:.2f}M values={values / 2 ** 20:.2f}M "
          f"band={band / 2 ** 20:.2f}M estimate={estimate / 2 ** 20:.2f}M")
    assert scoped + values + band <= estimate <= pf.VMEM_BUDGET


W_OPEN = 61         # the cells' own request: an hour at a window a minute


@pytest.mark.parametrize("S,G,fn,ragged,phased,samples,windows,range_ms,Tq", [
    # promchurn-counters-262k.open's one program a request since ISSUE 46:
    # a kernel instance computes over the 512 columns an hour of `[5m]`
    # reaches (391 slots) from a column the launch computes, out of 640
    # loaded from the tile edge below it
    (CHURN_ROWS, (10, 10, 1, 20), "rate", True, True, T, W_OPEN, RANGE_MS,
     512),
    (CHURN_ROWS, (10, 10, 1, 20), "increase", True, True, T, W_OPEN,
     RANGE_MS, 512),
    # ... its dense twin's, the unphased cells', the gauges' band kinds,
    # the histogram cell's largest shard, ragged rows on one shared row
    ((79_042, 78_244, 52_281, 52_577), (10, 10, 1, 20), "rate", False, True,
     T, W_OPEN, RANGE_MS, 512),
    ((79_042, 78_244, 52_281, 52_577), (10, 10, 1, 20), "rate", False, False,
     T, W_OPEN, RANGE_MS, 512),
    ((79_042, 78_244, 52_281, 52_577), (10, 10, 1, 20), "sum_over_time",
     False, False, T, W_OPEN, RANGE_MS, 512),
    (S_SHARD, 1000, "avg_over_time", True, True, T, W_OPEN, RANGE_MS, 512),
    (1_235 * 64, 10 * 64, "rate", False, False, T, W_OPEN, RANGE_MS, 512),
    (S_SHARD, 1000, "rate", True, False, T, W_OPEN, RANGE_MS, 512),
    (S_SHARD, 1000, "last_over_time", True, True, T, W_OPEN, RANGE_MS, 512),
    (S_SHARD, 1000, "last_over_time", False, False, T, W_OPEN, RANGE_MS,
     512),
    # a shorter dashboard: 30 minutes reach 211 slots, two tiles
    (CHURN_ROWS, (10, 10, 1, 20), "rate", True, True, T, 31, RANGE_MS, 256),
    # the six-hour dashboard reaches 2,165 of 2,304 columns: the row, the
    # program it had (promperf6h-counters-82k.open)
    (S_6H, (10, 10, 1, 20), "rate", False, False, T_6H, W_6H, RANGE_6H_MS,
     T_6H),
    # ... and two hours of it at the same resolution 768 columns, under
    # two window tiles: the looped gather over the TURNED block, parked
    # (dense rows) or computed from (ragged ones)
    (S_6H, (10, 10, 1, 20), "rate", False, False, T_6H, 241, RANGE_6H_MS,
     768),
    (S_6H, (10, 10, 1, 20), "rate", False, True, T_6H, 241, RANGE_6H_MS,
     768),
    (S_6H, (10, 10, 1, 20), "last_over_time", False, False, T_6H, 241,
     RANGE_6H_MS, 768),
    (S_6H, (10, 10, 1, 20), "rate", True, True, T_6H, 241, RANGE_6H_MS, 768),
], ids=["churn-rate", "churn-increase", "scrape-rate", "rate-4sets", "sum_ot",
        "avg_ot-ragged-phased", "rate-hist64", "rate-ragged",
        "last_ot-ragged-phased", "last_ot", "churn-rate-30min", "rate-6h",
        "rate-6h-2h", "rate-6h-2h-phased", "last_ot-6h-2h",
        "rate-6h-2h-ragged-phased"])
def test_a_row_block_at_the_windows_reach_compiles_for_v5e(
        one_chip, chip_runtime, S, G, fn, ragged, phased, samples, windows,
        range_ms, Tq):
    """A launch whose kernel instances load a block of a row from a tile
    only the launch knows (ISSUE 46: placed by element offsets off a
    prefetched scalar) and turn it by an amount only the launch knows (a
    dynamic lane rotate, then a static slice): Mosaic must take both, at
    every flavor the cells run; a block no wider than the row's gets no
    more series than the row's did at the cells' shape (more rows would
    regroup the f32 group sums)."""
    # the requests' own steps: a minute over the hour-long rows, 30 s over
    # the six-hour ones
    every = 30_000 if samples == T_6H else 60_000
    plan = _plan(range_ms, windows, samples, every)
    assert (plan.Tq, plan.Tp) == (Tq, pf._pad_to(samples, 128))
    kind = fn if fn in pf.OVER_TIME_FNS else "rate_family"
    Gp = pf.pad_group_count(max(G) if isinstance(G, tuple) else G)
    Wp = plan.t1.shape[1]
    rows = [pf.pick_block(t, Wp, Gp, kind, ragged, phased=phased)
            for t in (pf._load_cols(Tq, plan.Tp), plan.Tp)]
    # at the cells' 640 of 768 columns loaded no flavor's block grows; half
    # the row's columns and fewer leave the ragged rate family room for 256
    assert rows[0] == rows[1] or (Tq * 2 <= plan.Tp and rows[0] > rows[1])
    compiled = _compile_run(one_chip, S, G, fn, ragged, phased=phased,
                            range_ms=range_ms, windows=windows,
                            samples=samples, every_ms=every)
    _check(compiled, pallas=True)
    assert compiled.as_text().count(
        "custom_call_target=\"tpu_custom_call\"") \
        == (len(S) if isinstance(S, tuple) else 1)


# promchurn-counters-262k.open's four working sets as they are stored since
# ISSUE 50: (rows that fill every slot, rows with a hole) a shard, 221,000 of
# 290,975 whole
CHURN_PARTS = ((55_600, 17_320), (55_400, 17_480), (55_250, 17_700),
               (54_750, 17_475))


@pytest.mark.parametrize("fn,phased", [
    ("rate", True), ("increase", True), ("rate", False),
    ("sum_over_time", True), ("avg_over_time", False)],
    ids=["churn-rate", "churn-increase", "rate-one-row", "sum_ot-phased",
         "avg_ot-one-row"])
def test_a_launch_of_sets_stored_whole_rows_first_compiles_for_v5e(
        one_chip, chip_runtime, fn, phased):
    """One program a request, two Mosaic kernels a working set (ISSUE 50):
    the dense body over the blocks of the rows that fill every slot, the
    ragged body over the rest, each part a range of blocks of the one array
    (a block index offset; under a trimmed plan an element offset beside the
    prefetched tile), each with its own flavor's block; on one shared row
    the dense part's counts are a scatter beside the kernels."""
    plan = _plan(RANGE_MS, W_OPEN, T, 60_000)
    assert plan.Tq == 512
    flags = pf._flavor(plan, fn, True, False, True, phased)
    splits = tuple(pf.pad_series_count(w) for w, _ in CHURN_PARTS)
    rows = tuple(sw + pf.pad_series_count(h)
                 for sw, (_, h) in zip(splits, CHURN_PARTS))
    assert rows[0] == 57_344 + 18_432
    sets = tuple(
        (_sds((r, plan.Tp), jnp.float32, one_chip),
         _sds((r, 1), jnp.float32, one_chip),
         (_sds((r, 1), jnp.int32, one_chip),))
        + ((_sds((r, 1), jnp.float32, one_chip),) if phased else ())
        for r in rows)
    compiled = pf._run.lower(
        sets, None,
        _sds((plan.prows if phased else plan.rows).shape, jnp.float32,
             one_chip),
        _sds(plan.tsrow.shape, jnp.float32, one_chip)
        if flags.kind == "rate_family" else None,
        num_groups=tuple(pf.pad_group_count(g) for g in (10, 10, 1, 20)),
        splits=splits, **flags._asdict()).compile()
    _check(compiled, pallas=True)
    assert compiled.as_text().count(
        "custom_call_target=\"tpu_custom_call\"") == 2 * len(rows)
    # each part its own flavor's block: the dense phased body takes the
    # block the dense twin's program takes
    kind = flags.kind
    (_, dense, _), (_, own, _) = pf._part_forms(
        sets[:1], (24,), splits[:1], flags.Tq, plan.Tp, 128, kind, True,
        phased, False)
    assert own == 512
    assert dense == (512 if phased or kind != "rate_family" else plan.Tp)
    assert pf.pick_block(pf._load_cols(dense, plan.Tp), 128, 24, kind,
                         False, phased=phased) == 256
    assert pf.pick_block(640, 128, 24, kind, True, phased=phased) \
        == (128 if kind == "rate_family" else 256)


@pytest.mark.parametrize("fn,ragged,phased,windows", [
    ("rate", True, True, W_OPEN), ("rate", True, False, W_OPEN),
    ("increase", True, True, W_OPEN), ("rate", True, True, 31)],
    ids=["churn", "rate-ragged", "churn-increase", "churn-30min"])
def test_the_vmem_estimate_covers_mosaic_at_the_windows_reach(
        one_chip, chip_runtime, monkeypatch, fn, ragged, phased, windows):
    """`vmem_estimate` at the columns a trimmed program LOADS (`_run_set`
    sizes its block by them) against what Mosaic takes for that program
    of the ragged rate family, the flavor the estimate is fitted to (the
    pipeline's block buffers, then the kernel's temporaries): it still
    covers both, inside the budget, at the block `pick_block` gives: 128
    rows for the churned cell's flavor at 640 columns loaded as at 768
    (256 rows at 640 are estimated at 16.3 MiB)."""
    plan = _plan(RANGE_MS, windows, T, 60_000)
    load = pf._load_cols(plan.Tq, plan.Tp)
    Wp, Gp = plan.t1.shape[1], pf.pad_group_count(20)
    kind = fn if fn in pf.OVER_TIME_FNS else "rate_family"
    bs = pf.pick_block(load, Wp, Gp, kind, ragged, phased=phased)
    if windows == W_OPEN:
        assert (plan.Tq, load, bs) == (512, 640, 128)
    run = dict(S=CHURN_ROWS, G=(10, 10, 1, 20), fn=fn, ragged=ragged,
               phased=phased, windows=windows, every_ms=60_000)
    buffers = _scoped_bytes(one_chip, monkeypatch, 256 << 10, **run)
    temps = _scoped_bytes(one_chip, monkeypatch, buffers + (128 << 10),
                          **run)
    estimate = pf.vmem_estimate(load, Wp, Gp, kind, ragged, bs=bs,
                                phased=phased)
    print(f"Tq={plan.Tq} load={load} bs={bs} buffers={buffers / 2 ** 20:.2f}M "
          f"temps={temps / 2 ** 20:.2f}M estimate={estimate / 2 ** 20:.2f}M")
    assert buffers >= 2 * bs * load * 4
    assert buffers + temps <= estimate <= pf.VMEM_BUDGET


@pytest.mark.parametrize("fn,ragged", [
    ("rate", True), ("sum_over_time", True), ("avg_over_time", False),
    ("count_over_time", True)])
def test_phased_band_corrections_compile_past_128_windows(
        one_chip, chip_runtime, fn, ragged):
    """200 windows (Wp 256): the two gathered corrections of a band
    product take a row's own [BS, Wp] slots.  By the shared [1, Wp] row
    Mosaic refused the phased over_time kinds there ("Invalid input
    layout": a [1, 128] i32 slice at a lane offset broadcast down the
    sublanes), as it refused the UNPHASED gather kinds' `_gather_cols`
    until ISSUE 44 (which loads the tile off the ref)."""
    compiled = _compile_run(one_chip, 8_192, 16, fn, ragged, phased=True,
                            windows=200)
    _check(compiled, pallas=True)


def _scoped_bytes(one_chip, monkeypatch, limit, **run):
    """What Mosaic names when a kernel's scoped VMEM passes `limit`: the
    first of its allocations that does (the pipeline's block buffers, then
    the kernel's temporaries and scratch); 0 when it compiles under it."""
    import re

    from jax.experimental.pallas import tpu as pltpu
    real = pf.pl.pallas_call
    jax.clear_caches()      # a trace kept from another test holds no limit
    with monkeypatch.context() as m:
        m.setattr(pf.pl, "pallas_call", lambda *a, **k: real(
            *a, compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=int(limit)), **k))
        try:
            _compile_run(one_chip, **run)
            return 0
        except Exception as err:        # noqa: BLE001 - XLA's own
            said = re.search(r"Scoped allocation with size ([0-9.]+)([MK])",
                             str(err))
            if said is None:
                pytest.skip("the compiler did not name its scoped "
                            f"allocation: {str(err)[:200]}")
            return float(said.group(1)) * (1 << 20 if said.group(2) == "M"
                                           else 1 << 10)
        finally:
            jax.clear_caches()      # ... and this one's must not outlive it


# tsbscpu-gauges-40k.double-groupby (ISSUE 48): 13 h 9 min of a 10 s
# interval under 13 windows of an hour, a shard's hosts a group each
T_13H, W_13H, RANGE_1H_MS = 4_736, 13, 3_600_000
S_TSBS = (1_013, 1_006, 987, 994)           # 4,000 hosts on four shards


@pytest.mark.parametrize("S,G,fn,ragged,phased,samples,windows,range_ms", [
    (S_TSBS, S_TSBS, "avg_over_time", False, False, T_13H, W_13H,
     RANGE_1H_MS),
    (4_000, 4_000, "sum_over_time", False, False, T_13H, W_13H, RANGE_1H_MS),
    (1_000, 1_000, "avg_over_time", True, True, T_13H, W_13H, RANGE_1H_MS),
    (S_6H, (10, 10, 1, 20), "avg_over_time", False, False, T_6H, W_6H,
     RANGE_MS),
], ids=["tsbs-4sets-G1000", "tsbs-G4000", "tsbs-ragged-phased",
        "gauges-6h-Wp768"])
def test_the_tiled_band_compiles_inside_its_estimate(
        one_chip, chip_runtime, monkeypatch, S, G, fn, ragged, phased,
        samples, windows, range_ms):
    """The over_time kinds where the resident band fits no block (five
    [4736, 128] matrices are 12.1 MB, five [2304, 768] 35 MB: `pick_block`
    was None and the leaf took the general XLA path): `band_form` sizes the
    block by the tiled band, the program of a request's working sets
    compiles for the described v5e, and compiles again under a scoped limit
    SET AT `vmem_estimate` for the largest group count, so Mosaic's own
    need (block buffers, accumulators, the tile loop's temporaries) lies
    under the estimate, and the estimate inside the budget.  (Mosaic's
    need at the cell's shape, bs 128, Gp 1,024: between 5.70 and 6.0 MiB
    against 8.62 estimated.)"""
    every = 3_600_000 if samples == T_13H else None
    plan = _plan(range_ms, windows, samples, every)
    Wp = plan.t1.shape[1]
    load = pf._load_cols(plan.Tq, plan.Tp)
    Gp = pf.pad_group_count(max(G) if isinstance(G, tuple) else G)
    bs, tiled = pf.band_form(load, Wp, Gp, fn, ragged, phased=phased)
    assert tiled and bs is not None
    assert pf.vmem_estimate(load, Wp, Gp, fn, ragged, bs=32,
                            phased=phased) > pf.VMEM_BUDGET
    run = dict(S=S, G=G, fn=fn, ragged=ragged, phased=phased,
               range_ms=range_ms, windows=windows, samples=samples,
               every_ms=every)
    compiled = _compile_run(one_chip, **run)
    _check(compiled, pallas=True)
    assert compiled.as_text().count(
        "custom_call_target=\"tpu_custom_call\"") \
        == (len(S) if isinstance(S, tuple) else 1)
    estimate = pf.vmem_estimate(load, Wp, Gp, fn, ragged, bs=bs,
                                phased=phased, tiled=True)
    assert estimate <= pf.VMEM_BUDGET
    over = _scoped_bytes(one_chip, monkeypatch, estimate, **run)
    print(f"Tq={plan.Tq} Wp={Wp} Gp={Gp} bs={bs} "
          f"estimate={estimate / 2 ** 20:.2f}M over={over / 2 ** 20:.2f}M")
    assert over == 0, f"Mosaic names {over} bytes over the estimate"


WIDE_KINDS = [("rate", False, False), ("increase", False, False),
              ("delta", False, False), ("last_over_time", False, False),
              ("rate", True, False), ("rate", False, True)]


@pytest.mark.parametrize("samples,windows", [
    (T, 200), (T, W_6H), (T_6H, 200), (T_6H, W_6H)],
    ids=["Tp768-Wp256", "Tp768-Wp768", "Tp2304-Wp256", "Tp2304-Wp768"])
@pytest.mark.parametrize("fn,ragged,phased", WIDE_KINDS, ids=[
    "rate", "increase", "delta", "last_ot", "rate-ragged", "rate-phased"])
def test_gather_kinds_compile_past_one_window_tile_inside_the_estimate(
        one_chip, chip_runtime, monkeypatch, fn, ragged, phased, samples,
        windows):
    """Every kind whose boundaries `_gather_cols` selects, past 128 windows
    (where the shared `[1, Wp]` index did not lower before ISSUE 44) and at
    a Grafana dashboard's shape, Tp 2,304 x Wp 768: the program of a
    request's four working sets compiles for the described v5e, and
    Mosaic's own scoped allocations (the block buffers, then the
    temporaries with the gathers' scratch) lie under `vmem_estimate` at
    the block `pick_block` gives, inside the budget.  Where no block fits
    (the ragged rate family's `[Tp, Wp]` band lies in VMEM twice: 14 MB at
    2,304 x 768) `_run` declines by name before lowering, which is where
    leafexec's guard diverts."""
    plan = _plan(RANGE_6H_MS, windows, samples)
    Wp, Gp = plan.t1.shape[1], pf.pad_group_count(20)
    kind = fn if fn in pf.OVER_TIME_FNS else "rate_family"
    bs = pf.pick_block(plan.Tp, Wp, Gp, kind, ragged, phased=phased)
    run = dict(S=S_6H, G=(10, 10, 1, 20), fn=fn, ragged=ragged,
               phased=phased, range_ms=RANGE_6H_MS, windows=windows,
               samples=samples)
    if bs is None:
        assert ragged and plan.Tp * Wp * 8 > pf.VMEM_BUDGET // 2
        with pytest.raises(ValueError, match="exceeds VMEM budget"):
            _compile_run(one_chip, **run)
        return
    compiled = _compile_run(one_chip, **run)
    _check(compiled, pallas=True)
    assert compiled.as_text().count(
        "custom_call_target=\"tpu_custom_call\"") == len(S_6H)
    buffers = _scoped_bytes(one_chip, monkeypatch, 256 << 10, **run)
    temps = _scoped_bytes(one_chip, monkeypatch, buffers + (128 << 10),
                          **run)
    estimate = pf.vmem_estimate(plan.Tp, Wp, Gp, kind, ragged, bs=bs,
                                phased=phased)
    print(f"bs={bs} buffers={buffers / 2 ** 20:.2f}M "
          f"temps={temps / 2 ** 20:.2f}M estimate={estimate / 2 ** 20:.2f}M")
    assert buffers >= 2 * bs * plan.Tp * 4
    assert buffers + temps <= estimate <= pf.VMEM_BUDGET


@pytest.mark.parametrize("fn,ragged,phased", [
    ("rate", True, True), ("rate", False, False), ("delta", False, False),
    ("last_over_time", False, False), ("rate", True, False)],
    ids=["rate-ragged-phased", "rate", "delta", "last_ot", "rate-ragged"])
@pytest.mark.parametrize("samples,windows,range_ms,Gp,fits", [
    (1_500, 500, RANGE_MS, 16, True),       # Tp 1536, Wp 512: 32 rows
    (1_500, 500, 20_000_000, 16, True),     # ... fills across the row, 11
    (720, 1_000, RANGE_MS, 16, True),       # Tp 768, Wp 1024
    (1_200, 600, RANGE_MS, 16, True),       # Tp 1280, Wp 640
    (1_500, 500, RANGE_MS, 1_000, False),   # ... and 1,000 groups: no
    (1_500, 1_000, RANGE_MS, 16, False),    # Tp 1536, Wp 1024: 12 MiB of band
    (2_000, 1_000, RANGE_MS, 16, False),    # Tp 2048, Wp 1024: 16 MiB
], ids=["1536x512", "1536x512-11steps", "768x1024", "1280x640",
        "1536x512-G1000", "1536x1024", "2048x1024"])
def test_a_long_range_of_many_windows_compiles_or_diverts(
        one_chip, chip_runtime, samples, windows, range_ms, Gp, fits,
        fn, ragged, phased):
    """The ragged rate family's [Tp, Wp] band lies in VMEM twice: hours
    of samples under a Grafana panel's 500 to 1,000 windows (ragged phased
    rows, promchurn's flavor, and ragged rows on one shared row) either get
    a block from `pick_block` and then compile under the chip's scoped
    limit, or get None, which is where leafexec and the mesh executor
    divert to the general path and `_run` refuses by name.  The dense
    unphased gather kinds (which lowered no more than 128 windows before
    ISSUE 44) hold no band: they fit at every one of these shapes."""
    plan = _plan(range_ms, windows, samples)
    kind = fn if fn in pf.OVER_TIME_FNS else "rate_family"
    fits = fits or not ragged
    bs = pf.pick_block(plan.Tp, plan.t1.shape[1], pf.pad_group_count(Gp),
                       kind, ragged, phased=phased)
    assert (bs is not None) == fits
    run = lambda: _compile_run(                              # noqa: E731
        one_chip, 8_192, Gp, fn, ragged, phased=phased, range_ms=range_ms,
        windows=windows, samples=samples)
    if fits:
        _check(run(), pallas=True)
    else:
        with pytest.raises(ValueError, match="exceeds VMEM budget"):
            run()


def test_histogram_gather_and_flatten_compile_for_v5e(one_chip,
                                                      chip_runtime):
    """What a histogram leaf runs before the kernel, at histdev-64b-4k's
    largest shard: the row gather out of the `[S, T, 64]` mirror and the
    `[rows, T, B] -> [rows*B, T]` step (leafexec's `leaf.hist_flatten`)."""
    S, B = 1_235, 64
    take = jax.jit(lambda a, i: jnp.take(a, i, axis=0)).lower(
        _sds((S, T, B), jnp.float32, one_chip),
        _sds((S,), jnp.int32, one_chip)).compile()
    _check(take, pallas=False)
    flat = jax.jit(lambda a: jnp.moveaxis(a, 2, 1).reshape(S * B, T)).lower(
        _sds((S, T, B), jnp.float32, one_chip)).compile()
    _check(flat, pallas=False)


@pytest.mark.parametrize("groups,merged", [
    ((1, 1, 1, 1), 1), ((2, 2, 2, 2), 2), ((5, 5, 5, 5), 10)],
    ids=["ungrouped", "by-dc", "by-ns"])
def test_the_histogram_quantile_epilogue_compiles_for_v5e(
        one_chip, chip_runtime, groups, merged):
    """`_run_hist_quantile` as a FusedDispatch calls it for
    histdev-64b-4k.quantiles (ISSUE 51): the four shards' working sets of
    series x 64 bucket rows, (group, bucket) slots, an hour at a window a
    minute, and behind the four kernels the bucket merge and the quantile
    in the same program, at the cell's three merged group counts: the
    kernels under Mosaic's scoped limit as in `_run`, the epilogue's XLA
    ops accepted, one [Gm_p, Wp] f32 block out."""
    B, rows = 64, (1_235, 1_223, 817, 821)
    plan = _plan(RANGE_MS, W_OPEN, T, 60_000)
    flags = pf._flavor(plan, "rate", True, False, False, False)
    Sp = [pf.pad_series_count(s * B) for s in rows]
    gps = tuple(pf.pad_group_count(g * B) for g in groups)
    Gm_p, Wp = pf.pad_group_count(merged), plan.t1.shape[1]
    sets = tuple((_sds((sp, plan.Tp), jnp.float32, one_chip),
                  _sds((sp, 1), jnp.float32, one_chip),
                  (_sds((sp, 1), jnp.int32, one_chip),)) for sp in Sp)
    compiled = pf._run_hist_quantile.lower(
        sets, None, _sds(plan.rows.shape, jnp.float32, one_chip), None,
        _sds((4, Gm_p), jnp.int32, one_chip),
        _sds((4,), jnp.int32, one_chip), _sds((), jnp.float32, one_chip),
        _sds((B,), jnp.float32, one_chip), num_groups=gps,
        **flags._asdict()).compile()
    _check(compiled, pallas=True)
    assert compiled.as_text().count(
        "custom_call_target=\"tpu_custom_call\"") == 4
    out, = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (Gm_p, Wp) and out.dtype == jnp.float32


@pytest.mark.parametrize("fn", ["rate", "sum_over_time"])
def test_general_xla_leaf_compiles_for_v5e(one_chip, chip_runtime,
                                           fn):
    """The route a non-uniform shard takes: per-series timestamps through
    ops/rangefns + the group aggregate, at one smoke shard's shape."""
    from filodb_tpu.ops import agg as agg_ops
    from filodb_tpu.ops.rangefns import _evaluate_range_function

    def leaf(ts_off, v, vb, g, w):
        res = _evaluate_range_function(
            ts_off, v, w, RANGE_MS, 0.0, vb, fn, (), False,
            fn in ("rate", "increase"), True)
        return agg_ops.map_phase("sum", res, g, 16)

    S = S_SHARD
    compiled = jax.jit(leaf).lower(
        _sds((S, T + 1), jnp.int32, one_chip),
        _sds((S, T + 1), jnp.float32, one_chip),
        _sds((S,), jnp.float32, one_chip),
        _sds((S,), jnp.int32, one_chip),
        _sds((61,), jnp.int32, one_chip)).compile()
    _check(compiled, pallas=False)


@pytest.mark.parametrize("fn,ragged", [("min_over_time", False),
                                       ("max_over_time", True)])
def test_minmax_reduce_window_compiles_for_v5e(one_chip, chip_runtime,
                                               fn, ragged):
    S = S_SHARD
    compiled = pf._fused_minmax_jit.lower(
        _sds((S, T), jnp.float32, one_chip),
        _sds((S,), jnp.float32, one_chip),
        _sds((S,), jnp.int32, one_chip),
        f0=30, stride=6, width=30, W=61, fn_name=fn, agg_op="max",
        num_groups=16, ragged=ragged).compile()
    _check(compiled, pallas=False)


def test_collective_partial_merge_compiles_for_four_chips(topo, chip_runtime):
    """The one program that spans chips: the [G, W] group partials of four
    per-device kernel runs merged by psum over the 'shard' axis
    (parallel/mesh.merge_device_partials' collective branch, which only a
    TPU backend takes)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from filodb_tpu.parallel.mesh import _merge_partials_collective
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("shard", "time"))
    x = jax.ShapeDtypeStruct(
        (4, 16, 1, 128), jnp.float32,
        sharding=NamedSharding(mesh, P("shard", None, "time", None)))
    compiled = _merge_partials_collective.lower(mesh, x, comb="sum").compile()
    assert "all-reduce" in compiled.as_text()
    _check(compiled, pallas=False)
