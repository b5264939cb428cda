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
T, W, STEP_MS, RANGE_MS = 720, 110, 10_000, 300_000
S_FLAGSHIP, S_SHARD = 1_048_576, 262_144


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


def _plan():
    ts_row = np.arange(T, dtype=np.int64) * STEP_MS
    wends = ts_row[-1] - np.arange(W, dtype=np.int64)[::-1] * 60_000
    return pf.build_plan(ts_row, wends, RANGE_MS)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _compile_run(one_chip, S, G, fn, ragged=False, panels=1, phased=False):
    """Lower + compile pallas_fused._run exactly as a FusedDispatch calls
    it (interpret=False).  `S` and `G` may be tuples: one working set
    each, all in the one program.  `phased`: working sets on a phase
    grid, each with its [Sp, 1] phase column, over the plan's 16 rows."""
    plan = _plan()
    # precorrected (no drop correction in the kernel), as the mirror serves
    flags = pf._flavor(fn, True, False, ragged, phased)
    Ss = S if isinstance(S, tuple) else (S,)
    Gs = G if isinstance(G, tuple) else (G,) * len(Ss)
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
