"""Distributed query execution over a JAX device mesh.

This is the TPU-native replacement for the reference's distributed exec tree:
where FiloDB dispatches serialized ExecPlan subtrees to shard-owner nodes via
Akka and tree-reduces partial aggregates through ReduceAggregateExec
(ref: query/.../exec/PlanDispatcher.scala:20-57, exec/AggrOverRangeVectors.scala
:51-123, doc/query-engine.md:90-155), we lay the per-shard dense series arrays
out on a device mesh and let XLA collectives do the reduce:

  mesh axes:  ('shard', 'time')
    - 'shard': data parallelism over series — each device (or device column)
      owns the series of one FiloDB shard, the moral equivalent of
      1 shard = 1 node (ref: doc/sharding.md:23-56).
    - 'time':  sequence parallelism over the *output window grid* — each
      device row computes a contiguous slice of the PromQL step grid, the
      TPU analogue of the planner's time-range splitting + StitchRvsExec
      (ref: SingleClusterPlanner.scala:91-117).

  collectives: the 3-phase aggregate contract (map/reduce/present,
  doc/query-engine.md:311-330) maps onto shard_map as
      map_phase on-device per shard  ->  psum/pmin/pmax over the 'shard'
      axis (ICI)  ->  present host-side,
  so cross-shard aggregation rides ICI instead of Kryo-over-TCP.

All shapes are static under jit: shards are padded to a uniform
[series_per_shard, time] block and padded rows carry NaN values, which the
map phase masks out (same trick the single-shard path uses for ragged data).
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from filodb_tpu.ops import agg as agg_ops
from filodb_tpu.ops.rangefns import evaluate_range_function
from filodb_tpu.ops.timewindow import PAD_TS
from filodb_tpu.utils.jaxcompat import has_ici, shard_map


# --------------------------------------------------------------------- mesh

def make_mesh(n_shard: int, n_time: int = 1,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a ('shard', 'time') mesh from the first n_shard*n_time devices.

    Devices beyond n_shard*n_time are left out of the mesh; that
    truncation used to be silent — an operator sizing a pod for 8-way
    scaling with a 6-shard dataset would quietly idle 2 chips.  The
    unused count is logged once and the chosen shape exposed as gauges
    (`mesh_shard_axis` / `mesh_time_axis` / `mesh_unused_devices`)."""
    from filodb_tpu.utils.metrics import log_error_once, registry
    # local devices, like the mirror placer: per-device dispatch commits
    # operands to each mesh device, which this process must address
    devs = list(devices if devices is not None else jax.local_devices())
    need = n_shard * n_time
    if len(devs) < need:
        raise ValueError(f"need {need} devices, have {len(devs)}")
    if len(devs) > need:
        log_error_once(
            "mesh_unused_devices",
            RuntimeWarning(
                f"mesh ({n_shard} shard x {n_time} time) uses {need} of "
                f"{len(devs)} devices; {len(devs) - need} idle — resize "
                f"the mesh axes to cover the pod"))
    registry.gauge("mesh_shard_axis").update(n_shard)
    registry.gauge("mesh_time_axis").update(n_time)
    registry.gauge("mesh_unused_devices").update(len(devs) - need)
    grid = np.array(devs[:need]).reshape(n_shard, n_time)
    return Mesh(grid, ("shard", "time"))


# ---------------------------------------------------------------- packing

@dataclasses.dataclass
class PackedShards:
    """Host-side uniform pack of per-shard series blocks.

    ts_off  [D, S, T] int32 window-offset timestamps (PAD_TS past each row)
    values  [D, S, T] float  (NaN for padded rows)
    group_ids [D, S] int32   global aggregation-group slot per series row
    num_groups               static group count (for segment reductions)
    group_labels             slot -> label dict (for presenting results)
    base_ms                  common timestamp base
    n_series                 true (unpadded) series count per shard
    """
    ts_off: np.ndarray
    values: np.ndarray
    group_ids: np.ndarray
    num_groups: int
    group_labels: List[Dict[str, str]]
    base_ms: int
    n_series: np.ndarray
    # per-series value base subtracted host-side in f64 (ops/counter.
    # rebase_values) so counter deltas survive the f32 device downcast —
    # same contract as the single-shard leaf path (RawBlock.vbase)
    vbase: Optional[np.ndarray] = None      # [D, S]
    precorrected: bool = False
    # fused-kernel eligibility (ops/pallas_fused.py): when every real row
    # of every shard shares ONE scrape grid, the shared row (int32 [T],
    # PAD_TS tail) — else None.  Computed at pack time; `dense` qualifies
    # whether values are hole-free (dense kernel) or NaN-holed (ragged
    # kernel variant).
    shared_ts_row: Optional[np.ndarray] = None
    # series per aggregation group over REAL rows (for present-count math)
    gsize: Optional[np.ndarray] = None
    # False when any counted cell is non-finite: the rate family then runs
    # its valid-boundary variant (staleness markers are absent samples).
    # Computed ONCE at pack time on the HOST arrays (packs are cached, so
    # the boolean scan amortizes; post-device_put the values are sharded
    # device arrays a lazy scan would have to transfer back).
    dense: bool = True
    # host-side per-shard pid arrays in pack-row order (None for empty
    # shards): lets run_agg_batch recompute OTHER groupings over the SAME
    # rows without re-gathering (the mesh analogue of the leaf path's
    # PaddedValues/PaddedGroups split)
    pids_by_shard: Optional[List[np.ndarray]] = None
    # host-side views of the packed arrays, kept on backends without an
    # MXU (device_put_packed): the per-device dispatcher's host fused
    # route (ops/hostleaf) reads these instead of pulling device copies
    # back per query.  None on TPU — there the kernel path serves.
    host_values: Optional[np.ndarray] = None
    host_vbase: Optional[np.ndarray] = None
    host_group_ids: Optional[np.ndarray] = None

    @property
    def n_shards(self) -> int:
        return self.ts_off.shape[0]


class GroupRegistry:
    """Global aggregation-group slot assignment shared across shards (and
    across queries, when cached by MeshExecutor): group key -> stable slot.
    Group identity follows by/without label semantics (ref:
    exec/AggrOverRangeVectors.scala AggregateMapReduce grouping)."""

    def __init__(self, by: Sequence[str] = (), without: Sequence[str] = ()):
        self.by = frozenset(by) if by else None
        self.drop = (set(without) | {"_metric_", "__name__"}) if without else None
        self.slot_of: Dict[Tuple[Tuple[str, str], ...], int] = {}
        self.labels: List[Dict[str, str]] = []

    def slot_for(self, items: Tuple[Tuple[str, str], ...]) -> int:
        """items: the series' sorted (label, value) tuple."""
        if self.by is not None:
            key = tuple((k, v) for k, v in items if k in self.by)
        elif self.drop is not None:
            key = tuple((k, v) for k, v in items if k not in self.drop)
        else:
            key = ()
        slot = self.slot_of.get(key)
        if slot is None:
            slot = len(self.labels)
            self.slot_of[key] = slot
            self.labels.append(dict(key))
        return slot


def pack_shards(blocks: Sequence[Tuple],
                by: Sequence[str] = (), without: Sequence[str] = (),
                base_ms: int = 0,
                pad_series_to: Optional[int] = None,
                pad_time_to: Optional[int] = None,
                precorrected: bool = False,
                group_labels: Optional[List[Dict[str, str]]] = None
                ) -> PackedShards:
    """Pack per-shard (ts_off [S,T], vals [S,T], series label dicts[,
    vbase [S]]) into the uniform [D, S, T] layout, assigning
    globally-consistent group slots.

    Group identity follows the reference's by/without label semantics
    (ref: exec/AggrOverRangeVectors.scala AggregateMapReduce grouping):
    group key = labels restricted to `by` (or all minus `without`).

    Each block's third element is either a per-series label sequence
    (dicts or sorted (k, v) tuples) grouped here, or a precomputed int32
    gid array already compacted to [0, len(group_labels)) — the cached
    fast path that avoids per-series Python work entirely (see
    MeshExecutor._gids_for, which also does the per-query compaction).
    """
    D = len(blocks)
    S = pad_series_to or max((b[0].shape[0] for b in blocks), default=1)
    T = pad_time_to or max((b[0].shape[1] for b in blocks), default=1)
    S, T = max(S, 1), max(T, 1)

    reg = GroupRegistry(by, without)

    ts = np.full((D, S, T), PAD_TS, dtype=np.int32)
    vals = np.full((D, S, T), np.nan, dtype=np.float64)
    gids = np.zeros((D, S), dtype=np.int32)
    nser = np.zeros(D, dtype=np.int32)
    vbase = np.zeros((D, S), dtype=np.float64)
    any_vbase = False

    for d, blk in enumerate(blocks):
        t, v, labels = blk[0], blk[1], blk[2]
        if len(blk) > 3 and blk[3] is not None:
            vbase[d, :len(blk[3])] = blk[3]
            any_vbase = True
        s, tt = t.shape
        ts[d, :s, :tt] = t
        vals[d, :s, :tt] = v
        # real series = labeled rows; empty-shard placeholder blocks carry
        # a single all-PAD row with NO labels — that row is padding, not
        # data (it must not count toward group sizes or grid uniformity)
        if isinstance(labels, np.ndarray):
            nser[d] = labels.shape[0]
            gids[d, :labels.shape[0]] = labels
        else:
            nser[d] = min(s, len(labels))
            for i, lab in enumerate(labels):
                items = (lab if isinstance(lab, tuple)
                         else tuple(sorted(lab.items())))
                gids[d, i] = reg.slot_for(items)

    labels_out = group_labels if group_labels is not None else list(reg.labels)
    num_groups = max(len(labels_out), 1)
    # fused-kernel eligibility: one shared grid across every real row.
    # Per-shard views with early exit — no [N, T] fancy-index copies (packs
    # run for every query shape, most of which can't fuse anyway).
    shared_row = None
    ref = None
    for d in range(D):
        n = nser[d]
        if n == 0:
            continue
        if ref is None:
            ref = ts[d, 0]
        rows = ts[d, :n]
        if not (rows == ref[None, :]).all():
            ref = None
            break
    if ref is not None:
        shared_row = ref.copy()
    gsize = np.zeros(num_groups, dtype=np.int64)
    for d in range(D):
        if nser[d]:
            gsize += np.bincount(gids[d, :nser[d]],
                                 minlength=num_groups)[:num_groups]
    # dense = every counted cell finite.  Tracked SEPARATELY from grid
    # sharing (r4): a uniform-grid pack with NaN holes keeps its
    # shared_ts_row and runs the RAGGED fused kernel variant.  isfinite,
    # not isnan: an inf sample would be clamped by the dense kernel
    # wrapper's nan_to_num and silently change query results.
    dense = all(
        nser[d] == 0
        or bool((np.isfinite(vals[d, :nser[d]])
                 | (ts[d, :nser[d]] >= PAD_TS)).all())
        for d in range(D))
    return PackedShards(ts, vals, gids, num_groups,
                        labels_out, base_ms, nser,
                        vbase=vbase if any_vbase else None,
                        precorrected=precorrected,
                        shared_ts_row=shared_row, gsize=gsize,
                        dense=dense)


def device_put_packed(packed: PackedShards, mesh: Mesh) -> PackedShards:
    """Place packed arrays on the mesh: series data sharded over 'shard',
    replicated over 'time' (each time-row needs the full series to evaluate
    any window slice — windows reach back `range` into the data)."""
    data_spec = NamedSharding(mesh, P("shard", None, None))
    gid_spec = NamedSharding(mesh, P("shard", None))
    # host-side views feed only the host fused route, which serves dense
    # packs exclusively — keeping them for ragged packs would hold a
    # full extra [D, S, T] copy per cache entry that nothing ever reads
    keep_host = jax.default_backend() != "tpu" and packed.dense
    return dataclasses.replace(
        packed,
        ts_off=jax.device_put(packed.ts_off, data_spec),
        values=jax.device_put(packed.values, data_spec),
        group_ids=jax.device_put(packed.group_ids, gid_spec),
        vbase=(None if packed.vbase is None
               else jax.device_put(packed.vbase, gid_spec)),
        host_values=(np.asarray(packed.values) if keep_host else None),
        host_vbase=(np.asarray(packed.vbase)
                    if keep_host and packed.vbase is not None else None),
        host_group_ids=(np.asarray(packed.group_ids)
                        if keep_host else None))


# ------------------------------------------------ per-device fused dispatch
#
# The multi-chip fused scan.  The kernel is never traced under shard_map:
# there it and its grid loop are re-traced and scheduled per mesh program
# instead of dispatched as the single-chip binary, which measured ~30x
# slower than the general path on an 8-device mesh (25.3 s against 0.88 s
# warm; PERF.md section 7, "Before the chip benchmark").  Instead device
# (s, t) runs the SINGLE-CHIP kernel over its committed [S, T] shard block
# with time-slice t's plan, and only the [G, Wl] group partials cross
# chips — one tiny psum collective on ICI, a host-side
# ops/agg.reduce_phase merge otherwise.  That is exactly the reference's
# 3-phase map/reduce/present contract (doc/query-engine.md :311-330) with
# the map phase on-chip and the reduce over partials only.

@functools.partial(jax.jit, static_argnames=(
    "G", "S", "T", "Tp", "is_counter", "is_rate", "interpret", "kind",
    "ragged", "steps"))
def _device_fused_call(values, group_ids, vbase, rows, tsrow, *, G: int,
                       S: int, T: int, Tp: int, is_counter: bool,
                       is_rate: bool, interpret: bool,
                       kind: str = "rate_family", ragged: bool = False,
                       steps: Optional[int] = None):
    """One device's share of the multi-chip fused scan: pad this device's
    [1, S, T] values + [1, S, P] grouping (P > 1: run_agg_batch panels
    over disjoint group-id ranges, multi-hot kernel epilogue) to kernel
    tile shapes and run the single-chip Pallas kernel over the plan's
    uploaded (rows, tsrow).  Every operand is committed to the owning
    device, so the jit executes THERE (device-pinned dispatch) and only
    the [G, Wlp] group partials leave the chip.  The leading shard axis
    is kept so the pack's addressable shards feed straight in.

    Dense packs: NaN cells are exactly pad rows / beyond-count columns,
    zeroed they contribute nothing (pack pad rows carry gid 0 but add +0
    to its sums).  Ragged packs keep their NaNs — the kernel's fill
    scans treat them as absent samples; pad rows become all-NaN rows
    whose presence is 0.  `steps` is the time slice's plan's
    pf.scan_steps (left out: the fills cross the row, run_kernel).
    with_drops is always False here: counter functions require a
    precorrected pack."""
    from filodb_tpu.ops import pallas_fused as pf
    Gp = pf.pad_group_count(G)
    Sp = pf.pad_series_count(S)
    v = values[0].astype(jnp.float32)
    if ragged:
        v = jnp.pad(v, ((0, Sp - S), (0, Tp - T)), constant_values=np.nan)
    else:
        v = jnp.pad(jnp.nan_to_num(v), ((0, Sp - S), (0, Tp - T)))
    vb = jnp.pad(vbase[0].astype(jnp.float32), (0, Sp - S))[:, None]
    g = jnp.pad(group_ids[0].astype(jnp.int32), ((0, Sp - S), (0, 0)),
                constant_values=-1)
    res = pf.run_kernel(v, vb, g, rows, tsrow, num_groups=Gp,
                        is_counter=is_counter, is_rate=is_rate,
                        with_drops=False, interpret=interpret, kind=kind,
                        ragged=ragged, steps=steps)
    if ragged:
        return res[0][:G], res[1][:G]
    return res[:G]


@functools.partial(jax.jit, static_argnames=("mesh", "comb"))
def _merge_partials_collective(mesh: Mesh, x, *, comb: str = "sum"):
    """The cross-chip reduce of the 3-phase contract as ONE tiny jitted
    collective over group partials [D, G, n_time, Wlp] (psum/pmin/pmax
    over 'shard'; the [S, T] series blocks never ride a collective)."""
    def step(blk):
        p = blk[0]
        if comb == "sum":
            return jax.lax.psum(p, "shard")
        return (jax.lax.pmin if comb == "min" else jax.lax.pmax)(p, "shard")
    return shard_map(step, mesh=mesh,
                     in_specs=P("shard", None, "time", None),
                     out_specs=P(None, "time", None))(x)


def merge_device_partials(parts: Dict[Tuple[int, int], jax.Array],
                          mesh: Mesh, comb: str = "sum",
                          collective: Optional[bool] = None) -> np.ndarray:
    """Merge per-device [G, Wlp] partials -> [G, n_time * Wlp] float64.

    parts[(s, t)] is mesh device (s, t)'s partial (shard s, time-slice
    t).  With ICI (TPU backend) the merge is one jitted collective over
    the partials only; host platforms emulate collectives through host
    memory anyway, so there the partials come host-side in one
    device_get and merge with ops/agg.reduce_phase combiner semantics in
    ascending shard order — deterministic, and bit-stable across runs."""
    n_shard, n_time = mesh.shape["shard"], mesh.shape["time"]
    G, Wlp = parts[(0, 0)].shape
    if collective is None:
        collective = has_ici()
    if collective and n_shard > 1:
        pieces = [jnp.reshape(parts[(s, t)], (1, G, 1, Wlp))
                  for s in range(n_shard) for t in range(n_time)]
        sh = NamedSharding(mesh, P("shard", None, "time", None))
        glob = jax.make_array_from_single_device_arrays(
            (n_shard, G, n_time, Wlp), sh, pieces)
        from filodb_tpu.utils.metrics import registry
        registry.counter("mesh_partials_collective_merge").increment()
        out = np.asarray(_merge_partials_collective(mesh, glob, comb=comb),
                         dtype=np.float64)
        return out.reshape(G, n_time * Wlp)
    ordered = [parts[(s, t)] for t in range(n_time)
               for s in range(n_shard)]
    host = [np.asarray(a, np.float64) for a in jax.device_get(ordered)]
    from filodb_tpu.utils.metrics import registry
    registry.counter("mesh_partials_host_merge").increment()
    cols = []
    for t in range(n_time):
        acc = host[t * n_shard]
        for s in range(1, n_shard):
            nxt = host[t * n_shard + s]
            if comb == "sum":
                acc = acc + nxt
            elif comb == "min":
                acc = np.minimum(acc, nxt)
            else:
                acc = np.maximum(acc, nxt)
        cols.append(acc)
    return np.concatenate(cols, axis=1)


def distributed_window_agg(mesh: Mesh, ts_off, values, group_ids, wends, *,
                           range_ms, fn_name, params=(), agg_op="sum",
                           num_groups=1, base_ms=0, vbase=None,
                           precorrected=False, dense=True):
    """Eager wrapper: floats base_ms before the jit boundary (epoch-ms ints
    overflow int32 canonicalization on no-x64 TPU; see rangefns)."""
    if vbase is None:
        vbase = jnp.zeros(values.shape[:2], values.dtype)
    return _distributed_window_agg(mesh, ts_off, values, group_ids, wends,
                                   vbase,
                                   range_ms=range_ms, fn_name=fn_name,
                                   params=params, agg_op=agg_op,
                                   num_groups=num_groups,
                                   base_ms=float(base_ms),
                                   precorrected=precorrected, dense=dense)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "fn_name", "params", "agg_op", "num_groups",
                     "precorrected", "dense"))
def _distributed_window_agg(mesh: Mesh,
                           ts_off: jax.Array, values: jax.Array,
                           group_ids: jax.Array, wends: jax.Array,
                           vbase: jax.Array,
                           *, range_ms: int, fn_name: Optional[str],
                           params: Tuple[float, ...] = (),
                           agg_op: str = "sum", num_groups: int = 1,
                           base_ms: int = 0,
                           precorrected: bool = False,
                           dense: bool = True) -> jax.Array:
    """Full distributed query step: windowed range function + cross-shard
    aggregate, SPMD over the ('shard', 'time') mesh.

    ts_off/values [D, S, T] sharded over 'shard'; wends [W] sharded over
    'time'.  Returns partial components [G, W, C] (replicated over 'shard',
    sharded over 'time') — call agg_ops.present() to finish.
    """
    def _collective(comb, x):
        if comb == "sum":
            return jax.lax.psum(x, "shard")
        return (jax.lax.pmin if comb == "min" else jax.lax.pmax)(x, "shard")

    def step(ts_blk, val_blk, gid_blk, wends_blk, vbase_blk):
        # ts_blk [1, S, T] — this device column's shard; wends_blk [W/nt]
        res = evaluate_range_function(ts_blk[0], val_blk[0], wends_blk,
                                      range_ms, fn_name, params, base_ms,
                                      vbase=vbase_blk[0],
                                      precorrected=precorrected,
                                      dense=dense)
        part = agg_ops.map_phase(agg_op, res, gid_blk[0], num_groups)
        combs = agg_ops.combiners_for(agg_op, part.shape[-1])
        if len(set(combs)) == 1:
            return _collective(combs[0], part)
        return jnp.stack([_collective(c, part[..., i])
                          for i, c in enumerate(combs)], axis=-1)

    return shard_map(
        step, mesh=mesh,
        in_specs=(P("shard", None, None), P("shard", None, None),
                  P("shard", None), P("time"), P("shard", None)),
        out_specs=P(None, "time", None))(ts_off, values, group_ids, wends,
                                         vbase)


def distributed_window_raw(mesh: Mesh, ts_off, values, wends, *, range_ms,
                           fn_name, params=(), base_ms=0, vbase=None,
                           precorrected=False, dense=True):
    """Eager wrapper: floats base_ms (see distributed_window_agg)."""
    if vbase is None:
        vbase = jnp.zeros(values.shape[:2], values.dtype)
    return _distributed_window_raw(mesh, ts_off, values, wends, vbase,
                                   range_ms=range_ms, fn_name=fn_name,
                                   params=params, base_ms=float(base_ms),
                                   precorrected=precorrected, dense=dense)


@functools.partial(
    jax.jit, static_argnames=("mesh", "fn_name", "params", "precorrected",
                              "dense"))
def _distributed_window_raw(mesh: Mesh,
                           ts_off: jax.Array, values: jax.Array,
                           wends: jax.Array, vbase: jax.Array,
                           *, range_ms: int,
                           fn_name: Optional[str],
                           params: Tuple[float, ...] = (),
                           base_ms: int = 0,
                           precorrected: bool = False,
                           dense: bool = True) -> jax.Array:
    """Un-aggregated distributed evaluation -> [D, S, W] (the DistConcatExec
    analogue: per-shard results stay sharded; host gathers lazily)."""

    def step(ts_blk, val_blk, wends_blk, vbase_blk):
        res = evaluate_range_function(ts_blk[0], val_blk[0], wends_blk,
                                      range_ms, fn_name, params, base_ms,
                                      vbase=vbase_blk[0],
                                      precorrected=precorrected,
                                      dense=dense)
        return res[None]

    return shard_map(
        step, mesh=mesh,
        in_specs=(P("shard", None, None), P("shard", None, None), P("time"),
                  P("shard", None)),
        out_specs=P("shard", None, "time"))(ts_off, values, wends, vbase)


def _host_counts(gsize: np.ndarray, wvalid: np.ndarray) -> np.ndarray:
    """Dense-pack present counts: every REAL series emits a value exactly
    where the shared window is valid — counts[g, w] = |group g| * valid[w].
    The single home of the formula for both the kernel-route epilogue and
    the dense count panels (_finish_count_panels)."""
    return gsize[:, None] * wvalid[None, :].astype(np.float64)


# ----------------------------------------------------------- executor glue

class MeshExecutor:
    """Bridges a multi-shard TimeSeriesMemStore to the mesh SPMD path.

    The moral equivalent of the reference's QueryActor + ActorPlanDispatcher
    wiring, minus the actors: shard lookup happens host-side per shard (the
    Lucene-analogue index), data ships to mesh devices once, and the
    aggregate executes as one SPMD program.
    """

    def __init__(self, memstore, dataset: str, mesh: Mesh):
        self.memstore = memstore
        self.dataset = dataset
        self.mesh = mesh
        self.n_shard = mesh.shape["shard"]
        # (by, without) -> (GroupRegistry, per-shard pid->slot arrays).
        # Slots are assigned once per series lifetime; repeat queries map
        # pids to group slots with one numpy gather instead of per-series
        # label work (ref: the reference re-groups every query — this is
        # a deliberate TPU-side improvement for the 1M-series target).
        self._group_caches: Dict[Tuple, Tuple[GroupRegistry, Dict[int, np.ndarray]]] = {}
        # Device-resident pack cache: the mesh analogue of the leaf path's
        # DeviceMirror (core/devicecache.py).  A pack is revalidated by
        # every shard's (partition count, store generations) signature —
        # unchanged data means repeat queries skip the host gather AND the
        # host->device transfer entirely; any ingest invalidates it and the
        # next query pays one re-upload (never worse than uncached).
        self._pack_cache: Dict[Tuple, Dict] = {}
        self._pack_cache_max = 8
        # packing-LAYOUT memo, validated against the actual per-shard
        # pid sets the index lookup returns: survives value-level
        # invalidations of _pack_cache, so live-ingest re-polls
        # re-upload values but never repack the layout (see
        # lookup_and_pack; mesh_pack_memo_hits counts the wins)
        self._pack_layout_memo: Dict[Tuple, Dict] = {}
        # fused-path plan cache: (shared_ts_row, wends, range) ->
        # (per-time-slice plans, wvalid, wvalid1); see _run_agg_fused
        self._fused_plan_cache: Dict[Tuple, Tuple] = {}
        # run_agg_batch merged-gid cache: (id(pack), panels, fn) -> the
        # device-resident [D, S, P] grouping matrix (+ the pack ref to
        # pin identity), so a dashboard refresh loop over a warm pack
        # skips the per-panel host remaps AND the gid upload.  Panel-
        # grouping entries live in their own dict: with one shared dict
        # a gids_dev insert (cap 4) could purge recently cached panel
        # groupings (cap 8) and defeat the dashboard-refresh warm path
        self._batch_gid_cache: Dict[Tuple, Dict] = {}
        self._panel_group_cache: Dict[Tuple, Dict] = {}
        # queries can reach the executor from HTTP worker threads (same
        # contract as the leaf caches' _FUSED_CACHE_LOCK in query/exec.py):
        # every cache read-modify-write below holds this lock; device work
        # runs outside it
        self._cache_lock = threading.Lock()

    def _cluster_sig(self) -> Tuple:
        return tuple(
            (sh.shard_num, len(sh.partitions),
             tuple((name, st.generation)
                   for name, st in sorted(sh.stores.items())))
            for sh in self.memstore.shards_for(self.dataset))

    def _gids_for(self, shard, pids: np.ndarray,
                  by: Sequence[str], without: Sequence[str]
                  ) -> Tuple[np.ndarray, GroupRegistry]:
        ck = (tuple(by), tuple(without))
        # the whole resolve runs under the lock: GroupRegistry.slot_for is
        # check-then-insert (a race would assign one group key two slots and
        # permanently split its aggregates) and the per-shard array is
        # read-modify-written; keys_for is a fast snapshot read
        with self._cache_lock:
            entry = self._group_caches.get(ck)
            if entry is None:
                entry = (GroupRegistry(by, without), {})
                self._group_caches[ck] = entry
            reg, per_shard = entry
            arr = per_shard.get(shard.shard_num)
            n = len(shard.partitions)
            if arr is None:
                arr = np.full(n, -1, dtype=np.int32)
            elif arr.shape[0] < n:
                arr = np.concatenate(
                    [arr, np.full(n - arr.shape[0], -1, dtype=np.int32)])
            need = arr[pids] < 0
            if need.any():
                new_pids = pids[need]
                keys = shard.keys_for(new_pids)
                for pid, key in zip(new_pids.tolist(), keys):
                    arr[pid] = reg.slot_for(key.labels)
            per_shard[shard.shard_num] = arr
            return arr[pids], reg

    def lookup_and_pack(self, filters, start_ms: int, end_ms: int,
                        by: Sequence[str] = (),
                        without: Sequence[str] = (),
                        fn_name: Optional[str] = None
                        ) -> Optional[PackedShards]:
        """fn_name (the range function the pack will feed) selects counter
        semantics: counter columns are reset-corrected host-side in f64 so
        f32 deltas on device are exact — same contract as the leaf exec.

        Packs are cached on device: a repeat query over unchanged data
        (validated by per-shard generation signatures) reuses the resident
        arrays — run_agg rebases any window grid onto the pack's base, so
        the cache serves rolling windows too, as long as the requested
        start doesn't reach below what the pack was paged for."""
        from filodb_tpu.ops.counter import rebase_values
        from filodb_tpu.ops.rangefns import RANGE_FUNCTIONS
        from filodb_tpu.ops.timewindow import to_offsets
        from filodb_tpu.utils.metrics import registry as metrics_registry
        ck = (tuple(str(f) for f in filters), tuple(by), tuple(without),
              fn_name)
        sig = self._cluster_sig()
        with self._cache_lock:
            # stale entries pin device memory for nothing — drop eagerly
            for k in [k for k, e in self._pack_cache.items()
                      if e["sig"] != sig]:
                del self._pack_cache[k]
            ent = self._pack_cache.get(ck)
            # a hit needs the requested range INSIDE the cached one: the
            # index prunes series by time, so a later end could admit
            # series the cached pack never gathered
            if ent is not None and ent["start_ms"] <= start_ms \
                    and ent["end_ms"] >= end_ms:
                metrics_registry.counter("mesh_pack_cache_hits").increment()
                self._pack_cache[ck] = self._pack_cache.pop(ck)  # LRU touch
                return ent["packed"]
        spec = RANGE_FUNCTIONS.get(fn_name or "")
        fn_is_counter = spec.is_counter if spec else False
        shards = list(self.memstore.shards_for(self.dataset))
        if not shards:
            return None

        def gather_block(shard, pids, schema_name, state):
            """Value-level (re)gather for one shard's memoized row set."""
            shard.ensure_paged_pids(schema_name, pids, start_ms, end_ms)
            store = shard.stores[schema_name]
            rows = shard.rows_for(pids)
            ts, cols, counts = shard.snapshot_read(
                store, lambda: store.gather_rows(rows))
            schema = shard.schemas[schema_name]
            col_def = next((c for c in schema.data_columns
                            if c.name == schema.value_column), None)
            counter_col = col_def is not None and (col_def.detect_drops
                                                   or col_def.counter)
            correct = counter_col and fn_is_counter
            state["precorrected"] = state["precorrected"] and correct
            vals, vbase = rebase_values(cols[schema.value_column], correct)
            return to_offsets(ts, counts, start_ms), vals, vbase

        # Packing LAYOUT memo: the row order, group-slot arrays, labels
        # and schema routing depend only on the per-shard pid SETS the
        # index lookup returns — so a re-poll whose lookup yields the
        # SAME pid sets (the common live-ingest case: values appended,
        # no index change admitting or pruning different series for the
        # new range) reuses the memoized grouping/labels and skips the
        # per-series Python of group resolution + slot compaction.
        # Validity is checked against the ACTUAL lookup result, never
        # inferred from generation counters: new-series ingest and
        # time-range drift both change the pid sets without necessarily
        # moving keys_serial/keys_epoch.  lookup_partitions is itself
        # memoized per (filters, range, index.mutations, keys_epoch)
        # (core/shard.py), so the guard costs one cached lookup + pid
        # array compare per shard.
        lookups: List[Tuple[Optional[np.ndarray], Optional[str]]] = []
        for shard in shards:
            lookup = shard.lookup_partitions(filters, start_ms, end_ms)
            schema_name = lookup.first_schema
            pids = (lookup.pids_by_schema.get(schema_name)
                    if schema_name else None)
            if pids is None or pids.size == 0:
                lookups.append((None, None))
            else:
                lookups.append((np.asarray(pids), schema_name))

        def _memo_valid(memo):
            if len(memo["pids"]) != len(lookups):
                return False
            return all(
                sch == msch and ((pids is None and mp is None)
                                 or (pids is not None and mp is not None
                                     and np.array_equal(pids, mp)))
                for (pids, sch), mp, msch in zip(lookups, memo["pids"],
                                                 memo["schemas"]))

        with self._cache_lock:
            memo = self._pack_layout_memo.get(ck)
            if memo is not None and _memo_valid(memo):
                self._pack_layout_memo[ck] = self._pack_layout_memo.pop(ck)
            else:
                memo = None
        state = {"precorrected": True}
        blocks = []
        pids_by_shard = []
        if memo is not None:
            metrics_registry.counter("mesh_pack_memo_hits").increment()
            for shard, (pids, schema_name), gids in zip(
                    shards, lookups, memo["gids"]):
                if pids is None:
                    blocks.append((np.full((1, 1), PAD_TS, np.int32),
                                   np.full((1, 1), np.nan), []))
                    pids_by_shard.append(None)
                    continue
                pids_by_shard.append(pids)
                ts_off, vals, vbase = gather_block(shard, pids,
                                                   schema_name, state)
                blocks.append((ts_off, vals, gids, vbase))
            labels = memo["labels"]
        else:
            metrics_registry.counter("mesh_pack_memo_misses").increment()
            registry = None
            schemas_by_shard: List[Optional[str]] = []
            for shard, (pids, schema_name) in zip(shards, lookups):
                if pids is None:
                    blocks.append((np.full((1, 1), PAD_TS, np.int32),
                                   np.full((1, 1), np.nan), []))
                    pids_by_shard.append(None)
                    schemas_by_shard.append(None)
                    continue
                pids_by_shard.append(pids)
                schemas_by_shard.append(schema_name)
                ts_off, vals, vbase = gather_block(shard, pids,
                                                   schema_name, state)
                gids, registry = self._gids_for(shard, pids, by, without)
                blocks.append((ts_off, vals, gids, vbase))
            # Compact global registry slots to this query's groups only,
            # so a narrow filter never emits phantom groups from earlier
            # queries and num_groups (-> jit shapes) doesn't grow
            # unboundedly.
            labels = None
            if registry is not None:
                arrs = [b[2] for b in blocks
                        if isinstance(b[2], np.ndarray)]
                uniq = (np.unique(np.concatenate(arrs)) if arrs
                        else np.zeros(0, dtype=np.int32))
                labels = [registry.labels[int(g)] for g in uniq]
                blocks = [(b[0], b[1],
                           (np.searchsorted(uniq, b[2]).astype(np.int32)
                            if isinstance(b[2], np.ndarray) else b[2]),
                           *b[3:]) for b in blocks]
            with self._cache_lock:
                self._pack_layout_memo[ck] = {
                    "pids": list(pids_by_shard),
                    "gids": [(b[2] if isinstance(b[2], np.ndarray)
                              else None) for b in blocks],
                    "schemas": schemas_by_shard,
                    "labels": labels}
                while len(self._pack_layout_memo) > 8:
                    self._pack_layout_memo.pop(
                        next(iter(self._pack_layout_memo)))
        precorrected = state["precorrected"]
        if len(blocks) > self.n_shard:
            raise ValueError(
                f"memstore has {len(blocks)} shards but mesh shard axis is "
                f"{self.n_shard}; data would be silently dropped")
        # pad shard list to mesh size
        while len(blocks) < self.n_shard:
            blocks.append((np.full((1, 1), PAD_TS, np.int32),
                           np.full((1, 1), np.nan), []))
        packed = pack_shards(blocks, by=by, without=without, base_ms=start_ms,
                             precorrected=precorrected, group_labels=labels)
        packed.pids_by_shard = pids_by_shard
        packed = device_put_packed(packed, self.mesh)
        # cache under the PRE-gather signature: a concurrent ingest landing
        # mid-gather then invalidates the entry (over-invalidation is safe;
        # re-reading the signature here could cache a pack MISSING those
        # samples under the post-ingest generation and serve it as fresh).
        # ODP during the first gather also bumps generations, so the second
        # query re-packs once and stabilizes from the third on.
        with self._cache_lock:
            self._pack_cache[ck] = {"sig": sig,
                                    "start_ms": start_ms, "end_ms": end_ms,
                                    "packed": packed}
            while len(self._pack_cache) > self._pack_cache_max:
                self._pack_cache.pop(next(iter(self._pack_cache)))
        metrics_registry.counter("mesh_pack_cache_misses").increment()
        return packed

    def _prep_wends(self, packed: PackedShards, wends: np.ndarray
                    ) -> Tuple[np.ndarray, int]:
        """Rebase absolute window ends onto the pack's offset base and pad
        the grid to a multiple of the time axis; padded windows end before
        all data (-PAD_TS) so they are empty, not garbage."""
        wends = np.asarray(wends, np.int64) - packed.base_ms
        if wends.size and (wends.max() >= (1 << 30) or
                           wends.min() <= -(1 << 30)):
            raise ValueError("window ends more than ~12 days from the packed "
                             "base; split the query by time range")
        wends = wends.astype(np.int32)
        W = wends.shape[0]
        n_time = self.mesh.shape["time"]
        Wp = -(-W // n_time) * n_time
        if Wp != W:
            wends = np.concatenate(
                [wends, np.full(Wp - W, -PAD_TS, np.int32)])
        return wends, W

    def run_agg_batch(self, filters, start_ms: int, end_ms: int,
                      wends: np.ndarray, *, range_ms: int,
                      fn_name: Optional[str],
                      panels) -> List[Tuple[np.ndarray, List[Dict[str, str]]]]:
        """A dashboard's panels over one packed working set: panels is
        [(by, without, agg_op)]; returns [(values [G, W], labels)] in
        panel order.

        The mesh analogue of engine.query_range_batch: the values are
        packed ONCE (grouping recomputed per panel over the same rows via
        pids_by_shard), and every fused-eligible panel merges into ONE
        shard_map kernel dispatch over disjoint group-id ranges
        (_run_agg_fused_multi multi-hot epilogue).  Ineligible panels —
        and all panels when the shared fused gate rejects — fall back to
        run_agg per panel, where the pack cache still dedups the gather
        for repeated groupings."""
        by0, wo0, _ = panels[0]
        packed = self.lookup_and_pack(filters, start_ms, end_ms, by=by0,
                                      without=wo0, fn_name=fn_name)
        results: List = [None] * len(panels)
        if packed is None:
            # no shards for the dataset: keep the declared contract —
            # one (empty values, no labels) tuple per panel
            empty = np.zeros((0, np.asarray(wends).shape[0]))
            return [(empty, []) for _ in panels]
        panels_key = tuple((tuple(by), tuple(wo), op)
                           for by, wo, op in panels)
        merged_key = (id(packed), panels_key, fn_name)
        with self._cache_lock:
            cached = self._panel_group_cache.get(merged_key)
        if cached is not None and cached["packed"] is packed:
            kpanels, kmap, klabels = cached["kpanels"], cached["kmap"], \
                cached["klabels"]
        else:
            kpanels, kmap, klabels = self._panel_groupings(packed, panels)
            with self._cache_lock:
                self._panel_group_cache[merged_key] = {
                    "packed": packed, "kpanels": kpanels, "kmap": kmap,
                    "klabels": klabels}
                while len(self._panel_group_cache) > 8:
                    self._panel_group_cache.pop(
                        next(iter(self._panel_group_cache)))
        if kpanels:
            wends_p, W = self._prep_wends(packed, wends)
            try:
                fused = self._run_agg_fused_multi(
                    packed, wends_p, W, range_ms, fn_name, kpanels,
                    merged_key=merged_key)
            except Exception as e:  # noqa: BLE001 — fusion is optional
                from filodb_tpu.utils.metrics import (
                    log_fused_degradation, registry as mreg)
                mreg.counter("mesh_fused_errors").increment()
                log_fused_degradation("mesh", e)
                fused = None
            if fused is not None:
                for arr, idx, labels in zip(fused, kmap, klabels):
                    results[idx] = (arr, labels)
        for idx, (by, wo, op) in enumerate(panels):
            if results[idx] is None:
                pk = self.lookup_and_pack(filters, start_ms, end_ms,
                                          by=by, without=wo,
                                          fn_name=fn_name)
                results[idx] = self.run_agg(pk, np.asarray(wends),
                                            range_ms=range_ms,
                                            fn_name=fn_name, agg_op=op)
        return results

    def run_binop_agg(self, filters_l, filters_r, start_ms: int,
                      end_ms: int, wends: np.ndarray, *, range_ms: int,
                      fn_name: Optional[str], op: str,
                      agg_op_l: str = "sum", agg_op_r: str = "sum",
                      by=(), without=(), bool_modifier: bool = False
                      ) -> Tuple[np.ndarray, List[Dict[str, str]]]:
        """Mesh-wide vector-matching binary op between two aggregated
        expressions: ``aggL by(...)(fnL(selL)) <op> aggR by(...)(selR)``
        matched on the (shared) group labels.  Returns
        (values [P, W], per-pair label dicts).

        Whole-expression dispatch (PR 17): when both sides select the
        SAME working set the two panels ride ONE run_agg_batch — one
        pack, one merged kernel dispatch across the mesh; otherwise each
        side runs its own fused scan.  Either way only the two sides'
        [G, W] partials cross chips; the label match resolves host-side
        into index maps and the op itself is one jitted gather+binop
        program (ops/select.gather_binop)."""
        from filodb_tpu.ops.select import gather_binop
        by, without = tuple(by), tuple(without)
        if list(filters_l) == list(filters_r):
            (lv, ll), (rv, rl) = self.run_agg_batch(
                filters_l, start_ms, end_ms, wends, range_ms=range_ms,
                fn_name=fn_name,
                panels=[(by, without, agg_op_l), (by, without, agg_op_r)])
        else:
            pl = self.lookup_and_pack(filters_l, start_ms, end_ms, by=by,
                                      without=without, fn_name=fn_name)
            pr = self.lookup_and_pack(filters_r, start_ms, end_ms, by=by,
                                      without=without, fn_name=fn_name)
            W = np.asarray(wends).shape[0]
            lv, ll = ((np.zeros((0, W)), []) if pl is None else
                      self.run_agg(pl, np.asarray(wends), range_ms=range_ms,
                                   fn_name=fn_name, agg_op=agg_op_l))
            rv, rl = ((np.zeros((0, W)), []) if pr is None else
                      self.run_agg(pr, np.asarray(wends), range_ms=range_ms,
                                   fn_name=fn_name, agg_op=agg_op_r))
        # group labels are unique per side: one-to-one match on the
        # label dict (both sides grouped by the same by/without)
        rindex = {tuple(sorted(d.items())): j for j, d in enumerate(rl)}
        pairs = [(i, rindex[tuple(sorted(d.items()))])
                 for i, d in enumerate(ll)
                 if tuple(sorted(d.items())) in rindex]
        W = lv.shape[1] if lv.ndim == 2 else np.asarray(wends).shape[0]
        if not pairs:
            return np.zeros((0, W)), []
        mi = np.asarray([p[0] for p in pairs], np.int64)
        oi = np.asarray([p[1] for p in pairs], np.int64)
        import time as _time

        from filodb_tpu.utils.devicetelem import telem
        _b0 = _time.perf_counter()
        out = np.asarray(gather_binop(
            jnp.asarray(np.asarray(lv)), jnp.asarray(np.asarray(rv)),
            jnp.asarray(mi), jnp.asarray(oi), op=op,
            bool_modifier=bool_modifier, keep_side="lhs"))
        telem.record_dispatch(
            "gather_binop", shape=f"P{len(pairs)}xW{W}:{op}",
            seconds=_time.perf_counter() - _b0, bytes_out=int(out.nbytes))
        return out, [ll[i] for i, _ in pairs]

    def _panel_groupings(self, packed: PackedShards, panels):
        """Per-panel (gids, G, op, gsize) + labels over the pack's rows —
        the host remap work run_agg_batch caches per (pack, panels)."""
        kpanels, kmap, klabels = [], [], []
        shards = list(self.memstore.shards_for(self.dataset))
        D, S, _ = packed.ts_off.shape
        for idx, (by, wo, op) in enumerate(panels):
            if op not in ("sum", "avg", "count"):
                continue
            if idx == 0:
                kpanels.append((None, packed.num_groups, op, packed.gsize))
                kmap.append(idx)
                klabels.append(packed.group_labels)
                continue
            if packed.pids_by_shard is None:
                continue          # pack built outside lookup_and_pack
            garrs, registry = [], None
            for shard, pids in zip(shards, packed.pids_by_shard):
                if pids is None:
                    garrs.append(None)
                    continue
                g, registry = self._gids_for(shard, pids, tuple(by),
                                             tuple(wo))
                garrs.append(np.asarray(g, np.int64))
            real = [g for g in garrs if g is not None]
            uniq = (np.unique(np.concatenate(real)) if real
                    else np.zeros(0, np.int64))
            labels = ([registry.labels[int(x)] for x in uniq]
                      if registry is not None else [])
            G = max(len(labels), 1)
            gids = np.full((D, S), -1, np.int32)
            gsize = np.zeros(G, np.int64)
            for d, g in enumerate(garrs):
                if g is None:
                    continue
                cg = np.searchsorted(uniq, g).astype(np.int32)
                gids[d, :len(cg)] = cg
                gsize += np.bincount(cg, minlength=G)[:G]
            kpanels.append((gids, G, op, gsize))
            kmap.append(idx)
            klabels.append(labels)
        return kpanels, kmap, klabels

    def run_agg(self, packed: PackedShards, wends: np.ndarray, *,
                range_ms: int, fn_name: Optional[str], agg_op: str,
                params: Tuple[float, ...] = ()) -> Tuple[np.ndarray, List[Dict[str, str]]]:
        """Returns (final [G, W] values, group label dicts).

        wends are ABSOLUTE ms (same clock as lookup_and_pack's time range);
        they are rebased onto the pack's offset base here."""
        wends, W = self._prep_wends(packed, wends)
        if agg_op in ("sum", "avg", "count") and not params:
            try:
                fused = self._run_agg_fused(packed, wends, W, range_ms,
                                            fn_name, agg_op)
            except Exception as e:  # noqa: BLE001 — fusion is optional
                from filodb_tpu.utils.metrics import (
                    log_fused_degradation, registry)
                registry.counter("mesh_fused_errors").increment()
                log_fused_degradation("mesh", e)
                fused = None
            if fused is not None:
                return fused, packed.group_labels
        wends_dev = jax.device_put(
            wends, NamedSharding(self.mesh, P("time")))
        partials = distributed_window_agg(
            self.mesh, packed.ts_off, packed.values, packed.group_ids,
            wends_dev, range_ms=range_ms, fn_name=fn_name, params=params,
            agg_op=agg_op, num_groups=packed.num_groups,
            base_ms=packed.base_ms, vbase=packed.vbase,
            precorrected=packed.precorrected,
            dense=(packed.dense
                   if fn_name in ("rate", "increase", "delta",
                                  "irate", "idelta") else True))
        out = agg_ops.present(agg_op, partials)
        return np.asarray(out)[:, :W], packed.group_labels

    def _run_agg_fused(self, packed: PackedShards, wends_p: np.ndarray,
                       W: int, range_ms: int, fn_name: Optional[str],
                       agg_op: str = "sum") -> Optional[np.ndarray]:
        """Single-panel form of _run_agg_fused_multi (see below)."""
        res = self._run_agg_fused_multi(
            packed, wends_p, W, range_ms, fn_name,
            [(None, packed.num_groups, agg_op, packed.gsize)])
        return None if res is None else res[0]

    def _run_agg_fused_multi(self, packed: PackedShards,
                             wends_p: np.ndarray, W: int, range_ms: int,
                             fn_name: Optional[str],
                             kpanels,
                             merged_key: Optional[Tuple] = None
                             ) -> Optional[List[np.ndarray]]:
        """sum/avg/count(rate|increase|delta|*_over_time) over a
        uniform-grid pack via PER-DEVICE dispatch of the single-chip MXU
        kernel (ops/pallas_fused.py): device (s, t) runs the kernel over
        its committed shard block with time-slice t's selection-matrix
        plan, and only the [G] group partials merge across chips
        (merge_device_partials — psum collective on ICI, host reduce
        otherwise).  The kernel is never traced inside shard_map (see
        the section comment above _device_fused_call).
        One HBM pass per device instead of the general path's several.
        NaN-holed (ragged) packs run the kernel's valid-boundary variant
        with per-cell presence merged as a second partial (r4).  On a
        dense pack count needs NO device work (identical per-window
        counts); avg divides sums by counts.  Backends without an MXU
        dispatch ops/hostleaf per shard instead (same merge contract).

        kpanels: [(gids [D, S] int32 or None for the pack's own grouping,
        G, agg_op, gsize [G])] — multiple panels (run_agg_batch) merge
        into ONE kernel dispatch over disjoint group-id ranges, the mesh
        analogue of the leaf path's fused_leaf_agg_batch.  Returns the
        finished [G, W] arrays in panel order, or None when the shared
        gate rejects (callers then take the general path per panel)."""
        from filodb_tpu.ops import pallas_fused as pf
        shared = packed.shared_ts_row is not None and packed.gsize is not None
        dense = packed.dense
        for _, _, op, _ in kpanels:
            if not pf.can_fuse(fn_name or "", op, shared, dense):
                return None
        if fn_name in pf.MINMAX_FNS:
            # reduce_window kinds run through the general mesh path (XLA
            # fuses them fine); the matmul kernel has no min/max kind
            return None
        ragged = not dense
        if ragged and fn_name in ("last_over_time", "count_over_time"):
            # slot-semantics kinds: their kernel presence counts grid
            # SLOTS, and mesh pack padding rows carry gid 0 (unlike the
            # leaf path's -1) — they would inflate group 0.  General path.
            return None
        minsamp = 2 if fn_name in ("rate", "increase", "delta") else 1
        over_time = fn_name in pf.OVER_TIME_FNS

        out: List[Optional[np.ndarray]] = [None] * len(kpanels)
        # dense count panels: every REAL series emits a value exactly
        # where the shared window is valid — pure host math
        kidx = [i for i, (_, _, op, _) in enumerate(kpanels)
                if not (op == "count" and dense)]
        if kidx:
            if fn_name in ("rate", "increase") and not packed.precorrected:
                return None
            n_time = self.mesh.shape["time"]
            Wp = wends_p.shape[0]
            Wl = Wp // n_time
            D, S, T = packed.ts_off.shape
            Tp = pf._pad_to(T, pf._LANE)
            Wlp = pf._pad_to(max(Wl, 1), pf._LANE)
            offsets, Gtot = [], 0
            for i in kidx:
                offsets.append(Gtot)
                Gtot += kpanels[i][1]
            # padded group count, matching _run's recomputation exactly
            kind_k = fn_name if over_time else "rate_family"
            if pf.pick_block(Tp, Wlp, pf.pad_group_count(Gtot), kind_k,
                             ragged, panels=max(len(kidx), 1)) is None:
                return None
            interpret = pf.kernel_mode()
            if interpret is None:
                # no MXU here: the per-device unit becomes the host fused
                # leaf (ops/hostleaf), same dispatch + partial-merge shape
                # — the single-chip cost-based router's host path scaled
                # out over shards.  Ragged sets have no host variant.
                host_out = self._run_agg_fused_host(
                    packed, wends_p, W, range_ms, fn_name, kpanels, kidx)
                if host_out is None:
                    return None
                for i, arr in zip(kidx, host_out):
                    out[i] = arr
                return self._finish_count_panels(packed, wends_p, W,
                                                 range_ms, kpanels, out,
                                                 minsamp)
            # plan cache: per-time-slice plans; a plan's [8, Wlp] rows go
            # to a device at its first dispatch there and stay with the
            # plan (pf.enqueue_operands)
            plan_key = (packed.shared_ts_row.tobytes(), wends_p.tobytes(),
                        range_ms)
            from filodb_tpu.query.exec import _lru_touch
            with self._cache_lock:
                ent = _lru_touch(self._fused_plan_cache, plan_key)
            if ent is None:
                ts_row = packed.shared_ts_row.astype(np.int64)
                plans = [pf.build_plan(
                    ts_row, wends_p[i * Wl:(i + 1) * Wl].astype(np.int64),
                    range_ms) for i in range(n_time)]
                ent = (plans,
                       np.concatenate([p.wvalid for p in plans]),
                       np.concatenate([p.wvalid1 for p in plans]))
                with self._cache_lock:
                    self._fused_plan_cache[plan_key] = ent
                    while len(self._fused_plan_cache) > 4:
                        self._fused_plan_cache.pop(
                            next(iter(self._fused_plan_cache)))
            plans, wvalid, wvalid1 = ent
            vbase = packed.vbase
            if vbase is None:
                vbase = jax.device_put(
                    np.zeros((D, S), np.float32),
                    NamedSharding(self.mesh, P("shard", None)))
                # the pack is cached across queries — keep the device zeros
                # with it so repeats skip this alloc + transfer (also
                # serves the general path, which otherwise re-zeros)
                packed.vbase = vbase
            if len(kidx) == 1 and kpanels[kidx[0]][0] is None:
                gids_dev = packed.group_ids[..., None]
            else:
                gids_dev = None
                if merged_key is not None:
                    with self._cache_lock:
                        ent2 = self._batch_gid_cache.get(merged_key)
                    if ent2 is not None and ent2["packed"] is packed:
                        gids_dev = ent2["gids_dev"]
                if gids_dev is None:
                    cols = []
                    for j, i in enumerate(kidx):
                        g = kpanels[i][0]
                        if g is None:
                            g = np.asarray(packed.group_ids)
                        # pack pad rows carry gid 0 over zeroed/NaN
                        # values: offset keeps them harmless (+0 sums,
                        # 0 presence)
                        cols.append(np.where(g >= 0, g + offsets[j], -1)
                                    .astype(np.int32))
                    gids_dev = jax.device_put(
                        np.stack(cols, axis=-1),
                        NamedSharding(self.mesh, P("shard", None, None)))
                    if merged_key is not None:
                        with self._cache_lock:
                            self._batch_gid_cache[merged_key] = {
                                "packed": packed, "gids_dev": gids_dev}
                            while len(self._batch_gid_cache) > 4:
                                self._batch_gid_cache.pop(
                                    next(iter(self._batch_gid_cache)))
            # per-device dispatch: device (s, t) runs the SINGLE-CHIP
            # kernel over its committed shard block with time-slice t's
            # plan — all D*n_time dispatches are issued before any
            # result is touched, so the chips compute concurrently; only
            # the [Gtot, Wlp] partials then merge (collective on ICI,
            # host reduce otherwise).
            is_counter = fn_name in ("rate", "increase")
            vblocks = {s.device: s.data
                       for s in packed.values.addressable_shards}
            grid = self.mesh.devices
            if any(dev not in vblocks for dev in grid.flat):
                # multi-host mesh: remote devices' blocks are not
                # addressable from this process, so per-device dispatch
                # cannot read them — route the general SPMD path (the
                # multi-host-correct shard_map composition) instead of
                # raising a KeyError per query
                from filodb_tpu.utils.metrics import registry
                registry.counter("mesh_fused_unaddressable").increment()
                return None
            gblocks = {s.device: s.data
                       for s in gids_dev.addressable_shards}
            vbblocks = {s.device: s.data
                        for s in vbase.addressable_shards}
            parts_sums: Dict[Tuple[int, int], jax.Array] = {}
            parts_cnts: Dict[Tuple[int, int], jax.Array] = {}
            import time as _time

            from filodb_tpu.utils.devicetelem import telem, watched_call
            sig = (f"S{S}xT{T}xG{Gtot}:{kind_k}"
                   + (":ragged" if ragged else ""))
            for si in range(D):
                for ti in range(n_time):
                    dev = grid[si, ti]
                    steps = pf.scan_steps(plans[ti], kind_k, ragged)
                    sig_t = sig + (f":{steps}steps" if steps else "")
                    rows_d, ts_d, _ = pf.enqueue_operands(
                        plans[ti], dev, kind_k, ragged)
                    _d0 = _time.perf_counter()
                    res = watched_call(
                        "mesh_fused", _device_fused_call, sig_t,
                        lambda: _device_fused_call(
                            vblocks[dev], gblocks[dev], vbblocks[dev],
                            rows_d, ts_d, G=Gtot, S=S, T=T, Tp=Tp,
                            is_counter=is_counter,
                            is_rate=(fn_name == "rate"),
                            interpret=interpret,
                            kind=kind_k, ragged=ragged, steps=steps),
                        device=dev)
                    # per-chip ledger entry per dispatch: the seconds here
                    # are issue wall only (the chips compute concurrently;
                    # the synchronizing merge below carries the wait), but
                    # the COUNTS reconcile 1:1 with
                    # mesh_fused_perdevice_dispatches
                    telem.record_dispatch(
                        "mesh_fused", device=dev, shape=sig_t,
                        seconds=_time.perf_counter() - _d0,
                        bytes_in=int(getattr(vblocks[dev], "nbytes", 0)))
                    if ragged:
                        parts_sums[(si, ti)], parts_cnts[(si, ti)] = res
                    else:
                        parts_sums[(si, ti)] = res
            _m0 = _time.perf_counter()
            merged = merge_device_partials(parts_sums, self.mesh, "sum")

            def unslice(a):
                return a.reshape(Gtot, n_time, Wlp)[:, :, :Wl] \
                    .reshape(Gtot, Wp)[:, :W]

            if ragged:
                all_out = unslice(merged)
                all_counts = unslice(
                    merge_device_partials(parts_cnts, self.mesh, "sum"))
            else:
                all_out, all_counts = unslice(merged), None
            # the merge is where the dispatches above synchronize: its
            # wall is the fleet's compute+reduce wait, attributed as one
            # ledger entry so QueryStats.device_seconds covers the mesh
            # path end to end
            telem.record_dispatch(
                "mesh_merge", shape=f"D{D}xG{Gtot}",
                seconds=_time.perf_counter() - _m0,
                bytes_out=int(all_out.nbytes))
            from filodb_tpu.utils.metrics import registry
            registry.counter("mesh_fused_kernel").increment()
            registry.counter("mesh_fused_perdevice_dispatches") \
                .increment(D * n_time)
            if len(kidx) > 1:
                registry.counter("mesh_fused_batch_panels") \
                    .increment(len(kidx))
            for j, i in enumerate(kidx):
                _, G, op, gsize = kpanels[i]
                lo = offsets[j]
                pout = all_out[lo:lo + G]
                counts = (all_counts[lo:lo + G] if ragged
                          else _host_counts(gsize,
                                            wvalid1 if over_time
                                            else wvalid)[:, :W])
                if op == "count":             # ragged: kernel presence
                    out[i] = np.where(counts > 0,
                                      counts.astype(np.float64), np.nan)
                    continue
                if op == "avg":
                    with np.errstate(invalid="ignore", divide="ignore"):
                        pout = np.asarray(pout, np.float64) \
                            / np.maximum(counts, 1.0)
                out[i] = pf.present_sum(pout, counts)
        return self._finish_count_panels(packed, wends_p, W, range_ms,
                                         kpanels, out, minsamp)

    def _finish_count_panels(self, packed: PackedShards,
                             wends_p: np.ndarray, W: int, range_ms: int,
                             kpanels, out: List[Optional[np.ndarray]],
                             minsamp: int) -> List[np.ndarray]:
        """Dense count panels: every REAL series emits a value exactly
        where the shared window is valid — pure host math, no device
        work (shared epilogue of the kernel and host dispatch routes)."""
        from filodb_tpu.ops import pallas_fused as pf
        valid = None                          # panel-independent; lazy
        for i, (_, _, op, gsize) in enumerate(kpanels):
            if out[i] is None:                # dense count: host math
                if valid is None:
                    n = pf.window_counts(
                        packed.shared_ts_row.astype(np.int64),
                        wends_p[:W].astype(np.int64), range_ms)
                    valid = (n >= minsamp).astype(np.float64)
                counts = _host_counts(gsize, valid)
                from filodb_tpu.utils.metrics import registry
                registry.counter("mesh_fused_count_host").increment()
                out[i] = np.where(counts > 0, counts, np.nan)
        return out

    def _host_plan(self, packed: PackedShards, wends_p: np.ndarray,
                   W: int, range_ms: int):
        """Full-grid FusedPlan for the host dispatch route, cached next
        to the per-slice device plans."""
        from filodb_tpu.ops import pallas_fused as pf
        from filodb_tpu.query.exec import _lru_touch
        plan_key = ("host", packed.shared_ts_row.tobytes(),
                    wends_p[:W].tobytes(), range_ms)
        with self._cache_lock:
            plan = _lru_touch(self._fused_plan_cache, plan_key)
        if plan is None:
            plan = pf.build_plan(packed.shared_ts_row.astype(np.int64),
                                 wends_p[:W].astype(np.int64), range_ms)
            with self._cache_lock:
                self._fused_plan_cache[plan_key] = plan
                while len(self._fused_plan_cache) > 4:
                    self._fused_plan_cache.pop(
                        next(iter(self._fused_plan_cache)))
        return plan

    def _run_agg_fused_host(self, packed: PackedShards,
                            wends_p: np.ndarray, W: int, range_ms: int,
                            fn_name: Optional[str], kpanels, kidx
                            ) -> Optional[List[np.ndarray]]:
        """Per-shard HOST fused evaluation (ops/hostleaf) with the same
        dispatch + partial-merge shape as the per-device kernel path —
        the dispatch unit on backends without an MXU, mirroring the
        single-chip cost-based router's host route.  Dense shared-grid
        packs only (hostleaf has no ragged variant); partials merge in
        ascending shard order via the sum combiner (ops/agg.reduce_phase
        semantics).  Returns finished [G, W] arrays in kidx order, or
        None to divert to the general path."""
        if not packed.dense or packed.host_values is None:
            return None
        from filodb_tpu.ops import hostleaf
        plan = self._host_plan(packed, wends_p, W, range_ms)
        hv = packed.host_values
        hvb = packed.host_vbase
        hg = packed.host_group_ids
        outs: List[np.ndarray] = []
        for i in kidx:
            g, G, op, _ = kpanels[i]
            comp = None
            for d in range(hv.shape[0]):
                nser = int(packed.n_series[d])
                if nser == 0:
                    continue
                gids_d = (hg[d, :nser] if g is None
                          else np.asarray(g[d, :nser]))
                vb_d = None if hvb is None else hvb[d, :nser]
                c = hostleaf.host_leaf_agg(plan, hv[d, :nser], vb_d,
                                           gids_d, G, fn_name, op)
                comp = c if comp is None else comp + c
            if comp is None:
                comp = np.zeros((G, W, 2))
            s, cnt = comp[..., 0], comp[..., 1]
            vals = s / np.maximum(cnt, 1.0) if op == "avg" else s
            outs.append(np.where(cnt > 0, vals, np.nan))
        from filodb_tpu.utils.metrics import registry
        registry.counter("mesh_fused_host").increment()
        registry.counter("mesh_partials_host_merge").increment()
        return outs
