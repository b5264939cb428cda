"""Primitives for evaluating a query step over a JAX device mesh.

Where FiloDB dispatches serialized ExecPlan subtrees to shard-owner nodes via
Akka and tree-reduces partial aggregates through ReduceAggregateExec
(ref: query/.../exec/PlanDispatcher.scala:20-57, exec/AggrOverRangeVectors.scala
:51-123, doc/query-engine.md:90-155), these functions lay per-shard dense
series arrays out on a device mesh and let XLA collectives do the reduce:

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

No door serves from here: a served query on several chips runs the leaf path
over a mirror a shard (core/devicecache.MirrorPlacer) with one fused call a
device (query/fusedbatch.finish_fused_calls) and the host's reduce
(doc/multichip.md).  The callers of this module are the driver's dry run
(`__graft_entry__.dryrun_multichip`), parallel/multihost.py and the tests.
"""
from __future__ import annotations

import dataclasses
import functools
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
    # False when any counted cell is non-finite: the rate family then runs
    # its valid-boundary variant (staleness markers are absent samples).
    # Computed at pack time on the HOST arrays (after device_put the values
    # are sharded device arrays a scan would have to transfer back).
    dense: bool = True

    @property
    def n_shards(self) -> int:
        return self.ts_off.shape[0]


class GroupRegistry:
    """Global aggregation-group slot assignment shared across the shards of
    one pack: group key -> stable slot.
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
    gid array already compacted to [0, len(group_labels)), which needs no
    per-series Python work.
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
    # dense = every counted cell finite.  isfinite, not isnan: an inf
    # sample would be clamped by the dense kernel wrapper's nan_to_num
    # and silently change query results.
    dense = all(
        nser[d] == 0
        or bool((np.isfinite(vals[d, :nser[d]])
                 | (ts[d, :nser[d]] >= PAD_TS)).all())
        for d in range(D))
    return PackedShards(ts, vals, gids, num_groups,
                        labels_out, base_ms, nser,
                        vbase=vbase if any_vbase else None,
                        precorrected=precorrected, dense=dense)


def device_put_packed(packed: PackedShards, mesh: Mesh) -> PackedShards:
    """Place packed arrays on the mesh: series data sharded over 'shard',
    replicated over 'time' (each time-row needs the full series to evaluate
    any window slice — windows reach back `range` into the data)."""
    data_spec = NamedSharding(mesh, P("shard", None, None))
    gid_spec = NamedSharding(mesh, P("shard", None))
    return dataclasses.replace(
        packed,
        ts_off=jax.device_put(packed.ts_off, data_spec),
        values=jax.device_put(packed.values, data_spec),
        group_ids=jax.device_put(packed.group_ids, gid_spec),
        vbase=(None if packed.vbase is None
               else jax.device_put(packed.vbase, gid_spec)))


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
    [1, S, T] values + [1, S, P] grouping (P > 1: several panels over
    disjoint group-id ranges, multi-hot kernel epilogue) to kernel
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
