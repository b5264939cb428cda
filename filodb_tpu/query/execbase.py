"""Exec-tree foundations: data shapes, fused-leaf caches, the
3-phase aggregation finishers, and the ExecPlan base classes.

Split from the original query/exec.py (round 4, no behavior change);
`filodb_tpu.query.exec` re-exports everything, so import paths are
unchanged.  ref: query/.../exec/ExecPlan.scala:41-186,
AggrOverRangeVectors.scala:17-125.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time as _time
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np
import jax.numpy as jnp

from filodb_tpu.core.devicecache import DeferredRows
from filodb_tpu.core.index import ColumnFilter, Equals
from filodb_tpu.ops import agg as agg_ops
from filodb_tpu.ops import hist as hist_ops
from filodb_tpu.ops.instant import (INSTANT_FUNCTIONS, ARITH_OPERATORS,
                                    COMPARISON_OPERATORS, apply_binary_op)
from filodb_tpu.ops import counter as counter_ops
from filodb_tpu.ops.rangefns import RANGE_FUNCTIONS, evaluate_range_function
from filodb_tpu.ops.timewindow import PAD_TS, to_offsets, make_window_ends
from filodb_tpu.query.rangevector import (QueryContext, QueryResult, QueryStats,
                                          RangeVectorKey, ResultBlock,
                                          concat_blocks, remove_nan_series)

# --------------------------------------------------------------- data shapes


class LazyKeys:
    """Sequence facade over `shard.keys_for(pids)` deferring the O(S)
    Python per-series key materialization until something actually reads
    a key.  Warm fused-path queries never do — group ids and group keys
    come from the snapshot-keyed group cache — so building RawBlock.keys
    eagerly charged every dashboard poll ~6 ms per 16k series (measured:
    keys_for was 35% of the batched 12-panel hist dashboard's host time)
    for a list nobody indexed.  len()/bool are O(1); iteration, indexing
    and slicing materialize once and memoize.

    Deferral widens the window in which eviction can recycle a pid
    between the leaf scan and first key read, so the shard's keys_epoch
    is captured at construction: if it moved by materialization time the
    pids may no longer name the snapshot's series — fall back to
    resolving each pid defensively (keys_for already yields a sentinel
    key for pruned slots) and count the event so the race is observable
    instead of silent (round-5 review)."""
    __slots__ = ("_shard", "_pids", "_keys", "_epoch")

    def __init__(self, shard, pids):
        self._shard = shard
        self._pids = pids
        self._keys = None
        self._epoch = shard.keys_epoch

    def _mat(self):
        if self._keys is None:
            if self._shard.keys_epoch != self._epoch:
                from filodb_tpu.utils.metrics import registry
                registry.counter("lazykeys_epoch_moved",
                                 dataset=self._shard.dataset).increment()
            self._keys = self._shard.keys_for(self._pids)
        return self._keys

    def __len__(self):
        return int(self._pids.size)

    def __bool__(self):
        return self._pids.size > 0

    def __iter__(self):
        return iter(self._mat())

    def __getitem__(self, i):
        return self._mat()[i]


@dataclasses.dataclass
class RawBlock:
    """Raw gathered samples for one schema on one shard: pre-step-grid.

    values are REBASED per series (absolute value - vbase[s]) so counter
    deltas survive the f32 device downcast; vbase is the per-series base
    in f64 (None = not rebased).  See ops/timewindow.series_value_base.

    A leaf over the device mirror hands `ts_off`, `values` and `vbase` as
    DeferredRows (core/devicecache.MirrorGather): each is gathered out of
    the mirror when that field is first read, and every reader gets the
    array it would have got.  `values_shape` answers without a read.
    `phase` is held the same way and reads as the snapshot's HOST copy of
    the rows' phases (no device work); `rows_padded("phase", n)` is the
    device column the fused kernel takes."""
    keys: List[RangeVectorKey]
    ts_off: np.ndarray                  # int32 [S, T] offsets from base_ms
    values: np.ndarray                  # [S, T] or [S, T, B]
    base_ms: int
    bucket_les: Optional[np.ndarray] = None
    samples: int = 0                    # total valid samples (stats)
    vbase: Optional[np.ndarray] = None  # [S] or [S, B]
    precorrected: bool = False          # counter reset-correction done host-side
    # shared scrape grid: row-0 ts offsets when ALL rows share one grid
    # (the pallas_fused precondition, tracked by the device mirror); None
    # otherwise.  `dense` qualifies it: True = no NaN holes anywhere in the
    # counted region; False = NaN-holed values on the shared grid, which
    # only the validity-weighted fused kinds accept.
    shared_ts_row: Optional[np.ndarray] = None
    dense: bool = True
    # working-set identity (shard keys_serial, keys_epoch, pids bytes):
    # lets key-preserving transformers reuse cached host group ids —
    # _group_ids is an O(S) Python loop that dominated warm general-path
    # queries (~0.3s of a 0.4s query at 65k series)
    cache_token: Optional[Tuple] = None
    # cost-based router verdict (round-5 item 6): True when the leaf's
    # estimated working set is below query.host_route_max_samples — the
    # gather then stays host-side and _try_fused evaluates in numpy
    # (ops/hostleaf) instead of paying the ~65 ms device dispatch floor
    route_host: bool = False
    # rows on a phase grid (core/devicecache._MirrorSnapshot): row s lies
    # at shared_ts_row + phase[s], int32 [S] whole ms.  None: every row
    # lies on shared_ts_row itself.  Only the fused leaf reads a grid with
    # phases; every other reader takes `shared_grid` below
    phase: Optional[np.ndarray] = None

    @property
    def values_shape(self) -> Tuple[int, ...]:
        """Shape of `values` ([S, T] or [S, T, B]; () for what has none),
        known without gathering rows nobody has read yet."""
        return tuple(getattr(self.__dict__["_values"], "shape", ()))

    @property
    def phased(self) -> bool:
        """Whether the rows may lie off `shared_ts_row` by a phase (known
        without reading the phases)."""
        return self.__dict__["_phase"] is not None

    @property
    def shared_grid(self) -> bool:
        """Every row lies on `shared_ts_row` itself: ONE timestamp row
        stands for all of them."""
        return self.shared_ts_row is not None and not self.phased

    def rows_padded(self, field: str, rows_to: int,
                    whole_first: bool = False):
        """`field` (`values`, `vbase` or `phase`) for the fused leaf's
        padded working set: rows still in the mirror are taken with zero rows up to
        `rows_to` behind them (DeferredRows.resolve: one program a padded
        row count), or `whole_first`, in that layout where they have one;
        an array already here comes as it is."""
        held = self.__dict__["_" + field]
        return held.resolve(rows_to, whole_first) \
            if isinstance(held, DeferredRows) else held

    @property
    def placed(self) -> bool:
        """Whether the rows are still in a PLACED mirror snapshot, whose
        fused working sets may be stored whole rows first (known without
        reading anything of the rows)."""
        held = self.__dict__["_values"]
        return isinstance(held, DeferredRows) and held.placed

    def whole_first(self):
        """The whole-rows-first layout of the rows still in the mirror
        (MirrorGather.whole_first: (at, Sw), or None)."""
        held = self.__dict__["_values"]
        return held.whole_first() if isinstance(held, DeferredRows) \
            else None


def _resolved_on_read(field: str) -> property:
    """A RawBlock field that may be set to a DeferredRows and reads as its
    array (`phase`: as the host copy of its rows).  Still a dataclass
    field: the constructor, `dataclasses.replace` and the wire formats
    (parallel/serialize.py, streams.py) see the name."""
    slot = "_" + field

    def get(self):
        held = self.__dict__[slot]
        if not isinstance(held, DeferredRows):
            return held
        return held.host() if field == "phase" else held.resolve()

    def put(self, value):
        self.__dict__[slot] = value

    return property(get, put)


for _field in ("ts_off", "values", "vbase", "phase"):
    setattr(RawBlock, _field, _resolved_on_read(_field))


# Fused-leaf caches (see MultiSchemaPartitionsExec._build_fused).  Each is
# a _FusedCache: a dict in LRU order (oldest first), bounded by BYTES alone,
# so that it holds what a chip's deployment holds: one padded working set
# and a few groupings for every shard and selector value the traffic
# touches, however many that is.  The VALUES and GROUP caches are keyed by
# (mirror serial, snapshot gen, column, rows) so any ingest naturally
# misses, and a new generation's first insert drops its mirror's older
# entries.  The VALUES cache holds the full padded device copies — shared
# across grouping variants (they depend only on the working set); this HBM
# lives outside the DeviceMirror's own hbm_limit_bytes accounting.  The
# GROUP cache holds the small per-grouping gid arrays.  The PLAN cache is
# keyed by what a plan is built from (the shared timestamp row, the grid,
# the window, the base) and by nothing of the shard, so the leaves of a
# request and the panels of an open share one build.


class _FusedCache(dict):
    """key -> entry, least recently used first.  Every method that reads
    or writes is called under _FUSED_CACHE_LOCK.  `nbytes(entry)` weighs
    an entry, `budget()` is the most the cache may hold; the entry just
    added always stays.  Books fused_cache_lookups_total{cache, result},
    fused_cache_evictions_total{cache, cause} and the gauges
    fused_cache_bytes{cache} / fused_cache_entries{cache}."""

    def __init__(self, name: str, nbytes: Callable[[object], int],
                 budget: Callable[[], int], generations: bool = True):
        super().__init__()
        self.name, self._nbytes, self._budget = name, nbytes, budget
        # whether keys start (mirror serial, snapshot generation, ...)
        self._generations = generations

    def lookup(self, key):
        """The entry, moved to the newest end; None on a miss."""
        from filodb_tpu.utils.metrics import registry
        val = _lru_touch(self, key)
        registry.counter("fused_cache_lookups", cache=self.name,
                         result="miss" if val is None else "hit").increment()
        return val

    def insert(self, key, val):
        """Add an entry and return the one the cache holds: a key that
        another thread filled since the caller's miss keeps ITS entry, so
        concurrent builders of one key end up sharing one object (the
        leaves of a request ride one device call only while they hold
        the same plan object: query/fusedbatch.py).  Where keys carry
        generations, first drop the
        entries of the same mirror (key[0]) under another snapshot
        generation (key[1]): each pins device arrays that nothing can ask
        for again.  Then the oldest entries go until the cache fits its
        budget."""
        from filodb_tpu.utils.metrics import registry
        held = self.get(key)
        if held is not None:
            return held
        if self._generations:
            stale = [k for k in self if k[0] == key[0] and k[1] != key[1]]
            for k in stale:
                del self[k]
            if stale:
                registry.counter("fused_cache_evictions", cache=self.name,
                                 cause="generation").increment(len(stale))
        self[key] = val
        held = sum(self._nbytes(v) for v in self.values())
        budget, evicted = self._budget(), 0
        while held > budget and len(self) > 1:
            held -= self._nbytes(self.pop(next(iter(self))))
            evicted += 1
        if evicted:
            registry.counter("fused_cache_evictions", cache=self.name,
                             cause="bytes").increment(evicted)
        registry.gauge("fused_cache_bytes", cache=self.name).update(held)
        registry.gauge("fused_cache_entries",
                       cache=self.name).update(len(self))
        return val


def _plan_nbytes(plan) -> int:
    """A plan's weight, taken at insert and so before any enqueue: its
    host arrays and the copies of the call's operands that
    `pf.enqueue_operands` may leave on every local device for as long as
    the plan lives (`pf.FusedPlan.nbytes`), so the cache's budget bounds
    those too."""
    return plan.nbytes


def _vals_nbytes(v) -> int:
    return int(v.vals_p.size * 4 + v.vbase_p.size * 4
               + (0 if v.phase_p is None else v.phase_p.size * 4))


def _groups_nbytes(ent) -> int:
    groups, gkeys = ent
    # the [Sp, 1] gid column, the group sizes, and a group key's labels
    return int(groups.gids_p.size * 4 + groups.gsize.nbytes
               + 256 * len(gkeys))


# NaN-padded device copies for the reduce_window path's end=now shape,
# keyed (working set, t_needed) — small cap: each entry pins a full copy
_FUSED_MINMAX_PAD_CACHE: Dict[Tuple, object] = {}
_FUSED_VALS_CACHE_BYTES: Optional[int] = None    # resolved lazily
_MIRROR_LIMIT_SEEN: Optional[int] = None         # largest live mirror budget


def _note_mirror_limit(limit_bytes: int) -> None:
    """Record the largest DeviceMirror HBM budget actually constructed so
    the fused-cache budget subtracts the REAL mirror share, not just the
    compile-time default (review r3)."""
    global _MIRROR_LIMIT_SEEN, _FUSED_VALS_CACHE_BYTES
    if _MIRROR_LIMIT_SEEN is None or limit_bytes > _MIRROR_LIMIT_SEEN:
        _MIRROR_LIMIT_SEEN = limit_bytes
        _FUSED_VALS_CACHE_BYTES = None   # re-derive on next insert


def _fused_vals_budget() -> int:
    """Byte budget for the padded-values cache.  Configurable via
    FILODB_TPU_FUSED_CACHE_BYTES; otherwise derived from the device's
    reported HBM minus the live mirror budget so mirror + this cache +
    headroom cannot exceed the chip (round-2 review: the old fixed 4 GiB
    ignored the mirror's budget).  Resolved lazily — the backend is
    already initialized by the time the first fused query inserts."""
    global _FUSED_VALS_CACHE_BYTES
    if _FUSED_VALS_CACHE_BYTES is not None:
        return _FUSED_VALS_CACHE_BYTES
    env = os.environ.get("FILODB_TPU_FUSED_CACHE_BYTES")
    if env:
        _FUSED_VALS_CACHE_BYTES = int(env)
        return _FUSED_VALS_CACHE_BYTES
    budget = 4 << 30
    try:
        import jax

        from filodb_tpu.core.devicecache import DEFAULT_HBM_LIMIT_BYTES
        mirror_limit = _MIRROR_LIMIT_SEEN or DEFAULT_HBM_LIMIT_BYTES
        # the budget applies to every local device (sharded mirrors pin
        # each shard's padded copy to its own chip): size it by the
        # smallest one, not by whichever happens to be first
        limit = min((int((d.memory_stats() or {}).get("bytes_limit", 0))
                     for d in jax.local_devices()), default=0)
        if limit:
            budget = min(budget,
                         max(1 << 30, limit - mirror_limit - (2 << 30)))
    except Exception:  # noqa: BLE001 — stats unavailable: keep the default
        pass
    _FUSED_VALS_CACHE_BYTES = budget
    return budget


_FUSED_PLAN_CACHE = _FusedCache("plan", _plan_nbytes, lambda: 16 << 20,
                                generations=False)
_FUSED_VALS_CACHE = _FusedCache("values", _vals_nbytes, _fused_vals_budget)
# a grouping's gid column is a 768th of its working set's padded values:
# a sixteenth of their budget holds a dozen groupings for each of them
_FUSED_GROUP_CACHE = _FusedCache("groups", _groups_nbytes,
                                 lambda: _fused_vals_budget() // 16)
# queries run on HTTP worker threads (http/server.py ThreadingHTTPServer) —
# every cache read-modify-write holds this lock; the kernel runs outside it
_FUSED_CACHE_LOCK = threading.Lock()


_FUSED_VALS_BUILDERS: Dict[Tuple, threading.Lock] = {}   # key -> its lock
_FUSED_PLAN_BUILDERS: Dict[Tuple, threading.Lock] = {}


def fused_values(key, build):
    """The padded working set under `key`, built by ONE of the leaves that
    miss it together: a new snapshot makes every request in flight miss at
    once, and each build takes the working set's rows out of the mirror
    and pads them (two device arrays of its size, 440 MB at 73,000 rows;
    six requests over four shards held 10 GB of a 16 GB chip between them
    for one entry each: PERF.md section 6, PR 42).  The key's lock is held
    around `build()` alone; the others wait there and take what it made."""
    return _built_once(_FUSED_VALS_CACHE, _FUSED_VALS_BUILDERS, key, build)


def fused_plan(key, build):
    """The plan under `key`, built by ONE of the leaves that miss it
    together: the six panels of an open arrive at once, all on a grid no
    plan has been built for, and a plan of 721 windows over 2,304 slots is
    a hundred NumPy calls on arrays long enough to let the interpreter lock
    go at each (44 ms a build beside five other threads, 0.86 builds a
    request: PERF.md section 6, PR 44).  The others wait off the lock."""
    return _built_once(_FUSED_PLAN_CACHE, _FUSED_PLAN_BUILDERS, key, build)


def _built_once(cache: "_FusedCache", builders: dict, key, build):
    with _FUSED_CACHE_LOCK:
        held = cache.get(key)
        if held is not None:
            return held
        lock = builders.setdefault(key, threading.Lock())
    with lock:
        with _FUSED_CACHE_LOCK:
            held = cache.get(key)
        if held is not None:
            return held
        try:
            held = build()
            # (values: a new snapshot generation obsoletes this mirror's
            # older entries, and the insert drops them NOW, not at LRU
            # eviction: each pins a full padded copy of the working set in
            # HBM)
            with _FUSED_CACHE_LOCK:
                return cache.insert(key, held)
        finally:
            with _FUSED_CACHE_LOCK:
                builders.pop(key, None)


class GroupCardinalityError(ValueError):
    """group-by cardinality limit exceeded — a real query error that must
    surface even from the fused fast path (everything else falls back)."""


class QueryError(Exception):
    """Typed query-path failure with a stable machine-readable code — the
    error taxonomy a scatter-gather root surfaces when a node dies
    mid-query (ref: the Akka ask's clean QueryError at the root,
    query/.../exec/PlanDispatcher.scala:31-55).  Codes:

      shard_unavailable — a child dispatch could not reach its shard
          owner (connection refused / reset, e.g. SIGKILL mid-query).
          Retryable: after failover reassigns the shard, a re-planned
          query succeeds (QueryEngine retries once when
          query.dispatch_retries > 0 and a replan hook is wired).
      dispatch_timeout — the remote accepted the plan but no reply
          arrived within the dispatcher timeout (query.ask_timeout_s).
          NOT retried automatically: the remote may still be executing,
          and a re-send would run the query twice.
      remote_failure — the remote executed the plan and returned an
          error (its exception text rides along).  Not retryable here;
          the same plan would fail the same way.
      query_timeout — the query's end-to-end deadline
          (query.default_timeout_s / the `timeout=` request param)
          expired: at an exec-node boundary, while queued in the
          frontend scheduler, or mid-dispatch when the remaining budget
          (not the per-hop ask timeout) bounded the socket wait.  Never
          retried and never dropped-for-partial — the budget is global,
          so continuing cannot produce a timely answer.
      query_canceled — the query's CancellationToken was tripped
          (admin kill via POST /admin/queries/<id>/kill, a client
          disconnect detected mid-query, or a kill frame from the
          coordinator): checked at every exec-node boundary, inside
          the demand-paging loop, and before fused kernel dispatches.
          Never retried, never dropped-for-partial, never cached —
          nobody is waiting for the answer.

    The string form is always "<code>: <detail>", so HTTP/CLI clients
    (and tests) can route on `error.split(':', 1)[0]`."""

    def __init__(self, code: str, detail: str):
        self.code = code
        super().__init__(detail)

    def __str__(self):
        return f"{self.code}: {super().__str__()}"


def _lru_touch(cache: Dict, key) -> object:
    """Get + move-to-back (dicts iterate in insertion order, so eviction
    pops the front = least-recently-used).  One idiom for all fused caches."""
    val = cache.get(key)
    if val is not None:
        cache[key] = cache.pop(key)
    return val


def _group_cache_lookup(key, by, without):
    """Cached (PaddedGroups, gkeys) for this working set + grouping, or
    (None, None).  Pairs with _group_cache_insert — the two halves of the
    group-cache protocol, shared by the kernel and reduce_window paths."""
    if key is None:
        return None, None
    with _FUSED_CACHE_LOCK:
        ent = _FUSED_GROUP_CACHE.lookup(key + (by, without))
    return ent if ent is not None else (None, None)


def _group_cache_insert(key, by, without, groups, gkeys) -> None:
    """Insert a (PaddedGroups, gkeys) entry.  The single home of the
    group-cache write rules — used by both the kernel path and the
    reduce_window path."""
    if key is None:
        return
    with _FUSED_CACHE_LOCK:
        _FUSED_GROUP_CACHE.insert(key + (by, without), (groups, gkeys))


@dataclasses.dataclass
class ScalarResult:
    """One value per step (scalar plans)."""
    wends: np.ndarray                   # int64 [W]
    values: np.ndarray                  # float [W]


@dataclasses.dataclass
class AggPartial:
    """Partial aggregate: mesh-reducible (op-dependent) representation."""
    op: str
    group_keys: List[RangeVectorKey]
    wends: np.ndarray
    comp: Optional[np.ndarray] = None   # [G, W, C] associative component form
    # candidate form (topk/bottomk/quantile/count_values): raw rows
    cand_keys: Optional[List[RangeVectorKey]] = None
    cand_vals: Optional[np.ndarray] = None   # [N, W]
    cand_groups: Optional[np.ndarray] = None  # int [N] -> group_keys index
    params: Tuple = ()
    bucket_les: Optional[np.ndarray] = None  # hist_sum partials
    # quantile(): mergeable centroid sketch [G, W, K, 2] — O(groups) wire
    # cost instead of shipping every candidate series row
    # (ref: QuantileRowAggregator.scala:87 t-digest partials)
    sketch: Optional[np.ndarray] = None
    # working-set identity of the aggregated KEYS — ("agg", op, by,
    # without, source token): group keys are a pure function of the
    # source series set and the grouping, so downstream keys-only caches
    # (the PR 17 binary-join index maps) can reuse resolved matches
    # across dashboard re-polls.  Value-level identity is NOT implied
    # (rate and increase over one working set share a token by design).
    # Process-local like every cache_token — serialize nulls it.
    cache_token: Optional[Tuple] = None


def agg_token(op: str, by, without,
              data_token: Optional[Tuple]) -> Optional[Tuple]:
    """Token for an AggPartial built from a block carrying data_token."""
    if data_token is None:
        return None
    return ("agg", op, tuple(by), tuple(without), data_token)


@dataclasses.dataclass
class HistQuantileAnswer:
    """`histogram_quantile(q, sum by (..)(rate(h[..])))` answered whole by
    the epilogue of the request's one device call (query/fusedbatch.py
    HistQuantileCall): what a `ReduceAggregateExec` whose children's
    bucket sums never left the device hands its presenter, which passes it
    on, and the `InstantVectorFunctionMapper` it was made for, which takes
    `block` as its own result.  None of the three does array work."""
    q: float
    block: ResultBlock


Data = Union[RawBlock, ResultBlock, ScalarResult, AggPartial,
             HistQuantileAnswer, None]


def _block_empty(wends: np.ndarray) -> ResultBlock:
    return ResultBlock([], wends, np.zeros((0, len(wends))))



def _present_comp(op: str, comp: np.ndarray) -> np.ndarray:
    """Merged components [G, W, C] -> final [G, W], NaN where no series
    was present: ops/agg.present in NumPy, on the host, where the merged
    partial already is.  (On the device the same [G, W, C] cost an
    upload, a jit call and a blocking readback behind other requests'
    kernels, for under a microsecond of work, and took the merged f64
    sums through f32.)"""
    if op == "group":
        v = comp[..., 0]
        return np.where(np.isinf(v), np.nan, v)
    c = comp[..., -1]
    if op == "count":
        out = c
    elif op in ("sum", "min", "max"):
        out = comp[..., 0]
    elif op == "avg":
        out = comp[..., 0] / np.maximum(c, 1.0)
    elif op in ("stddev", "stdvar"):
        cs = np.maximum(c, 1.0)
        # inf - inf where a group's samples overflow: NaN on the device too
        with np.errstate(invalid="ignore", over="ignore"):
            out = np.maximum(comp[..., 1] / cs - (comp[..., 0] / cs) ** 2,
                             0.0)
            if op == "stddev":
                out = np.sqrt(out)
    else:
        raise ValueError(op)
    return np.where(c > 0, out, np.nan)


def present_partial(p: AggPartial) -> Optional[ResultBlock]:
    """Finish an AggPartial into a ResultBlock."""
    if p.sketch is not None:
        from filodb_tpu.ops import sketch as sketch_ops
        q = float(p.params[0])
        out = sketch_ops.sketch_quantile(p.sketch, q)
        return ResultBlock(p.group_keys, p.wends, out,
                           cache_token=p.cache_token)
    if p.comp is not None:
        if p.op == "hist_sum":
            # [G, W, B+1] with present-series count in the last slot
            buckets = p.comp[..., :-1]
            present_cnt = p.comp[..., -1]
            out = np.where(present_cnt[..., None] > 0, buckets, np.nan)
            return ResultBlock(p.group_keys, p.wends, out, p.bucket_les,
                               cache_token=p.cache_token)
        return ResultBlock(p.group_keys, p.wends,
                           _present_comp(p.op, p.comp),
                           cache_token=p.cache_token)
    # candidate form
    if p.op in ("topk", "bottomk"):
        k = int(p.params[0])
        gids = p.cand_groups
        mask = np.asarray(agg_ops.topk_mask(
            jnp.asarray(p.cand_vals), jnp.asarray(gids), len(p.group_keys),
            k, largest=(p.op == "topk")))
        vals = np.where(mask, p.cand_vals, np.nan)
        block = ResultBlock(p.cand_keys, p.wends, vals)
        return remove_nan_series(block)
    if p.op == "quantile":
        q = float(p.params[0])
        out = np.asarray(agg_ops.quantile_agg(
            jnp.asarray(p.cand_vals), jnp.asarray(p.cand_groups),
            len(p.group_keys), q))
        return ResultBlock(p.group_keys, p.wends, out)
    if p.op == "count_values":
        label = str(p.params[0])
        vals = p.cand_vals
        out_keys: List[RangeVectorKey] = []
        out_rows: List[np.ndarray] = []
        W = vals.shape[1]
        for g in range(len(p.group_keys)):
            rows = vals[p.cand_groups == g]
            uniq = np.unique(rows[~np.isnan(rows)])
            for v in uniq:
                cnt = np.nansum(rows == v, axis=0).astype(float)
                cnt[cnt == 0] = np.nan
                lbls = dict(p.group_keys[g].labels)
                lbls[label] = f"{v:g}"
                out_keys.append(RangeVectorKey.make(lbls))
                out_rows.append(cnt)
        if not out_keys:
            return None
        return ResultBlock(out_keys, p.wends, np.stack(out_rows))
    raise ValueError(p.op)


def _union_scheme(les_list: List[Optional[np.ndarray]]) -> Optional[np.ndarray]:
    """Union bucket scheme across shards, or None when any shard carries no
    boundaries (widths must then match — checked by the caller's reshape)."""
    from filodb_tpu.memory.histogram import union_les
    known = [l for l in les_list if l is not None]
    if len(known) != len(les_list):
        return None
    out = known[0]
    for l in known[1:]:
        out = union_les(out, l)
    return out


def _align_hist_schemes(parts: List[AggPartial]) -> List[AggPartial]:
    """Rebucket hist_sum partials onto the union scheme so shards whose
    series changed bucket scheme mid-retention still merge
    (ref: HistogramBuckets.scala:340; replaces the fail-loudly behavior)."""
    from filodb_tpu.memory.histogram import rebucket
    les_list = [p.bucket_les for p in parts]
    if any(l is None for l in les_list):
        # boundary-less partials can only merge by width (legacy behavior);
        # order of children must not matter — and any two KNOWN schemes
        # that differ cannot be silently index-merged just because a third
        # partial lacks boundaries
        widths = {p.comp.shape[-1] for p in parts}
        known = [l for l in les_list if l is not None]
        if len(widths) > 1 or any(not np.array_equal(l, known[0])
                                  for l in known[1:]):
            raise ValueError(
                "cannot merge histogram partials of different schemes when "
                "some shards carry no bucket boundaries to re-map by")
        return parts
    if all(np.array_equal(l, les_list[0]) for l in les_list):
        return parts
    union = _union_scheme(les_list)

    def _rebucket_comp(p):
        # comp is [G, W, B+1]: B bucket slots + the present-series count
        B = len(p.bucket_les)
        buckets = rebucket(p.comp[..., :B], p.bucket_les, union)
        return np.concatenate([buckets, p.comp[..., B:]], axis=-1)

    return [dataclasses.replace(p, comp=_rebucket_comp(p), bucket_les=union)
            if not np.array_equal(p.bucket_les, union) else p
            for p in parts]


def _reduced_token(parts: List[AggPartial]) -> Optional[Tuple]:
    """Composite identity of a merged partial: the children's tokens in
    merge order (the merged key order is a pure function of them)."""
    toks = tuple(p.cache_token for p in parts)
    return ("red",) + toks if all(t is not None for t in toks) else None


def reduce_partials(parts: List[AggPartial],
                    compress: bool = True) -> Optional[AggPartial]:
    """Inter-shard reduce (ReduceAggregateExec): merge partials by group key.

    ``compress=False`` is the node-level pushdown mode for quantile
    sketches: the centroid axes are concatenated (zero-weight padded)
    but NOT re-compressed, so the coordinator's single
    ``merge_sketches`` over the node partials sees the same centroid
    multiset — in the same order, since pushdown groups children
    contiguously — as a flat per-shard merge would, making quantile
    pushdown bit-identical to the ship-everything path."""
    parts = [p for p in parts if p is not None]
    if not parts:
        return None
    if parts[0].op == "hist_sum":
        from filodb_tpu.utils.metrics import span
        with span("exec.hist_reduce"):
            return _reduce_aligned(_align_hist_schemes(parts), compress)
    return _reduce_aligned(parts, compress)


# Merge layouts: what _reduce_aligned derives from its children's group
# keys alone (the merged key list, and for every child row its row of the
# merged block), by _reduced_token(parts).  A child's token names its
# working set (keys epoch, row set) and grouping, so a new epoch or row
# set is another key and a stale entry is never asked for again; the
# oldest entries leave beyond _REDUCE_LAYOUTS_MAX (an entry is a tuple of
# keys the partials hold anyway and 8 bytes a child row: 20 KB at 30
# shards of 80 groups).
_REDUCE_LAYOUTS: Dict[Tuple, Tuple] = {}
_REDUCE_LAYOUTS_MAX = 256
_REDUCE_LAYOUT_LOCK = threading.Lock()


def _merge_layout(parts: List[AggPartial], token: Optional[Tuple]
                  ) -> Tuple[List[RangeVectorKey], np.ndarray]:
    """(merged group keys in first-seen order, int64 [sum of the parts'
    groups]: the merged row of every part's every group, parts in child
    order).  Remembered by `token` where there is one; the lock is held
    around the dict's pop and reinsert only, and of two threads that
    miss together the first insert stays."""
    from filodb_tpu.utils.metrics import registry
    held = None
    if token is not None:
        with _REDUCE_LAYOUT_LOCK:
            held = _lru_touch(_REDUCE_LAYOUTS, token)
        registry.counter("reduce_layout",
                         result="miss" if held is None else "hit").increment()
    if held is None:
        gmap: Dict[RangeVectorKey, int] = {}
        index = np.asarray([gmap.setdefault(k, len(gmap))
                            for p in parts for k in p.group_keys], np.int64)
        index.setflags(write=False)
        held = (tuple(gmap), index)
        if token is not None:
            with _REDUCE_LAYOUT_LOCK:
                held = _REDUCE_LAYOUTS.setdefault(token, held)
                while len(_REDUCE_LAYOUTS) > _REDUCE_LAYOUTS_MAX:
                    del _REDUCE_LAYOUTS[next(iter(_REDUCE_LAYOUTS))]
    return list(held[0]), held[1]


def _part_rows(parts: List[AggPartial], index: np.ndarray):
    """(part, the merged rows of its groups), parts in child order."""
    lo = 0
    for p in parts:
        yield p, index[lo:lo + len(p.group_keys)]
        lo += len(p.group_keys)


def _reduce_aligned(parts: List[AggPartial],
                    compress: bool) -> AggPartial:
    op = parts[0].op
    token = _reduced_token(parts)
    gkeys, index = _merge_layout(parts, token)
    wends = parts[0].wends
    if parts[0].sketch is not None:
        # quantile sketches: concat centroid axis per group (zero-weight
        # padding for shards that lack a group), then re-compress to K
        from filodb_tpu.ops import sketch as sketch_ops
        G = len(gkeys)
        W = parts[0].sketch.shape[1]
        M = sum(p.sketch.shape[2] for p in parts)
        cat = np.zeros((G, W, M, 2))
        cat[..., 0] = np.nan
        off = 0
        for p, idx in _part_rows(parts, index):
            m = p.sketch.shape[2]
            cat[idx, :, off:off + m] = p.sketch
            off += m
        return AggPartial(op, gkeys, wends,
                          sketch=(sketch_ops.merge_sketches(cat)
                                  if compress else cat),
                          params=parts[0].params, cache_token=token)
    if parts[0].comp is not None:
        from filodb_tpu.utils.metrics import registry
        C = parts[0].comp.shape[-1]
        W = parts[0].comp.shape[1]
        combs = agg_ops.combiners_for(op, C)
        init = {"sum": 0.0, "min": np.inf, "max": -np.inf}
        ufuncs = {"sum": np.add, "min": np.minimum, "max": np.maximum}
        if op == "hist_sum":
            # Left as PR 31 wrote it, its fill a component included, ON
            # PURPOSE.  Merged as below, the third of
            # histdev-64b-4k.quantiles' requests that group by _ns_ answers
            # 26 ms sooner, the cell serves 8% more requests and its p95
            # falls 28%; but six requests share one interpreter lock, the
            # ungrouped two thirds lose the turns that the grouped ones
            # gave up at every call here, and the cell's MEDIAN, which is
            # theirs, rises from 33 to 41 ms against a bound of 6%
            # (PERF.md section 6, PR 39; section 7 for what would free it)
            out = np.empty((len(gkeys), W, C))
            for i, comb in enumerate(combs):
                out[..., i] = init[comb]
            for p, idx in _part_rows(parts, index):
                np.add.at(out, idx, p.comp)
            calls = len(parts)
        else:
            # every part's rows in child order, merged in ONE call where
            # one combiner serves every component (all ops but min):
            # ufunc.at applies its indices in order, so a cell's f64 sum
            # adds its shards in child order whatever their rows' order in
            # the block they are views of (pf.FusedDispatch sorts its sets
            # by shape).  The calls a request are a constant, not two a
            # shard, and none of them hashes a key on a layout hit
            stacked = np.concatenate([p.comp for p in parts],
                                     dtype=np.float64)
            if len(set(combs)) == 1:
                out = np.full((len(gkeys), W, C), init[combs[0]])
                ufuncs[combs[0]].at(out, index, stacked)
                calls = 1
            else:
                out = np.empty((len(gkeys), W, C))
                for i, comb in enumerate(combs):
                    out[..., i] = init[comb]
                    ufuncs[comb].at(out[..., i], index, stacked[..., i])
                calls = C
        registry.counter("reduce_merge_calls").increment(calls)
        return AggPartial(op, gkeys, wends, comp=out, params=parts[0].params,
                          bucket_les=parts[0].bucket_les, cache_token=token)
    # candidate form: concat and remap groups
    ck: List[RangeVectorKey] = []
    cv: List[np.ndarray] = []
    cg: List[np.ndarray] = []
    for p, idx in _part_rows(parts, index):
        ck.extend(p.cand_keys)
        cv.append(p.cand_vals)
        cg.append(idx[p.cand_groups])
    return AggPartial(op, gkeys, wends,
                      cand_keys=ck, cand_vals=np.concatenate(cv),
                      cand_groups=np.concatenate(cg), params=parts[0].params)


# ---------------------------------------------------------------- exec plans


class AnalyzeRecorder:
    """Per-node resource records for `/api/v1/explain?analyze=true` (the
    EXPLAIN ANALYZE of the exec tree): every locally-executed node
    appends its EXCLUSIVE wall/device/transfer attribution plus the
    cumulative scan counters its subtree produced.  Attach by setting
    `ctx.analyze = AnalyzeRecorder()` on the QueryContext BEFORE
    execution (a plain attribute, deliberately not a dataclass field, so
    remote-dispatched subtrees serialize without it — their spans still
    stitch into the trace; their per-node detail stays on their node)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.by_node: Dict[int, dict] = {}      # id(node) -> record
        self.order: List[dict] = []

    def add(self, node, rec: dict) -> None:
        with self._lock:
            self.by_node[id(node)] = rec
            self.order.append(rec)

    def annotation(self, node) -> str:
        """Tree-line suffix for print_tree(annot=...)."""
        r = self.by_node.get(id(node))
        if r is None:
            return "  [not executed locally]"
        out = ("  [self=%.3fms device=%.3fms transfer=%.3fms "
               "bytes=%d samples=%d series=%d]"
               % (r["self_s"] * 1e3, r["device_s"] * 1e3,
                  r["transfer_s"] * 1e3, r["bytes_transferred"],
                  r["samples_scanned"], r["series_scanned"]))
        if r.get("pushdown"):
            # node-group aggregation pushdown verdict (query/pushdown.py)
            out += f" [pushdown={r['pushdown']}]"
        return out


class PlanDispatcher:
    """ref: exec/PlanDispatcher.scala:20."""

    def dispatch(self, plan: "ExecPlan", source) -> QueryResultLike:
        raise NotImplementedError


QueryResultLike = Tuple[Data, QueryStats]


def fold_exec_tally(stats: QueryStats, total_s: float) -> float:
    """Fold what this thread's exec tally accumulated during `total_s`
    seconds of work into `stats`; returns the exclusive host seconds.
    Nested nodes' wall AND the work's own synchronous device/transfer
    waits are carved out, so the three phase columns (exec/device/
    transfer) partition wall time instead of double-counting it.  One
    home for exec nodes (execute_internal) and for the leaf work the
    engine hoists out of the tree (exprfuse prepare + merged dispatch)."""
    from filodb_tpu.utils.metrics import exec_tally
    self_wall = max(total_s - exec_tally.child_wall
                    - exec_tally.device_s - exec_tally.transfer_s, 0.0)
    stats.cpu_seconds += self_wall
    stats.device_seconds += exec_tally.device_s
    stats.transfer_s += exec_tally.transfer_s
    stats.bytes_transferred += exec_tally.transfer_bytes
    stats.mirror_full_rebuilds += exec_tally.mirror_full
    stats.mirror_incremental += exec_tally.mirror_incremental
    # per-(device, kernel) split of device_seconds (PR 18): folded
    # under a flat "dev|kernel" key so the generic dataclass wire
    # codec ships it unchanged with dispatch replies
    for (dev, kern), cell in exec_tally.device_calls.items():
        key = f"{dev}|{kern}"
        mine = stats.device_calls.get(key)
        if mine is None:
            stats.device_calls[key] = [cell[0], cell[1]]
        else:
            mine[0] += cell[0]
            mine[1] += cell[1]
    return self_wall


class InProcessPlanDispatcher(PlanDispatcher):
    """Run the subtree in-process (ref: exec/InProcessPlanDispatcher.scala:89)."""

    def dispatch(self, plan: "ExecPlan", source) -> QueryResultLike:
        return plan.execute_internal(source)


class ExecPlan:
    """Base execution node.  `execute_internal` returns raw Data + stats;
    `execute` materializes a QueryResult with limits enforced
    (ref: ExecPlan.scala:96-186)."""

    def __init__(self, ctx: Optional[QueryContext] = None):
        self.ctx = ctx or QueryContext()
        self.transformers: List[RangeVectorTransformer] = []
        self.dispatcher: PlanDispatcher = InProcessPlanDispatcher()

    def add_transformer(self, t: RangeVectorTransformer) -> "ExecPlan":
        self.transformers.append(t)
        return self

    @property
    def children(self) -> List["ExecPlan"]:
        return []

    # -- execution

    def _do_execute(self, source) -> QueryResultLike:
        raise NotImplementedError

    def _execute_impl(self, source) -> QueryResultLike:
        data, stats = self._do_execute(source)
        for t in self.transformers:
            data = t.apply(data, self.ctx, stats, source)
        return data, stats

    def execute_internal(self, source) -> QueryResultLike:
        """_execute_impl wrapped in the resource tally: each node's
        EXCLUSIVE wall time (total minus nested nodes') plus whatever
        device/transfer work the thread accumulated while this node ran
        lands in ITS QueryStats — children's contributions arrive via
        stats.merge, so the root totals are exact sums over nodes."""
        from filodb_tpu.utils.metrics import exec_tally, span
        # deadline check at every node boundary: a query past its budget
        # stops HERE instead of fanning out more work (getattr: contexts
        # serialized by an older peer lack the field)
        dl = getattr(self.ctx, "deadline_unix_s", 0.0)
        if dl and _time.time() >= dl:
            raise QueryError(
                "query_timeout",
                f"deadline exceeded at {type(self).__name__} "
                f"(budget expired {_time.time() - dl:.3f}s ago)")
        # cooperative cancellation at the same boundary: a killed query
        # stops HERE instead of fanning out more work (the token is a
        # plain attribute — it never rides the wire; remote nodes mint
        # their own, keyed by query id)
        tok = getattr(self.ctx, "cancel", None)
        if tok is not None and tok.cancelled:
            tok.raise_if_cancelled(f"at {type(self).__name__}")
        snap = exec_tally.snapshot()
        # the node's span is its clock: `exec.<PlanClass>`, every node
        node = span("exec." + type(self).__name__)
        try:
            with node:
                data, stats = self._execute_impl(source)
        except BaseException:
            # attribution on the error path: the parent sees the whole
            # failed subtree as child time, never as its own cpu
            exec_tally.restore(snap, node.dur_s)
            raise
        total = node.dur_s
        self_wall = fold_exec_tally(stats, total)
        rec = getattr(self.ctx, "analyze", None)
        if rec is not None:
            rec.add(self, {
                "plan": type(self).__name__,
                "self_s": self_wall,
                "device_s": exec_tally.device_s,
                "transfer_s": exec_tally.transfer_s,
                "bytes_transferred": exec_tally.transfer_bytes,
                # cumulative over this node's subtree (leaves: own scan)
                "samples_scanned": stats.samples_scanned,
                "series_scanned": stats.series_scanned,
                "shards_queried": stats.shards_queried,
            })
        # live-counter hook (query/activequeries.py): the registry entry
        # riding the context sees this node's contribution IN PLACE —
        # leaves add their scan counters, every node its exclusive
        # device work — so GET /admin/queries shows a query progressing,
        # not just existing
        ent = getattr(self.ctx, "active", None)
        if ent is not None:
            ent.tally(self, stats, exec_tally)
        exec_tally.restore(snap, total)
        return data, stats

    def execute(self, source) -> QueryResult:
        # span + error counters per plan type (ref: ExecPlan.scala:102-131
        # Kamon span around doExecute; query-error counters QueryActor:80-96)
        # bound to the query's trace id, so every span lands in ONE
        # cross-node trace (remote subtrees ship theirs back on the wire)
        from filodb_tpu.utils.metrics import registry, span, trace_context
        try:
            with trace_context(self.ctx.query_id), \
                    span("execplan", hist=True, plan=type(self).__name__):
                data, stats = self.execute_internal(source)
        except QueryError as e:
            # typed taxonomy (shard_unavailable / dispatch_timeout /
            # remote_failure): str(e) already leads with the code
            registry.counter("query_errors",
                             plan=type(self).__name__,
                             code=e.code).increment()
            return QueryResult([], QueryStats(), error=str(e))
        except Exception as e:  # noqa: BLE001 — query errors surface in result
            registry.counter("query_errors",
                             plan=type(self).__name__).increment()
            return QueryResult([], QueryStats(), error=f"{type(e).__name__}: {e}")
        with span("engine.present"):
            if isinstance(data, AggPartial):
                data = present_partial(data)
            if isinstance(data, ScalarResult):
                data = ResultBlock([RangeVectorKey(())], data.wends,
                                   data.values[None, :])
            data = remove_nan_series(data)
            blocks = [data] if data is not None else []
            limit = self.ctx.planner_params.sample_limit
            result_samples = sum(int(np.asarray(b.values).size)
                                 for b in blocks)
        if limit and result_samples > limit:
            return QueryResult([], stats,
                               error=f"sample limit {limit} exceeded "
                                     f"({result_samples} samples)")
        stats.result_samples = result_samples
        stats.result_bytes = sum(int(np.asarray(b.values).nbytes)
                                 for b in blocks)
        if stats.partial:
            # root-level degradation counter (execute() runs once per
            # root; children go through execute_internal)
            registry.counter("query_partial_results").increment()
        return QueryResult(blocks, stats, partial=stats.partial)

    # -- plan printing (ref: ExecPlan.printTree, doc/query-engine.md:174-204)

    def args_str(self) -> str:
        return ""

    def print_tree(self, level: int = 0, annot=None) -> str:
        """annot: optional node -> suffix-string callable (the explain
        analyze mode passes AnalyzeRecorder.annotation)."""
        transf = [f"{'-' * (level + i + 1)}T~{type(t).__name__}({t.args_str()})"
                  for i, t in enumerate(reversed(self.transformers))]
        me = (f"{'-' * (level + len(self.transformers) + 1)}"
              f"E~{type(self).__name__}({self.args_str()})"
              + (annot(self) if annot is not None else ""))
        kids = [c.print_tree(level + len(self.transformers) + 1, annot)
                for c in self.children]
        return "\n".join(transf + [me] + kids)

    def __str__(self):
        return self.print_tree()


class LeafExecPlan(ExecPlan):
    pass


class EmptyResultExec(LeafExecPlan):
    """ref: exec/EmptyResultExec."""

    def _do_execute(self, source) -> QueryResultLike:
        return None, QueryStats()


class NonLeafExecPlan(ExecPlan):
    """Scatter-gather over children via their dispatchers
    (ref: ExecPlan.scala NonLeafExecPlan)."""

    # concat/reduce plans whose children are SAME-SELECTOR per-shard
    # leaves set this True (nonleaf.py): when two children name the same
    # shard — both owners listed during a live handoff window — only the
    # first to answer contributes, so an aggregation can never
    # double-count a shard's samples (replication/handoff.py dedup
    # contract).  Positional plans (BinaryJoin/SetOperator: lhs and rhs
    # legitimately repeat shard numbers) keep it False.
    dedup_shard_children = False

    def __init__(self, ctx: QueryContext, children: Sequence[ExecPlan]):
        super().__init__(ctx)
        self._children = list(children)

    @property
    def children(self) -> List[ExecPlan]:
        return self._children

    def _dedup_groups(self) -> Dict[int, Tuple]:
        """child index -> leaf-identity key, ONLY for children that
        appear more than once (a live-handoff window lists both owners
        of a shard).  Within a group the first child to answer is the
        shard's result; the rest are hot standbys.

        The key is the leaf's FULL identity — plan type, dataset,
        shard, args_str (filters/time range/columns), and the
        transformer chain — never just the shard number: a
        ShardKeyRegexPlanner fan-out legitimately puts two same-shard
        leaves with DIFFERENT selectors under one concat, and deduping
        those would silently drop a shard-key combo's data."""
        if not self.dedup_shard_children:
            return {}
        shards = [getattr(c, "shard", None) for c in self._children]
        listed = [s for s in shards if s is not None]
        if len(set(listed)) == len(listed):
            # no shard twice, so no key twice: the common case pays no
            # identity string a child (32 of them on every request of a
            # chip's share of a 128-shard layout)
            return {}
        by_key: Dict[Tuple, List[int]] = {}
        for i, (c, shard) in enumerate(zip(self._children, shards)):
            if shard is None:
                continue
            key = (type(c).__name__, getattr(c, "dataset", None), shard,
                   c.args_str(),
                   tuple((type(t).__name__, t.args_str())
                         for t in c.transformers))
            by_key.setdefault(key, []).append(i)
        return {i: key for key, idxs in by_key.items()
                if len(idxs) > 1 for i in idxs}

    def child_stream_fold(self, child) -> Optional[Callable]:
        """Factory for an incremental fold of a STREAMED child reply
        (parallel/streams.StreamFold): when non-None, the transport
        hands each row-slice frame to `factory().add(mini_block)` as it
        arrives and returns `.result()` — the child's full block never
        materializes on the coordinator.  Default: None (whole-block
        assembly).  ReduceAggregateExec overrides with its map+reduce
        fold."""
        return None

    def _gather(self, source) -> Tuple[List[Data], QueryStats]:
        stats = QueryStats()
        results = []
        ent = getattr(self.ctx, "active", None)
        if ent is not None:
            ent.set_phase("gathering")
        pp = self.ctx.planner_params
        allow_partial = pp.allow_partial_results
        # shard_unavailable drops only once the ENGINE has engaged
        # degradation (partial_now: re-plan retries exhausted) — so a
        # transient owner death still gets routed around before any data
        # is given up.  A peer blowing its deadline share
        # (dispatch_timeout) drops under the gate alone: retrying cannot
        # help inside the budget.  query_timeout NEVER drops — the
        # budget is global, so the root propagates the structured error.
        droppable = set()
        if allow_partial:
            droppable.add("dispatch_timeout")
            if getattr(pp, "partial_now", False):
                droppable.add("shard_unavailable")
        # handoff-window dedup: when the planner materialized BOTH
        # owners of a shard, the duplicates are hot standbys — only the
        # first to answer contributes (aggregations never double-count a
        # shard), and a standby absorbs its twin's shard_unavailable
        # BEFORE the partial machinery is consulted
        dedup_groups = self._dedup_groups()
        answered: set = set()       # keys already answered
        for i, c in enumerate(self._children):
            key = dedup_groups.get(i)
            if key is not None and key in answered:
                from filodb_tpu.utils.metrics import registry
                registry.counter("query_shard_dedup").increment()
                results.append(None)         # twin already answered
                continue
            has_later_twin = key is not None and any(
                j > i for j, k in dedup_groups.items() if k == key)
            ff = self.child_stream_fold(c)
            if ff is not None:
                # plain attribute, never serialized: the remote side
                # streams row slices and THIS side folds them in place
                c._stream_fold = ff
            try:
                data, st = c.dispatcher.dispatch(c, source)
                if key is not None:
                    answered.add(key)
            except QueryError as e:
                if e.code == "shard_unavailable" and has_later_twin:
                    # this owner is dead but its twin is still listed:
                    # the twin becomes the shard's answer — no partial,
                    # no error, exactly the handoff-window contract
                    results.append(None)
                    continue
                # a dead shard owner mid-query: fail the whole query with
                # the typed error — or, when partial results are engaged,
                # drop the child and FLAG the result (never silent
                # partials; ref: PlanDispatcher.scala:31-55,
                # PlannerParams.allowPartialResults)
                if e.code in droppable:
                    from filodb_tpu.utils.metrics import registry
                    registry.counter("query_partial_children",
                                     plan=type(self).__name__,
                                     code=e.code).increment()
                    stats.partial = True
                    stats.warnings.append(f"shard dropped ({e})")
                    # placeholder, NOT continue: BinaryJoin/SetOperator
                    # split `results` positionally at n_lhs, so a dropped
                    # child must keep its slot (every compose filters by
                    # isinstance, so None contributes nothing)
                    results.append(None)
                    continue
                raise
            stats.merge(st)
            results.append(data)
        return results, stats

    def compose(self, results: List[Data], stats: QueryStats) -> Data:
        raise NotImplementedError

    def _do_execute(self, source) -> QueryResultLike:
        results, stats = self._gather(source)
        return self.compose(results, stats), stats


