"""TimeSeriesShard — all state for one shard.

Rebuild of the reference's shard runtime (ref:
core/.../memstore/TimeSeriesShard.scala:246): partition registry keyed by
partKey bytes, tag index, ingest entry point, flush groups with checkpoint
watermarks, eviction, and partition lookup for query.  The per-partition
write-buffer/chunk machinery is replaced by the dense per-schema
DenseSeriesStore (see blockstore.py) which the TPU kernels consume directly.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import logging
import threading
import time
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

_log = logging.getLogger("filodb.shard")

_SHARD_KEYS_SERIAL = itertools.count(1)  # see TimeSeriesShard.keys_serial

# append_horizon_ms sentinel: "nothing is immutable" (a registered row
# with zero samples accepts arbitrary-time appends).  Shared with the
# query frontend's cache-bypass check — one constant, not two literals.
NO_HORIZON_MS = -(1 << 62)
# ... and of a shard that holds no row at all: there is nothing an append
# could extend, so it bounds nothing (a first series moves the shard's
# index.mutations, which the cache's token carries)
NO_ROWS_HORIZON_MS = 1 << 62
_KEY_RESOLVE_CACHE_MAX = 4               # live key tables per shard (schemas)
_LOOKUP_CACHE_MAX = 32                   # memoized lookup_partitions results
_SELECTION_PARTS_MAX = 16                # ... and masks kept of each of them

import numpy as np

from filodb_tpu.config import FilodbSettings, settings as default_settings
from filodb_tpu.core.blockstore import DenseSeriesStore, estimate_samples
from filodb_tpu.core.index import ColumnFilter, PartKeyIndex, MAX_TIME
from filodb_tpu.core.partkey import PartKey
from filodb_tpu.core.ratelimit import (QuotaReachedException,
                                       TenantBudgetExceeded)
from filodb_tpu.core.records import RecordBatch
from filodb_tpu.core.schemas import Schemas, DEFAULT_SCHEMAS
from filodb_tpu.core.store import (ColumnStore, MetaStore, NullColumnStore,
                                   InMemoryMetaStore, PartKeyRecord)
from filodb_tpu.memory.chunks import ChunkSet, encode_chunksets
from filodb_tpu.memory.histogram import HistogramBuckets
from filodb_tpu.utils.faults import faults
from filodb_tpu.utils.metrics import (registry as metrics_registry,
                                      span as metrics_span)


@dataclasses.dataclass
class PartitionInfo:
    """Lightweight partition record (the TimeSeriesPartition analogue,
    ref: memstore/TimeSeriesPartition.scala:64 — heavy state lives in the
    dense store row)."""
    part_id: int
    part_key: PartKey
    schema_name: str
    row: int                      # row in the schema's DenseSeriesStore
    group: int                    # flush group


@dataclasses.dataclass
class ShardStats:
    """ref: TimeSeriesShardStats (TimeSeriesShard.scala:41)."""
    rows_ingested: int = 0
    partitions_created: int = 0
    rows_dropped: int = 0
    chunks_flushed: int = 0
    flushes: int = 0
    evictions: int = 0
    quota_dropped: int = 0          # series rejected by cardinality quota
    tenant_rejected: int = 0        # series rejected by the per-ws budget


from filodb_tpu.utils.growable import grow_to as _grow_to


class PagedLimitExceeded(ValueError):
    """Demand paging hit the query's scan limit.  A ValueError subclass so
    existing handlers keep working, but typed and self-describing: carries
    how much paging WORK already happened (that work is kept — paged
    chunks are valid cache; `paged_floor`/`paged_ceil` advanced for the
    completed rows) so the query layer can surface a structured
    `paged_limit_exceeded` error instead of a bare 500."""

    def __init__(self, limit: int, samples_paged: int,
                 partitions_paged: int):
        self.limit = limit
        self.samples_paged = samples_paged
        self.partitions_paged = partitions_paged
        super().__init__(
            f"demand paging exceeded the scan limit {limit} after paging "
            f"{samples_paged} samples across {partitions_paged} "
            f"partitions — narrow the filters or time range (the paged "
            f"data is kept warm for a narrower retry)")


_NEVER_MS = int(np.iinfo(np.int64).max)


class SelectionFacts:
    """What a leaf reads off a selection's rows of one store before it
    dispatches, as of one even `store.generation`: every write to what
    they are read from (samples, eviction, paging bookkeeping) lies
    inside `store.mutation()`, so facts whose `generation` is the
    store's are the store's own.  Arrays are frozen."""
    __slots__ = ("generation", "counts", "first", "last", "samples",
                 "uniform", "covered_max", "ceil_min", "_estimated")

    def __init__(self, store: DenseSeriesStore, rows: np.ndarray):
        self.generation = store.generation
        cnt, first, last = store.row_extents(rows)
        for arr in (cnt, first, last):
            arr.setflags(write=False)
        self.counts, self.first, self.last = cnt, first, last
        self.samples = int(cnt.sum())
        self._estimated = None          # estimate(): (start, end, answer)
        # every row with the same count and the same extent from its
        # first to its last timestamp (one scrape grid, each row on it or
        # behind it by its own phase): the estimate is then arithmetic on
        # four scalars.  (count, earliest first, latest first, extent)
        self.uniform = None
        if rows.size and (cnt == cnt[0]).all():
            extent = last - first
            if (extent == extent[0]).all():
                self.uniform = (int(cnt[0]), int(first.min()),
                                int(first.max()), int(extent[0]))
        # ensure_paged_pids' two conditions, each reduced over the rows:
        # a row needs paging below when start < min(paged_floor,
        # first_mem), a page-only row with samples above when
        # end > max(paged_ceil, last_mem)
        has = cnt > 0
        covered = np.minimum(store.paged_floor[rows],
                             np.where(has, first, MAX_TIME))
        self.covered_max = int(covered.max()) if rows.size else -_NEVER_MS
        above = store.page_only[rows] & has
        self.ceil_min = int(np.maximum(store.paged_ceil[rows],
                                       last)[above].min()) \
            if above.any() else _NEVER_MS

    def estimate(self, start_ms: int, end_ms: int) -> int:
        """estimate_samples over these rows.  On uniform rows: its
        formula on one row, in Python, times the row count — a product
        where it sums S equal floats, so the integer may differ by 1."""
        alike = False
        if self.uniform is not None:
            cnt, first, first_hi, extent = self.uniform
            last = first + extent
            # rows behind the grid by a phase: one row stands for all
            # where the range clips every row alike, which is inside the
            # span that all rows cover (a dashboard's range is)
            alike = first_hi == first or (start_ms >= first_hi
                                          and end_ms <= last)
        if not alike:
            # the array formula is 2.2 ms at 73,000 rows, and the panels of
            # a dashboard's open ask for one range: the last answer stands
            held = self._estimated
            if held is None or held[:2] != (start_ms, end_ms):
                held = self._estimated = (start_ms, end_ms, estimate_samples(
                    self.counts, self.first, self.last, start_ms, end_ms))
            return held[2]
        lo, hi = max(first, start_ms), min(last, end_ms)
        if cnt <= 0 or hi < lo:
            return 0
        frac = min(max(float(hi - lo) / float(max(last - first, 1)), 0.0),
                   1.0)
        return int(max(cnt * frac, 1.0) * self.counts.size)

    def may_need_paging(self, start_ms: int, end_ms: int) -> bool:
        return start_ms < self.covered_max or end_ms > self.ceil_min


class SchemaSelection:
    """One schema's series of a lookup, and what the host derives from
    them alone: store rows, and each array's bytes once, so that cache
    keys built from them (the fused leaf's, the host leaf's memo,
    RawBlock.cache_token) hit by identity instead of copying and
    comparing the arrays; `facts` holds the newest SelectionFacts
    (TimeSeriesShard.selection_facts)."""
    __slots__ = ("pids", "rows", "pids_key", "rows_key", "facts", "member")

    def __init__(self, pids: np.ndarray, rows: np.ndarray):
        rows.setflags(write=False)
        self.pids, self.rows = pids, rows
        self.pids_key, self.rows_key = pids.tobytes(), rows.tobytes()
        self.facts: Optional[SelectionFacts] = None
        # PartLookupResult.among: where these series stand in `within`'s
        self.member: Optional[np.ndarray] = None


@dataclasses.dataclass
class PartLookupResult:
    """ref: TimeSeriesShard.scala:212 PartLookupResult.

    Hot paths consume the vectorized pid arrays (pids_by_schema) plus the
    shard's pid->row / pid->key tables; parts_by_schema materializes
    PartitionInfo lists lazily for metadata/maintenance consumers.
    `shared`: the lookup memo hands this object to every request whose
    range holds every selected series' life.  `within`: the memo entry's whole
    answer, where this one is the part of it that a range keeps (the same
    series in the same order, less those whose life the range misses)."""
    shard: int
    part_ids: np.ndarray
    pids_by_schema: Dict[str, np.ndarray]
    first_schema: Optional[str]
    shard_obj: Optional["TimeSeriesShard"] = None
    shared: bool = False
    within: Optional["PartLookupResult"] = None
    _selections: Dict[str, SchemaSelection] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    @property
    def parts_by_schema(self) -> Dict[str, List[PartitionInfo]]:
        parts = self.shard_obj.partitions
        return {s: [parts[p] for p in pids.tolist()]
                for s, pids in self.pids_by_schema.items()}

    def selection(self, schema_name: str) -> SchemaSelection:
        sel = self._selections.get(schema_name)
        if sel is None:
            pids = self.pids_by_schema[schema_name]
            sel = self._selections[schema_name] = SchemaSelection(
                pids, self.shard_obj.rows_for(pids))
        return sel

    def among(self, schema_name: str
              ) -> Optional[Tuple[SchemaSelection, np.ndarray]]:
        """Where this answer is part of a whole one: the whole's selection
        on one schema, and where this part's series stand in it (ascending:
        a part keeps the whole's order).  The fused leaf pads ONE working
        set a shard, the whole's, and drops the rows a range leaves out by
        their group (MultiSchemaPartitionsExec._build_fused)."""
        if self.within is None:
            return None
        sel, whole = self.selection(schema_name), \
            self.within.selection(schema_name)
        if sel.member is None:
            sel.member = np.flatnonzero(np.isin(whole.pids, sel.pids))
            sel.member.setflags(write=False)
        return whole, sel.member


class _Selection:
    """A lookup memo entry: the series (filters, limit) select on this
    shard whatever the range, in part_ids_from_filters' end-time order,
    with their lives; valid while `stamp` is (index.mutations,
    keys_epoch).  `whole` answers every range that holds all the lives;
    `parts` holds the answers of the others by the mask a range lays over
    `ids` (its bytes -> PartLookupResult, least recently used first)."""
    __slots__ = ("stamp", "ids", "start", "end", "starts", "ends",
                 "max_start", "min_end", "whole", "parts")


class TimeSeriesShard:

    def __init__(self, dataset: str, shard_num: int,
                 schemas: Schemas = DEFAULT_SCHEMAS,
                 column_store: Optional[ColumnStore] = None,
                 meta_store: Optional[MetaStore] = None,
                 config: Optional[FilodbSettings] = None):
        self.dataset = dataset
        self.shard_num = shard_num
        self.schemas = schemas
        self.config = config or default_settings()
        self.column_store = column_store or NullColumnStore()
        self.meta_store = meta_store or InMemoryMetaStore()
        self.index = PartKeyIndex()
        self.part_set: Dict[bytes, int] = {}       # partKey bytes -> partId
        self.partitions: List[Optional[PartitionInfo]] = []
        # vectorized pid tables: schema code / store row / liveness per pid,
        # so query-path lookup+gather never loops partitions in Python
        # (the partId->TimeSeriesPartition map equivalent, SoA form)
        self._schema_code_of: Dict[str, int] = {}
        self._schema_names: List[str] = []
        self._pid_schema_code = np.zeros(0, dtype=np.int16)
        self._pid_row = np.zeros(0, dtype=np.int64)
        self._pid_alive = np.zeros(0, dtype=bool)
        self._rv_keys: List[Optional[object]] = []  # cached RangeVectorKeys
        # identity for downstream per-working-set caches (host group-id
        # cache, transformers._group_ids): process-unique serial (ids are
        # reused after GC; tests rebuild memstores with the same dataset
        # name) + an epoch that bumps whenever a pid's cached key mapping
        # is invalidated (tombstone reclaim can recycle pids)
        self.keys_serial = next(_SHARD_KEYS_SERIAL)
        self.keys_epoch = 0
        # key-table resolution cache: streaming sources reuse one part_keys
        # list across batches (the broker/generator key-table pattern), so
        # per-batch key->pid resolution collapses to one dict hit instead
        # of an O(K) Python loop.  id -> (list ref, pids, epoch, schema);
        # the pinned list ref both validates identity (ids are reused
        # after GC) and bounds the cache to _KEY_RESOLVE_CACHE_MAX tables
        self._key_resolve_cache: Dict[int, tuple] = {}
        # lookup_partitions memo (see its docstring): (filters, limit) ->
        # _Selection, stamped with index.mutations + keys_epoch
        self._lookup_cache: Dict[tuple, _Selection] = {}
        self._lookup_lock = threading.Lock()
        self.stores: Dict[str, DenseSeriesStore] = {}
        # compressed resident tier: sealed chunks kept encoded in host RAM
        # so the dense tier holds only the active tail (memory/resident.py)
        from filodb_tpu.memory.resident import ResidentChunkCache
        self.resident = ResidentChunkCache(
            self.config.store.resident_cache_bytes, dataset, shard_num,
            persistent=not isinstance(self.column_store, NullColumnStore))
        self.stats = ShardStats()
        # per-tenant (_ws_/_ns_) ingest attribution (utils/usage.py):
        # pid -> small tenant id resolved once at partition creation, so
        # the hot ingest paths pay ONE vectorized bincount per batch
        self._usage_enabled = self.config.query.tenant_usage_enabled
        # per-workspace alive-series counts backing the
        # index.tenant_series_limit cardinality budget (0 = off).
        # Internal workspaces (_rules_, _self_) and _ws_-less series are
        # exempt from the gate but still counted when present.
        self._tenant_series_limit = self.config.index.tenant_series_limit
        self._ws_series: Dict[str, int] = {}
        self._pid_tenant = np.zeros(0, dtype=np.int32)
        self._tenant_ids: Dict[Tuple[str, str], int] = {}
        self._tenant_names: List[Tuple[str, str]] = []
        self.ingested_offset = -1                   # latest ingest offset seen
        self._groups = self.config.store.groups_per_shard
        self._dirty_part_keys: set = set()          # partIds needing pk upsert
        # optional streaming downsampler fed at flush (ref:
        # ShardDownsampler.scala:103 populateDownsampleRecords at doFlushSteps)
        self.shard_downsampler = None
        # optional cardinality tracker enforcing quotas at series creation
        # (ref: TimeSeriesShard cardTracker, ratelimit/CardinalityTracker)
        self.cardinality_tracker = None
        # trace-filter logging of individual series: partitions whose labels
        # match ALL filters of any filter group get lifecycle log lines at
        # creation, ingest and query lookup (ref: tracedPartFilters,
        # README:871-875; TimeSeriesShard.scala:265).  Set via
        # set_traced_filters (list of label maps) or POST
        # /admin/tracedfilters; traced pids are tracked as a set so the
        # ingest/query hot paths pay one membership test.
        self.traced_part_filters: List[Tuple[str, str]] = []
        self._traced_groups: List[Dict[str, str]] = []
        self._traced_pids: set = set()
        # Writer mutex: ingest / flush / ODP page-in / eviction serialize
        # here (the reference serializes these on the shard's ingestion
        # dispatcher, ref: TimeSeriesShard.scala ingestSched + EvictionLock).
        # Queries do NOT take it — they use snapshot_read's seqlock retry
        # against DenseSeriesStore.generation, so reads stay lock-free
        # unless a writer is mid-mutation.  Acquire through _write_locked
        # for stall logging (the ChunkMap lock-stall detection analogue,
        # ref: memory/.../data/ChunkMap.scala:24-38).
        self.write_lock = threading.RLock()
        # flush-vs-flush mutex only: serializes concurrent flush_group
        # calls (downsampler state, store write ordering) WITHOUT holding
        # the shard write_lock across the expensive encode+persist phase
        self._flush_lock = threading.Lock()
        # per-partition newest-downsampled timestamp (flush-thread only,
        # under _flush_lock): dedupes downsample emission when a
        # shift-skipped seal makes a flush re-read an unsealed range
        self._ds_time_wm: Dict[int, int] = {}
        # flush-group membership maintained at creation so a group flush
        # walks only its own partitions, not all of them
        self._group_pids: List[List[int]] = [[] for _ in range(self._groups)]
        # write-buffer batching state (min_flush_samples): consecutive
        # rounds each group skipped small partitions, and the last offset
        # at which the group was FULLY persisted (the only offset its
        # checkpoint may claim — a skipped partition's samples are not on
        # disk yet, and replay-past-them would lose data)
        self._group_skip_rounds: List[int] = [0] * self._groups
        self._group_ckpt_offset: Dict[int, int] = {}
        # deferred tombstone reclamation queue: (evicted_at, pid).  Evicted
        # partitions keep their PartitionInfo for a grace period so lock-free
        # readers holding the pid can still resolve it; flush prunes entries
        # past the grace window under write_lock (two-phase reclamation).
        # A deque: mass-expiry pushes 100k+ entries and list.pop(0) would
        # make the prune quadratic
        self._evicted_tombstones: collections.deque = collections.deque()
        # overlap flag for latency attribution (bench/stress soaks tag
        # each recorded query with it): True while an eviction sweep or
        # memory enforcement is tearing down partitions / shifting rows
        self.eviction_in_progress = False
        # append_horizon_ms memo: store name -> (generation, horizon)
        self._horizon_memo: Dict[str, tuple] = {}

    # --------------------------------------------------------------- locking

    @contextlib.contextmanager
    def _write_locked(self, what: str, warn_after_s: float = 10.0):
        """write_lock acquisition with stall detection: a writer waiting
        past `warn_after_s` logs who is stalled and counts a metric before
        blocking on, so operators see lock contention instead of silent
        latency (ref: ChunkMap.scala:24-38 lock-stall logging)."""
        if not self.write_lock.acquire(timeout=warn_after_s):
            _log.warning(
                "write_lock stall: %s waited >%.0fs on shard %d — another "
                "writer (flush/ingest/paging/eviction) is holding it",
                what, warn_after_s, self.shard_num)
            metrics_registry.counter(
                "write_lock_stalls", dataset=self.dataset,
                shard=str(self.shard_num)).increment()
            self.write_lock.acquire()
        try:
            yield
        finally:
            self.write_lock.release()

    # ------------------------------------------------------------------ ingest

    def group_for(self, part_key: PartKey) -> int:
        """Stable flush-group assignment from the partKey hash."""
        return part_key.partition_hash() % self._groups

    def _store_for(self, schema_name: str) -> DenseSeriesStore:
        store = self.stores.get(schema_name)
        if store is None:
            store = DenseSeriesStore(self.schemas[schema_name])
            self.stores[schema_name] = store
        return store

    def schema_of(self, part_key: PartKey) -> Optional[str]:
        """Schema name of the partition this shard holds for `part_key`,
        None when it holds none (partition identity is the key alone)."""
        pid = self.part_set.get(part_key.to_bytes())
        return None if pid is None else self.partitions[pid].schema_name

    def get_or_create_partition(self, part_key: PartKey, schema_name: str,
                                start_time_ms: int) -> PartitionInfo:
        """ref: TimeSeriesShard.getOrAddPartitionAndIngest:1249 +
        createNewPartition:1301 (partId assignment + index add)."""
        kb = part_key.to_bytes()
        pid = self.part_set.get(kb)
        if pid is not None:
            return self.partitions[pid]
        if self.cardinality_tracker is not None:
            # raises QuotaReachedException before any state is touched
            # (ref: TimeSeriesShard.createNewPartition quota protocol)
            sk = part_key.shard_key(self.schemas.part)
            self.cardinality_tracker.series_created(
                tuple(sk.get(c, "") for c in
                      self.schemas.part.options.shard_key_columns))
        ws = ""
        if self._tenant_series_limit:
            # per-tenant cardinality budget: raises BEFORE any state is
            # touched, like the quota protocol above.  _ws_-less and
            # internal (_rules_/_self_) series are exempt, matching the
            # usage scan-limit exemptions.
            from filodb_tpu.utils.usage import INTERNAL_WORKSPACES
            ws = part_key.tags_dict.get("_ws_", "")
            if ws and ws not in INTERNAL_WORKSPACES:
                alive = self._ws_series.get(ws, 0)
                if alive >= self._tenant_series_limit:
                    self.stats.tenant_rejected += 1
                    metrics_registry.counter(
                        "tenant_series_rejected", dataset=self.dataset,
                        ws=ws).increment()
                    raise TenantBudgetExceeded(
                        ws, self._tenant_series_limit, alive)
        pid = len(self.partitions)
        store = self._store_for(schema_name)
        # group from the stable partKey hash, NOT partId: replay filtering by
        # group checkpoint must survive restart where partIds are reassigned
        # (ref: TimeSeriesShard.scala group = partKeyGroup(hash))
        info = PartitionInfo(pid, part_key, schema_name, store.new_row(),
                             group=self.group_for(part_key))
        self.partitions.append(info)
        code = self._schema_code_of.get(schema_name)
        if code is None:
            code = len(self._schema_names)
            self._schema_code_of[schema_name] = code
            self._schema_names.append(schema_name)
        n = pid + 1
        self._pid_schema_code = _grow_to(self._pid_schema_code, n)
        self._pid_row = _grow_to(self._pid_row, n)
        self._pid_alive = _grow_to(self._pid_alive, n, fill=False)
        self._pid_schema_code[pid] = code
        self._pid_row[pid] = info.row
        self._pid_alive[pid] = True
        self._pid_tenant = _grow_to(self._pid_tenant, n)
        if self._usage_enabled:
            tags = part_key.tags_dict
            tk = (tags.get("_ws_", ""), tags.get("_ns_", ""))
            tid = self._tenant_ids.get(tk)
            if tid is None:
                tid = self._tenant_ids[tk] = len(self._tenant_names)
                self._tenant_names.append(tk)
            self._pid_tenant[pid] = tid
        self._rv_keys.append(None)
        self._group_pids[info.group].append(pid)
        self.part_set[kb] = pid
        self.index.add_partition(pid, part_key, start_time_ms)
        self._dirty_part_keys.add(pid)
        if self._tenant_series_limit:
            if not ws:
                ws = part_key.tags_dict.get("_ws_", "")
            if ws:
                self._ws_series[ws] = self._ws_series.get(ws, 0) + 1
        self.stats.partitions_created += 1
        if self.traced_part_filters or self._traced_groups:
            if self._trace_match(part_key):
                self._traced_pids.add(pid)
                _log.info("TRACED series created: shard=%d partId=%d %s",
                          self.shard_num, pid, part_key)
        return info

    # ------------------------------------------- per-series debug follow

    def _trace_match(self, part_key: PartKey) -> bool:
        labels = {**part_key.tags_dict, "_metric_": part_key.metric}
        if self.traced_part_filters and \
                all(labels.get(k) == v
                    for k, v in self.traced_part_filters):
            return True
        return any(all(labels.get(k) == v for k, v in grp.items())
                   for grp in self._traced_groups)

    def set_traced_filters(self, groups) -> int:
        """groups: list of {label: value} maps; a series matching ALL
        labels of ANY map is debug-followed through creation, ingest and
        query lookup (ref: README.md:871-875 tracedPartFilters).  []
        clears.  Returns the number of currently-matching partitions.
        Takes the write lock: the scan must not race partition creation
        (a series created mid-scan would be dropped by the overwrite)."""
        with self._write_locked("traced_filters"):
            self._traced_groups = [dict(g) for g in groups]
            pids = set()
            if self._traced_groups:
                for info in self.partitions:
                    if info is not None and self._trace_match(info.part_key):
                        pids.add(info.part_id)
                        _log.info("TRACED series matched filter: shard=%d "
                                  "partId=%d %s", self.shard_num,
                                  info.part_id, info.part_key)
            self._traced_pids = pids
            return len(pids)

    def _trace_touch(self, what: str, pids, extra: str = "") -> None:
        if not self._traced_pids:
            return
        hit = self._traced_pids.intersection(
            pids if isinstance(pids, (list, set))
            else np.asarray(pids).tolist())
        for pid in sorted(hit):
            info = self.partitions[pid]
            _log.info("TRACED series %s: shard=%d partId=%d %s%s",
                      what, self.shard_num, pid,
                      info.part_key if info is not None else "?", extra)
            metrics_registry.counter(
                "traced_series_events", dataset=self.dataset,
                event=what).increment()

    def ingest(self, batch: RecordBatch, offset: int = -1) -> int:
        """Ingest one record batch (ref: TimeSeriesShard.ingest:570).
        Returns number of samples ingested.  Thread-safe: serialized with
        flush/eviction/paging via write_lock; concurrent queries read
        through the seqlock (snapshot_read)."""
        faults.fire("ingest.batch")
        with self._write_locked("ingest"):
            return self._ingest(batch, offset)

    def _resolve_key_table(self, pk_list, schema_name: str) -> list:
        """Cached key-table -> pid resolution entry [pk_list, pids, epoch,
        schema, grid_ok] (pid entries -1 until a partition exists).
        Cached per key-table identity: streaming sources reuse one
        part_keys list across batches, so steady-state ingest skips the
        O(K) Python loop entirely.  pids are cached, not rows:
        memory-pressure compaction remaps rows, and _pid_row picks that
        up per batch; evictions bump keys_epoch, invalidating the cache
        before a dead pid could be written to.  grid_ok memoizes the
        all-pids-distinct check the rectangular append path needs (a
        duplicate part key would alias two rows onto one pid)."""
        nk = len(pk_list)
        cache = self._key_resolve_cache
        ent = cache.get(id(pk_list))
        if (ent is not None and ent[0] is pk_list
                and ent[2] == self.keys_epoch
                and ent[3] == schema_name and len(ent[1]) == nk):
            cache[id(pk_list)] = cache.pop(id(pk_list))   # LRU touch
            return ent
        ent = [pk_list, np.full(nk, -1, dtype=np.int64), self.keys_epoch,
               schema_name, None]
        cache[id(pk_list)] = ent
        while len(cache) > _KEY_RESOLVE_CACHE_MAX:
            cache.pop(next(iter(cache)))
        return ent

    @staticmethod
    def _grid_rows_ok(ent: list) -> bool:
        """True when the entry's resolved pids are pairwise distinct (the
        append_grid precondition).  Fully-resolved tables memoize the
        verdict; tables with quota holes (-1 slots) are re-checked on the
        kept subset per batch — rare, and still vectorized."""
        pids = ent[1]
        if (pids < 0).any():
            kept = pids[pids >= 0]
            return bool(np.unique(kept).size == kept.size)
        if ent[4] is None:
            ent[4] = bool(np.unique(pids).size == pids.size)
        return ent[4]

    def _create_missing(self, pk_list, schema_name: str,
                        pids_for_key: np.ndarray, need: np.ndarray,
                        first_ts) -> None:
        """Create partitions for key indices `need` whose pid slot is -1.
        Python work is per NEW SERIES only (index + registry insertion are
        inherently per-object); steady-state batches resolve everything
        from the cache and never reach here.  `first_ts` maps key index ->
        first sample time (dict or array)."""
        for k in need.tolist():
            try:
                info = self.get_or_create_partition(
                    pk_list[k], schema_name, int(first_ts[k]))
            except QuotaReachedException:
                # quota-rejected series: drop its records, count them
                # (ref: TimeSeriesShard ingest QuotaReachedException
                # handling); retried per batch, so a later quota raise
                # admits the series — the pid slot stays -1 until then
                self.stats.quota_dropped += 1
                continue
            pids_for_key[k] = info.part_id

    @staticmethod
    def _grid_samples(batch: RecordBatch) -> int:
        """k if the batch is GRID-shaped — part_idx == repeat(arange(nk), k)
        — else 0.  Two vectorized comparison passes, far cheaper than the
        argsort/cumcount the flat path would spend on the same records."""
        nk = len(batch.part_keys)
        n = batch.num_records
        if nk == 0 or n % nk:
            return 0
        k = n // nk
        pi = batch.part_idx
        if k == 0 or pi[0] != 0 or pi[-1] != nk - 1:
            return 0
        pm = pi.reshape(nk, k)
        if not np.array_equal(pm[:, 0],
                              np.arange(nk, dtype=pm.dtype)):
            return 0
        if k > 1 and not (pm[:, 1:] == pm[:, :1]).all():
            return 0
        return k

    def _ingest(self, batch: RecordBatch, offset: int = -1) -> int:
        if batch.num_records == 0:
            return 0
        store = self._store_for(batch.schema.name)
        # map batch-local part indices -> pids (create partitions on miss);
        # only keys actually referenced by records get partitions — a
        # routed sub-batch carries the full key list but only this shard's
        # rows (ref: TimeSeriesShard.getOrAddPartitionAndIngest:1249 creates
        # per ingest record, never per container key table entry).
        pk_list = batch.part_keys
        ent = self._resolve_key_table(pk_list, batch.schema.name)
        pids_for_key = ent[1]
        grid_k = self._grid_samples(batch)
        if grid_k:
            # grid batch: every key is referenced exactly k times in order,
            # so resolution needs no np.unique and the store write is a
            # rectangular scatter (append_grid) — no per-sample index math
            ts2d = batch.timestamps.reshape(-1, grid_k)
            unresolved = np.flatnonzero(pids_for_key < 0)
            if unresolved.size:
                # first_ts is indexed by KEY INDEX inside _create_missing,
                # so hand over the full first-sample column — a subsetted
                # array would misalign when unresolved keys are a
                # non-prefix subset (quota-hole retries)
                self._create_missing(pk_list, batch.schema.name,
                                     pids_for_key, unresolved, ts2d[:, 0])
            if not self._grid_rows_ok(ent):
                grid_k = 0             # duplicate keys: flat path below
        if grid_k:
            if self._traced_pids:
                self._trace_touch_resolved(pids_for_key, offset)
            keep = pids_for_key >= 0
            rows = self._pid_row[pids_for_key[keep]] if keep.any() \
                else np.zeros(0, dtype=np.int64)
            dropped_keys = int((~keep).sum())
            if dropped_keys:
                self.stats.rows_dropped += dropped_keys * grid_k
                ts2d = ts2d[keep]
            cols2d = {c: v.reshape((len(pk_list), grid_k) + v.shape[1:])[keep]
                      for c, v in batch.columns.items()} if dropped_keys \
                else {c: v.reshape((len(pk_list), grid_k) + v.shape[1:])
                      for c, v in batch.columns.items()}
            n = store.append_grid(rows, ts2d, cols2d, batch.bucket_les)
            self.stats.rows_ingested += n
            self.stats.rows_dropped += ts2d.size - n
            metrics_registry.counter("ingested_rows", dataset=self.dataset,
                                     shard=str(self.shard_num)).increment(n)
            self._account_ingest(pids_for_key[keep], grid_k)
            if offset >= 0:
                self.ingested_offset = offset
            return n
        uniq, first = np.unique(batch.part_idx, return_index=True)
        unresolved = uniq[pids_for_key[uniq] < 0]
        if unresolved.size:
            first_ts = dict(zip(uniq.tolist(),
                                batch.timestamps[first].tolist()))
            self._create_missing(pk_list, batch.schema.name, pids_for_key,
                                 unresolved, first_ts)
        if self._traced_pids:
            touched = pids_for_key[uniq]
            traced_touched = [int(p) for p in touched[touched >= 0].tolist()
                              if int(p) in self._traced_pids]
            if traced_touched:
                self._trace_touch("ingest", traced_touched,
                                  extra=f" offset={offset}")
        pid_sel = pids_for_key[batch.part_idx]
        if self._pid_row.size == 0:        # every key quota-dropped
            rows = np.full(pid_sel.shape, -1, dtype=np.int64)
        else:
            rows = np.where(pid_sel >= 0,
                            self._pid_row[np.clip(pid_sel, 0, None)], -1)
        keep = rows >= 0
        if not keep.all():
            dropped = int((~keep).sum())
            self.stats.rows_dropped += dropped
            rows = rows[keep]
            batch = RecordBatch(batch.schema, batch.part_keys,
                                batch.part_idx[keep], batch.timestamps[keep],
                                {k: v[keep] for k, v in batch.columns.items()},
                                batch.bucket_les)
        n = store.append_batch(rows, batch.timestamps, batch.columns,
                               batch.bucket_les)
        self.stats.rows_ingested += n
        self.stats.rows_dropped += batch.num_records - n
        metrics_registry.counter("ingested_rows", dataset=self.dataset,
                                 shard=str(self.shard_num)).increment(n)
        self._account_ingest(pid_sel[keep], 1)
        if offset >= 0:
            self.ingested_offset = offset
        return n

    def _account_ingest(self, pids: np.ndarray, samples_per_key) -> None:
        """Per-tenant ingest attribution: one vectorized bincount over
        the batch's tenant ids.  `samples_per_key` is a scalar (grid
        paths: every key gained k cells) or a per-entry weight array.
        Counts OFFERED samples on the kept keys — the tenant asked for
        that ingest work whether or not OOO/dup rows were dropped."""
        if not self._usage_enabled or pids.size == 0 \
                or not self._tenant_names:
            return
        from filodb_tpu.utils.usage import usage
        tids = self._pid_tenant[pids]
        n_t = len(self._tenant_names)
        if np.ndim(samples_per_key) == 0:
            cnt = np.bincount(tids, minlength=n_t) * samples_per_key
        else:
            cnt = np.bincount(tids, weights=samples_per_key, minlength=n_t)
        for tid in np.flatnonzero(cnt):
            ws, ns = self._tenant_names[tid]
            usage.record_ingest(ws, ns, int(cnt[tid]), dataset=self.dataset)

    def _trace_touch_resolved(self, pids_for_key: np.ndarray,
                              offset: int) -> None:
        touched = pids_for_key[pids_for_key >= 0]
        traced = [int(p) for p in touched.tolist()
                  if int(p) in self._traced_pids]
        if traced:
            self._trace_touch("ingest", traced, extra=f" offset={offset}")

    def ingest_columns(self, schema_name: str, part_keys,
                       ts: np.ndarray, columns: Dict[str, np.ndarray],
                       offset: int = -1,
                       bucket_les: Optional[np.ndarray] = None) -> int:
        """Columnar ingest fast path: `ts` [S, k] and each column [S, k]
        (or [S, k, B]) where row i belongs to part_keys[i].  The natural
        shape of a scrape cycle — every series gains the same k samples —
        lands in the per-schema SoA store as rectangular slice writes with
        no flatten/re-sort round trip through a RecordBatch.  Semantically
        identical to ingest() of the equivalent flat batch (see
        tests/test_ingest_columnar.py for the enforced equivalence)."""
        ts = np.asarray(ts)
        if ts.ndim != 2 or len(part_keys) != ts.shape[0]:
            raise ValueError("ingest_columns: ts must be [num_keys, k]")
        faults.fire("ingest.batch")
        # write-path trace: the memstore-visibility stage of an ingest
        # batch (one span per slab; stitches under the door's trace id)
        with metrics_span("ingest_columns", hist=True, dataset=self.dataset), \
                self._write_locked("ingest"):
            if ts.size == 0:
                return 0
            store = self._store_for(schema_name)
            ent = self._resolve_key_table(part_keys, schema_name)
            pids_for_key = ent[1]
            unresolved = np.flatnonzero(pids_for_key < 0)
            if unresolved.size:
                # full first-sample column: _create_missing indexes it by
                # key index (see the grid path in _ingest)
                self._create_missing(part_keys, schema_name, pids_for_key,
                                     unresolved, ts[:, 0])
            if not self._grid_rows_ok(ent):
                # duplicate part keys: flatten to the per-record path,
                # which cumcounts duplicate rows correctly
                from filodb_tpu.core.records import RecordBatch
                flat = RecordBatch.from_grid(self.schemas[schema_name],
                                             list(part_keys), ts, columns,
                                             bucket_les)
                return self._ingest(flat, offset)
            if self._traced_pids:
                self._trace_touch_resolved(pids_for_key, offset)
            keep = pids_for_key >= 0
            if keep.all():
                rows = self._pid_row[pids_for_key]
            else:
                self.stats.rows_dropped += int((~keep).sum()) * ts.shape[1]
                rows = self._pid_row[pids_for_key[keep]]
                ts = ts[keep]
                columns = {c: v[keep] for c, v in columns.items()}
            n = store.append_grid(rows, ts, columns, bucket_les)
            self.stats.rows_ingested += n
            self.stats.rows_dropped += ts.size - n
            metrics_registry.counter("ingested_rows", dataset=self.dataset,
                                     shard=str(self.shard_num)).increment(n)
            self._account_ingest(pids_for_key[keep], ts.shape[1])
            if offset >= 0:
                self.ingested_offset = offset
            return n

    # ------------------------------------------------------------------- flush

    def flush_group(self, group: int, ingestion_time_ms: Optional[int] = None,
                    min_samples: int = 0) -> int:
        """Seal + persist unsealed samples for one flush group, then commit the
        group checkpoint (ref: TimeSeriesShard.doFlushSteps:969,
        writeChunks:1072, commitCheckpoint:1127).  Returns chunks written.

        min_samples > 0 (the background scheduler's path) batches like the
        reference's write buffers: partitions with fewer unsealed samples
        are left to accumulate — fewer, bigger chunks, and per-chunk
        encode/persist overhead stops throttling ingest.  The group's
        checkpoint then only advances on fully-persisted rounds, and a
        group force-seals after 8 consecutive skipping rounds so the
        replay window stays bounded.  Direct calls (tests, final flush,
        memory enforcement) default to sealing everything."""
        ingestion_time_ms = ingestion_time_ms or int(time.time() * 1000)
        # Flushes serialize against EACH OTHER here (downsampler state,
        # store writes), but hold the shard write_lock only for the brief
        # copy and seal phases — encode + persist + downsample run with
        # ingest and queries live.  The old whole-flush write_lock held
        # it >10 s per group at 131k series (soak-measured stall).
        with self._flush_lock:
            with metrics_span("flush", hist=True, dataset=self.dataset):
                written = self._do_flush_group(group, ingestion_time_ms,
                                               min_samples)
        metrics_registry.counter("chunks_flushed",
                                 dataset=self.dataset).increment(written)
        return written

    def _prune_tombstones(self, grace_s: float = 60.0,
                          max_prune: int = 8192) -> int:
        """Reclaim evicted partitions past the grace window (caller holds
        write_lock).  After grace_s no realistic in-flight query still holds
        the pid, so the PartitionInfo / cached key / group membership can be
        freed — otherwise high series churn grows them without bound.
        At most `max_prune` per call: the prune runs inside flush's
        lock-held copy phase, so one call must stay bounded; the next
        flush continues the drain."""
        if not self._evicted_tombstones:
            return 0
        cutoff = time.time() - grace_s
        pruned = []
        while (self._evicted_tombstones
               and self._evicted_tombstones[0][0] <= cutoff
               and len(pruned) < max_prune):
            _, pid = self._evicted_tombstones.popleft()
            info = self.partitions[pid]
            if info is not None:
                glist = self._group_pids[info.group]
                try:
                    glist.remove(pid)
                except ValueError:
                    pass
            self.partitions[pid] = None
            self._rv_keys[pid] = None
            pruned.append(pid)
        if pruned:
            # pids may be recycled from here on — invalidate any cache
            # keyed on (keys_serial, keys_epoch, pids)
            self.keys_epoch += 1
            self._key_resolve_cache.clear()
        return len(pruned)

    def _encode_blocks(self, blocks, n: int, ingestion_time_ms: int) -> list:
        """Encode the copied flush slices into `n` ChunkSets, in `pending`
        order.  Each block of the copy (its place in `pending`, schema,
        padded snapshot and the rows' lengths) is encoded whole, the rows
        of one length together (`encode_chunksets`: a few NumPy calls and
        one codec call a column): a scrape cycle gives the series of a
        group the same count, and a call a series, on this thread or on a
        pool's, is an interpreter-lock hand-off a series beside live
        queries (PERF.md section 6, PR 37).  Persist + downsample stay
        with the caller: store writers and the downsampler are not
        thread-safe, and their ordering is part of the checkpoint
        contract."""
        out: list = [None] * n
        for lo, schema_name, ts_pad, col_pads, les, lens in blocks:
            col_types = {c.name: c.col_type
                         for c in self.schemas[schema_name].data_columns}
            scheme = HistogramBuckets.custom(les) if les is not None else None
            for ln in np.unique(lens).tolist():
                rows = np.flatnonzero(lens == ln)
                chunksets = encode_chunksets(
                    ts_pad[rows, :ln],
                    {name: (np.zeros((rows.size, ln, 0)) if pad is None
                            else pad[rows, :ln])
                     for name, pad in col_pads.items()},
                    col_types, ingestion_time_ms, scheme)
                for r, cs in zip(rows.tolist(), chunksets):
                    out[lo + r] = cs
        return out

    def _do_flush_group(self, group: int, ingestion_time_ms: int,
                        min_samples: int = 0) -> int:
        """Three phases: (1) under write_lock, copy every partition's
        unsealed slice (cheap); (2) lock-FREE, encode + persist +
        downsample (the expensive part, overlapping live ingest/queries);
        (3) under write_lock, advance sealed watermarks + commit the
        checkpoint.  Sealing happens only AFTER chunks are persisted, so
        a crash mid-encode loses nothing (replay covers it) and eviction
        can never reclaim samples whose disk copy doesn't exist yet.  If
        an eviction SHIFTED a store's rows during phase 2 (shift_version
        moved), its seals are skipped — the next flush re-reads and
        re-writes those slices; chunk writes are idempotent."""
        pending, blocks = [], []
        with self._write_locked("flush_copy"):
            self._prune_tombstones()
            # Snapshot the replay watermark BEFORE reading any data: the
            # checkpoint must never claim offsets whose samples were not
            # yet encoded when this flush read them (a background flush
            # racing a live ingest would otherwise lose samples on
            # replay, ref: TimeSeriesShard.commitCheckpoint ordering).
            offset_snapshot = self.ingested_offset
            shift_snapshot = {name: st.shift_version
                              for name, st in self.stores.items()}
            # Copy every partition's unsealed slice with BATCH gathers —
            # one padded [R, Lmax] fancy-index per schema per column —
            # instead of a per-partition Python loop under the lock.  At
            # 1M series / 64 groups the old loop held the write lock
            # ~0.5 s per group while groups ticked every ~0.3 s, which
            # made flush, not the append path, the ingest throttle (the
            # r5 soak's 2.58M samples/s ceiling).  The padded matrices
            # ARE the snapshot; per-partition views are cut from them in
            # phase 2, outside the lock.
            seal_all = (min_samples <= 0
                        or self._group_skip_rounds[group] >= 7)
            skipped_any = False
            snap = []
            for pid in self._group_pids[group]:
                info = self.partitions[pid]
                if info is None or not self._pid_alive[pid]:
                    continue
                snap.append(pid)
            for schema_name, store in self.stores.items():
                pids = [p for p in snap
                        if self.partitions[p].schema_name == schema_name]
                if not pids:
                    continue
                pids = np.asarray(pids, dtype=np.int64)
                rows = self._pid_row[pids]
                lo = store.sealed[rows].astype(np.int64)
                hi = store.counts[rows].astype(np.int64)
                sel = hi > lo
                if not seal_all:
                    big = sel & (hi - lo >= min_samples)
                    skipped_any = skipped_any or bool((sel & ~big).any())
                    sel = big
                if not sel.any():
                    continue
                pids, rows, lo, hi = pids[sel], rows[sel], lo[sel], hi[sel]
                les = store.bucket_les
                # block the row set so R * Lmax padded cells stay bounded
                # (a mass-recovery group with long unsealed tails must not
                # materialize gigabytes)
                lens = hi - lo
                # <= ~64 MB per padded column gather: budget in CELLS,
                # deflated by the widest column's bucket axis so a
                # histogram schema's [R, Lmax, B] gather obeys the same
                # byte bound as a scalar column's [R, Lmax]
                widest = max([1] + [store.num_buckets or 1
                                    for c in store.schema.data_columns
                                    if c.col_type == "hist"])
                max_cells = max(1, (1 << 23) // widest)
                start = 0
                R = len(pids)
                while start < R:
                    end = start + 1
                    lmax = int(lens[start])
                    cells = lmax
                    while end < R:
                        nl = max(lmax, int(lens[end]))
                        nc = nl * (end - start + 1)
                        if nc > max_cells:
                            break
                        lmax, cells = nl, nc
                        end += 1
                    rs = rows[start:end]
                    lor = lo[start:end]
                    posm = lor[:, None] + np.arange(lmax, dtype=np.int64)
                    posc = np.minimum(posm, store.ts.shape[1] - 1)
                    ts_pad = store.ts[rs[:, None], posc]
                    col_pads = {}
                    for c in store.schema.data_columns:
                        arr = store.cols[c.name]
                        if arr is None:
                            col_pads[c.name] = None
                        elif arr.ndim == 3:
                            col_pads[c.name] = arr[rs[:, None], posc, :]
                        else:
                            col_pads[c.name] = arr[rs[:, None], posc]
                    blocks.append((len(pending), schema_name, ts_pad,
                                   col_pads, les, lens[start:end]))
                    for i in range(start, end):
                        pending.append((int(pids[i]),
                                        self.partitions[int(pids[i])],
                                        int(hi[i]), ts_pad, col_pads,
                                        les, i - start, int(lens[i])))
                    start = end
        # cut per-partition views from the padded snapshots (lock-free)
        pending = [
            (pid, info, hi_i,
             ts_pad[r, :ln],
             {name: (np.zeros((ln, 0)) if pad is None
                     else pad[r, :ln])
              for name, pad in col_pads_.items()},
             les)
            for pid, info, hi_i, ts_pad, col_pads_, les, r, ln in pending]
        written = 0
        encoded = []
        chunksets = self._encode_blocks(blocks, len(pending),
                                        ingestion_time_ms)
        if pending:
            faults.fire("flush.persist")
        for (pid, info, hi, ts, cols, les), cs in zip(pending, chunksets):
            self.column_store.write_chunks(
                self.dataset, self.shard_num, info.part_key, [cs],
                info.schema_name)
            if self.shard_downsampler is not None and len(ts):
                # downsample only samples past the per-partition TIME
                # watermark: a shift-skipped seal (phase 3) makes the next
                # flush re-read the same range, and chunk rewrites are
                # idempotent but downsample emission is NOT — without the
                # watermark those samples would double-count downstream
                wm = self._ds_time_wm.get(pid)
                if wm is None or ts[-1] > wm:
                    cut = int(np.searchsorted(ts, wm, side="right")) \
                        if wm is not None else 0
                    self.shard_downsampler.downsample(
                        info.part_key, self.schemas[info.schema_name],
                        ts[cut:], {k: v[cut:] for k, v in cols.items()},
                        bucket_les=les)
                    self._ds_time_wm[pid] = int(ts[-1])
            encoded.append((pid, info, hi, cs))
            written += 1
        dirty_pids: set = set()
        with self._write_locked("flush_seal"):
            for pid, info, hi, cs in encoded:
                store = self.stores[info.schema_name]
                if store.shift_version != shift_snapshot[info.schema_name]:
                    # rows shifted mid-flush: positions are stale — leave
                    # the watermark; the next flush re-covers this data
                    continue
                store.mark_sealed(info.row, hi)
                # the same encoded chunk stays resident in RAM: the dense
                # tier may drop these samples and re-page without disk
                self.resident.add(info.part_id, cs)
                dirty_pids.add(info.part_id)
            # newly created partitions in this group get their part key
            # persisted even before any data flush, so recover_index sees
            # them after a crash (ref: writeDirtyPartKeys:1051)
            for pid in self._dirty_part_keys:
                info = self.partitions[pid]
                if info is not None and info.group == group:
                    dirty_pids.add(pid)
            self._dirty_part_keys -= dirty_pids
            dirty = [PartKeyRecord(self.partitions[pid].part_key,
                                   self.partitions[pid].schema_name,
                                   self.index.start_time(pid),
                                   self.index.end_time(pid))
                     for pid in sorted(dirty_pids)]
        if dirty:
            self.column_store.write_part_keys(self.dataset, self.shard_num,
                                              dirty)
        if skipped_any:
            # small partitions kept accumulating: their samples are not on
            # disk, so the checkpoint may only claim the last FULLY
            # persisted offset (replaying a bit extra is safe — replayed
            # samples land in the dense tier and paging never duplicates
            # below the dense floor)
            self._group_skip_rounds[group] += 1
            ckpt = self._group_ckpt_offset.get(group)
            if ckpt is not None:
                self.meta_store.write_checkpoint(
                    self.dataset, self.shard_num, group, ckpt)
        else:
            self._group_skip_rounds[group] = 0
            self._group_ckpt_offset[group] = offset_snapshot
            self.meta_store.write_checkpoint(
                self.dataset, self.shard_num, group, offset_snapshot)
        if self.cardinality_tracker is not None:
            # buffered cardinality updates persist with the checkpoint
            self.cardinality_tracker.flush()
        self.stats.chunks_flushed += written
        self.stats.flushes += 1
        return written

    def flush_all_groups(self) -> int:
        """Seal + persist EVERYTHING (no write-buffer batching): the
        final-flush / memory-enforcement / test path."""
        return sum(self.flush_group(g) for g in range(self._groups))

    # ------------------------------------------------------------------- query

    def snapshot_read(self, store: DenseSeriesStore, fn: Callable,
                      retries: int = 8):
        """Run fn() — a host-side read that copies data out of `store` —
        against a consistent snapshot.  Lock-free seqlock retry: snapshot an
        even generation, read, verify unchanged; after `retries` torn reads
        fall back to excluding writers via write_lock.  The TPU-native
        replacement for the reference's reader Latch (SURVEY §7 seal/epoch
        protocol; ref: memory/.../Latch.scala).

        Cost-aware: when a single read attempt is EXPENSIVE (a big gather),
        back-to-back ingest will tear it every time — burning retries x
        the full copy cost before the lock fallback (the r4 soak's
        under-ingest degradation).  After the second torn read of a
        >50 ms fn, go straight to the lock."""
        torn_slow = 0
        for _ in range(retries):
            g0 = store.generation
            if g0 % 2:                      # mutation in progress
                time.sleep(0.0002)
                continue
            t0 = time.perf_counter()
            out = fn()
            if store.generation == g0:
                return out
            metrics_registry.counter("snapshot_read_torn").increment()
            if time.perf_counter() - t0 > 0.05:
                torn_slow += 1
                if torn_slow >= 2:
                    break
        metrics_registry.counter("snapshot_read_lock_fallbacks").increment()
        with self._write_locked("query_snapshot_fallback"):
            return fn()

    def lookup_partitions(self, filters: Sequence[ColumnFilter],
                          start_time_ms: int, end_time_ms: int,
                          limit: Optional[int] = None) -> PartLookupResult:
        """ref: TimeSeriesShard.lookupPartitions:1521 — index query + schema
        discovery (MultiSchemaPartitionsExec.scala:27-60).

        Memoized per (filters, limit) and valid while (index.mutations,
        keys_epoch) stand — not per range: the range enters the index's
        answer only through each series' life, every write to which moves
        index.mutations, so a dashboard whose `end` moves one step an
        open keeps hitting.  A range that holds every selected series'
        life takes the entry's frozen answer as it is; any other masks
        the entry's arrays (_part_of: one answer a mask), and a
        subsequence of a stably sorted sequence is the stably sorted
        subsequence: part_ids_from_filters' own answer either way."""
        try:
            ck = (tuple(filters), limit)
            hash(ck)                  # filters with unhashable fields
        except TypeError:             # (e.g. In with a list): uncached
            ck = None
        stamp = (self.index.mutations, self.keys_epoch)
        ent = None
        if ck is not None:
            # the lock covers the dict alone (pop + reinsert is the LRU
            # touch, and six panels of one open race one key); fills run
            # outside it, so two threads may both fill an entry
            with self._lookup_lock:
                ent = self._lookup_cache.pop(ck, None)
                if ent is not None and ent.stamp == stamp:
                    self._lookup_cache[ck] = ent
        if ent is None or ent.stamp != stamp:
            cause = ("new" if ent is None else
                     "index" if ent.stamp[0] != stamp[0] else "epoch")
            metrics_registry.counter("leaf_selection_fills",
                                     cause=cause).increment()
            ent = self._fill_selection(filters, limit, stamp)
            if ck is not None:
                with self._lookup_lock:
                    self._lookup_cache[ck] = ent
                    while len(self._lookup_cache) > _LOOKUP_CACHE_MAX:
                        self._lookup_cache.pop(next(iter(self._lookup_cache)))
        if ent.max_start <= end_time_ms and ent.min_end >= start_time_ms:
            res = ent.whole
        else:
            res = self._part_of(ent, start_time_ms, end_time_ms, limit)
        if self._traced_pids and res.part_ids.size:
            self._trace_touch("query_lookup", res.part_ids)
        return res

    def _part_of(self, ent: _Selection, start_ms: int, end_ms: int,
                 limit: Optional[int]) -> PartLookupResult:
        """The entry's series whose life meets a range.  Ranges differ with
        every open; WHICH series a range leaves out moves only where its
        end passes a birth or its start a death (a dozen an hour in a
        fleet that replaces its targets every ten minutes), so the answer
        is kept as `whole` is: one object, its selection, cache keys and
        facts, to every request that leaves the same series out.  They are
        named by two ranks, found by two binary searches in the entry's
        sorted lives: how many series ended before the range starts and
        how many were born by its end (the mask over the lives, four
        passes over 73,000 series a request and shard, is laid on a miss
        alone)."""
        mk = (int(np.searchsorted(ent.ends, start_ms, side="left")),
              int(np.searchsorted(ent.starts, end_ms, side="right")))
        with self._lookup_lock:
            res = ent.parts.pop(mk, None)
            if res is not None:
                ent.parts[mk] = res
        if res is None:
            mask = (ent.start <= end_ms) & (ent.end >= start_ms)
            res = self._lookup_result(ent.ids[mask][:limit])
            if limit is None:
                res.within = ent.whole
            with self._lookup_lock:
                ent.parts[mk] = res
                while len(ent.parts) > _SELECTION_PARTS_MAX:
                    ent.parts.pop(next(iter(ent.parts)))
        return res

    def _fill_selection(self, filters: Sequence[ColumnFilter],
                        limit: Optional[int], stamp: tuple) -> _Selection:
        ent = _Selection()
        ent.stamp = stamp
        ent.parts = {}
        ent.ids = self.index.part_ids_from_filters(filters, -_NEVER_MS,
                                                   _NEVER_MS)
        ent.start, ent.end = self.index.lives_of(ent.ids)
        ent.starts, ent.ends = np.sort(ent.start), np.sort(ent.end)
        ent.max_start = int(ent.start.max()) if ent.ids.size else -_NEVER_MS
        ent.min_end = int(ent.end.min()) if ent.ids.size else _NEVER_MS
        ent.whole = self._lookup_result(ent.ids[:limit])
        ent.whole.shared = True
        return ent

    def _lookup_result(self, ids: np.ndarray) -> PartLookupResult:
        """Schema discovery over index-ordered ids.  The arrays are
        frozen: the memo hands the SAME result to every hit, so a
        consumer mutating part_ids / pids_by_schema in place poisons its
        own copy attempt loudly instead of silently corrupting later
        queries (round-5 review)."""
        if ids.size:
            ids = ids[self._pid_alive[ids]]
        by_schema: Dict[str, np.ndarray] = {}
        first = None
        if ids.size:
            codes = self._pid_schema_code[ids]
            first = self._schema_names[int(codes[0])]
            for c in np.unique(codes):
                name = self._schema_names[int(c)]
                by_schema[name] = ids[codes == c]
        ids.setflags(write=False)
        for arr in by_schema.values():
            arr.setflags(write=False)
        return PartLookupResult(self.shard_num, ids, by_schema, first, self)

    def selection_facts(self, lookup: PartLookupResult, schema_name: str
                        ) -> Tuple[SchemaSelection, SelectionFacts]:
        """A leaf's selection on one schema with facts as of the store's
        generation now: the memo's while their stamp stands
        (`leaf_selection_hits`), else read again under the seqlock."""
        sel = lookup.selection(schema_name)
        store = self.stores[schema_name]
        facts = sel.facts
        if facts is not None and facts.generation == store.generation:
            if lookup.shared:
                metrics_registry.counter("leaf_selection_hits").increment()
            return sel, facts
        # a shared entry's first facts belong to the fill that
        # lookup_partitions just counted
        cause = ("generation" if facts is not None else
                 None if lookup.shared else "range")
        if cause is not None:
            metrics_registry.counter("leaf_selection_fills",
                                     cause=cause).increment()
        facts = sel.facts = self.snapshot_read(
            store, lambda: SelectionFacts(store, sel.rows))
        return sel, facts

    def rows_for(self, pids: np.ndarray) -> np.ndarray:
        """Store rows for a pid array — vectorized pid->row map."""
        return self._pid_row[pids]

    def append_horizon_ms(self) -> int:
        """Largest timestamp T such that every FUTURE append lands strictly
        after T: the min over rows of each row's newest sample (ingest
        drops out-of-order samples against last_ts, so appends only move
        forward).  The query frontend's result cache treats windows ending
        at or before T as immutable.  Registered rows with zero samples
        accept arbitrary timestamps, so their presence collapses the
        horizon (NO_HORIZON_MS; series-SET changes are tracked separately
        via keys_epoch/index.mutations).  A shard with no row at all bounds
        nothing (NO_ROWS_HORIZON_MS): of a 128-shard layout's shards some
        are empty, and one empty shard must not switch the dataset's
        result cache off.

        Memoized per store generation: the frontend calls this on EVERY
        request including sub-ms cache hits, and the O(S) scan would
        dominate the hit path at 262k+ series.  A torn scan racing a
        mutation is still sound (each per-row read lower-bounds that
        row's future appends) and the memo self-heals on the next
        generation tick."""
        horizon = None
        # list(): runs lock-free on query threads while ingest may insert
        # a new schema store — don't iterate the live dict
        for name, store in list(self.stores.items()):
            s = store.num_series
            if s == 0:
                continue
            gen = store.generation
            memo = self._horizon_memo.get(name)
            if memo is not None and memo[0] == gen:
                h = memo[1]
            else:
                h = (NO_HORIZON_MS if (store.counts[:s] == 0).any()
                     else int(store.last_ts[:s].min()))
                self._horizon_memo[name] = (gen, h)
            horizon = h if horizon is None else min(horizon, h)
        return horizon if horizon is not None else NO_ROWS_HORIZON_MS

    def keys_for(self, pids: np.ndarray) -> List:
        """RangeVectorKeys for a pid array, built once per partition lifetime
        and cached — repeat queries do list indexing, not dict construction
        (ref: TimeSeriesPartition caches its partKey bytes similarly)."""
        from filodb_tpu.query.rangevector import RangeVectorKey
        rk = self._rv_keys
        parts = self.partitions
        out = []
        for pid in pids.tolist():
            k = rk[pid]
            if k is None:
                p = parts[pid]
                if p is None:
                    # pruned tombstone hit by a query older than the grace
                    # window: keep shape alignment with a sentinel key
                    k = RangeVectorKey((("_evicted_", str(pid)),))
                else:
                    k = RangeVectorKey.make(
                        {**p.part_key.tags_dict,
                         "_metric_": p.part_key.metric})
                    rk[pid] = k
            out.append(k)
        return out

    def _decode_paged_chunks(self, store: DenseSeriesStore, chunks,
                             lo_excl: int, hi_incl: int,
                             max_samples: Optional[int] = None):
        """Decode + concatenate chunk data with ts in (lo_excl, hi_incl],
        dropping overlaps and bucket-scheme-mismatched histogram chunks.
        Raises once more than max_samples decode — chunk-granular, so a
        single partition with unbounded history can't OOM the pager."""
        from filodb_tpu.memory.chunks import decode_chunkset
        from filodb_tpu.memory.histogram import rebucket
        hist_cols = {c.name for c in store.schema.data_columns
                     if c.col_type == "hist"}
        ts_parts, col_parts, part_les = [], [], []
        decoded_total = 0
        for cs in sorted(chunks, key=lambda c: c.info.start_time_ms):
            if max_samples is not None and decoded_total > max_samples:
                raise PagedLimitExceeded(max_samples, decoded_total, 1)
            decoded_total += cs.info.num_rows
            chunk_les = None
            if cs.bucket_scheme is not None:
                chunk_les = cs.bucket_scheme.as_array()
                # widen the store to the union of every chunk's boundaries —
                # a scheme change mid-retention stays queryable instead of
                # dropping chunks (ref: HistogramBuckets.scala:340).  The
                # decoded payloads are harmonized onto the FINAL store
                # scheme after the loop, since a later chunk can widen the
                # store again after earlier chunks were already decoded.
                try:
                    store.ensure_scheme(cs.bucket_scheme.num_buckets,
                                        chunk_les)
                except ValueError:
                    # boundary-less store of a different width: no mapping
                    # exists — degrade to skipping this chunk, not failing
                    # the whole query
                    self.stats.rows_dropped += cs.info.num_rows
                    continue
            decoded = decode_chunkset(cs)
            ts = decoded.pop("timestamp")
            keep = (ts > lo_excl) & (ts <= hi_incl)
            if ts_parts:
                keep &= ts > ts_parts[-1][-1]     # chunks must not overlap
            if not keep.any():
                continue
            ts_parts.append(ts[keep])
            col_parts.append({k: v[keep] for k, v in decoded.items()})
            part_les.append(chunk_les)
        if not ts_parts:
            return None, None
        final_les = store.bucket_les
        if final_les is not None:
            for i, les in enumerate(part_les):
                if les is not None and not np.array_equal(les, final_les):
                    col_parts[i] = {k: (rebucket(v, les, final_les)
                                        if k in hist_cols else v)
                                    for k, v in col_parts[i].items()}
        return (np.concatenate(ts_parts),
                {k: np.concatenate([cp[k] for cp in col_parts])
                 for k in col_parts[0]})

    def _read_sealed_chunks(self, info: PartitionInfo, start_time_ms: int,
                            end_time_ms: int,
                            disk_chunks: Optional[list] = None) -> list:
        """Sealed chunks overlapping the range: the compressed RAM tier
        first, disk only for history older than what RAM retains (ref:
        OnDemandPagingShard paging order — block memory, then Cassandra).
        Duplicates are harmless: _decode_paged_chunks drops overlap.
        `disk_chunks`: a batched read_chunks_multi prefetch for this range
        (ensure_paged) — used instead of a per-partition store read."""
        chunks = self.resident.read(info.part_id, start_time_ms, end_time_ms)
        floor = self.resident.coverage_floor(info.part_id)
        ram_covers = (floor is not None and floor <= start_time_ms
                      and bool(chunks))
        if not ram_covers and not isinstance(self.column_store,
                                             NullColumnStore):
            if disk_chunks is None:
                disk_chunks = list(self.column_store.read_chunks(
                    self.dataset, self.shard_num, info.part_key,
                    start_time_ms, end_time_ms))
            chunks = list(disk_chunks) + chunks
        return chunks

    def ensure_paged_pids(self, schema_name: str, pids: np.ndarray,
                          start_time_ms: int, end_time_ms: int,
                          max_samples: Optional[int] = None,
                          cancel=None,
                          facts: Optional[SelectionFacts] = None) -> int:
        """Vectorized ensure_paged precheck: computes which pids actually
        need on-demand paging with numpy over the whole pid array, then runs
        the per-partition paging loop only on that (usually empty) subset —
        the fully-resident hot path costs O(S) numpy, no Python loop; with
        the pids' `facts` (selection_facts) two scalar comparisons, and
        the arrays only when those say some row may need paging."""
        if ((isinstance(self.column_store, NullColumnStore)
                and self.resident.num_chunks == 0) or pids.size == 0):
            return 0
        if facts is not None and not facts.may_need_paging(start_time_ms,
                                                           end_time_ms):
            return 0
        return self._page_in_needed(schema_name, pids, start_time_ms,
                                    end_time_ms, max_samples, cancel)

    def _page_in_needed(self, schema_name: str, pids: np.ndarray,
                        start_time_ms: int, end_time_ms: int,
                        max_samples: Optional[int], cancel) -> int:
        store = self.stores[schema_name]
        rows = self._pid_row[pids]
        cnt, first, last = store.row_extents(rows)
        first_mem = np.where(cnt > 0, first, MAX_TIME)
        last_mem = np.where(cnt > 0, last, 0)
        covered = np.minimum(store.paged_floor[rows], first_mem)
        need = start_time_ms < covered
        page_only = store.page_only[rows]
        need |= (page_only & (cnt > 0)
                 & (end_time_ms > np.maximum(store.paged_ceil[rows], last_mem)))
        if not need.any():
            return 0
        parts = [self.partitions[p] for p in np.asarray(pids)[need].tolist()]
        with self._write_locked("demand_paging"):
            return self.ensure_paged(parts, start_time_ms, end_time_ms,
                                     max_samples=max_samples, cancel=cancel)

    def ensure_paged(self, parts: Sequence[PartitionInfo],
                     start_time_ms: int, end_time_ms: int,
                     max_samples: Optional[int] = None,
                     cancel=None) -> int:
        """On-demand paging: load persisted chunks not in the in-memory
        working set so the query sees full history (ref:
        OnDemandPagingShard.scala:27-39, DemandPagedChunkStore.scala:17-34).

        Coverage bookkeeping lives in the DenseSeriesStore (per-row
        paged_floor/paged_ceil) so eviction invalidates it.  Two directions:
        below the in-memory data (prepend — recovered partitions whose flushed
        history is on disk) and, for page-only rows (no live appends, e.g. a
        query-only downsample store), above it too.  Returns samples paged."""
        if (isinstance(self.column_store, NullColumnStore)
                and self.resident.num_chunks == 0):
            return 0
        # Batched disk prefetch: ONE read_chunks_multi for every partition
        # whose below-floor range needs the column store, instead of a
        # round trip per partition (the netstore win; free locally).
        prefetch: Dict[int, list] = {}
        nothing_down_to: Dict[int, int] = {}    # pid -> asked, nothing there
        if not isinstance(self.column_store, NullColumnStore):
            reqs, req_pids = [], []
            for info in parts:
                store = self.stores[info.schema_name]
                row = info.row
                cnt = int(store.counts[row])
                first_mem = int(store.ts[row, 0]) if cnt else MAX_TIME
                covered = min(int(store.paged_floor[row]), first_mem)
                if start_time_ms >= covered:
                    continue
                hi = end_time_ms if cnt == 0 else first_mem - 1
                if hi < start_time_ms:
                    continue
                floor = self.resident.coverage_floor(info.part_id)
                if floor is not None and floor <= start_time_ms:
                    continue            # RAM tier likely covers it
                reqs.append((info.part_key, start_time_ms, hi))
                req_pids.append(info.part_id)
            if reqs:
                got = list(self.column_store.read_chunks_multi(
                    self.dataset, self.shard_num, reqs))
                prefetch.update(zip(req_pids, got))
                # rows with nothing there (a series born inside the range:
                # a fleet that replaces its targets has hundreds a shard)
                # are asked about once more in one more batch, as far below
                # again as was asked or as the query is long: what holds
                # nothing there either is not asked about again by every
                # query that starts a step earlier
                none = [i for i, chunks in enumerate(got) if not chunks]
                wider = [(reqs[i][0], max(start_time_ms - max(
                    reqs[i][2] - start_time_ms + 1,
                    end_time_ms - start_time_ms), 0), start_time_ms - 1)
                    for i in none]
                for i, (_, lo, hi), chunks in zip(
                        none, wider, self.column_store.read_chunks_multi(
                            self.dataset, self.shard_num, wider)
                        if wider else ()):
                    if not chunks and not self.resident.read(req_pids[i],
                                                             lo, hi):
                        nothing_down_to[req_pids[i]] = lo
        paged = 0
        parts_paged = 0
        for info in parts:
            # abort BEFORE materializing more history than the query may
            # scan — demand paging itself must not be the OOM (ref:
            # capDataScannedPerShardCheck runs pre-ODP on chunk metadata).
            # Work already done is KEPT (floors advanced, chunks resident):
            # it is valid cache for a narrower retry.
            if max_samples is not None and paged > max_samples:
                raise PagedLimitExceeded(max_samples, paged, parts_paged)
            # cooperative cancellation (query/activequeries.py): a killed
            # query stops paging between partitions; the callable raises
            # the caller's structured error (the shard stays query-layer
            # agnostic).  Paged work is kept — valid cache, like the
            # scan-limit abort above.
            if cancel is not None:
                cancel()
            store = self.stores[info.schema_name]
            row = info.row
            cnt = int(store.counts[row])
            floor = int(store.paged_floor[row])
            first_mem = int(store.ts[row, 0]) if cnt else MAX_TIME
            covered_down_to = min(floor, first_mem)
            if start_time_ms < covered_down_to:
                # non-empty rows page all the way up to the in-memory floor —
                # NOT clamped to end_time_ms — so the resident region stays
                # contiguous and paged_floor's "covered down to" claim holds;
                # empty rows clamp to the query range (coverage tracked by
                # paged_floor/paged_ceil as an interval)
                hi = end_time_ms if cnt == 0 else first_mem - 1
                if hi >= start_time_ms:
                    chunks = self._read_sealed_chunks(
                        info, start_time_ms, hi,
                        disk_chunks=prefetch.get(info.part_id))
                    try:
                        ts_all, cols_all = self._decode_paged_chunks(
                            store, chunks, start_time_ms - 1, hi,
                            max_samples=(None if max_samples is None
                                         else max_samples - paged))
                    except PagedLimitExceeded as e:
                        raise PagedLimitExceeded(
                            max_samples, paged + e.samples_paged,
                            parts_paged) from None
                    if ts_all is not None:
                        n = store.prepend_row(row, ts_all, cols_all)
                        paged += n
                        if n:
                            parts_paged += 1
                        # trimmed page-ins must not claim full coverage
                        if n == len(ts_all):
                            store.set_paged(row, floor=start_time_ms)
                        elif n > 0:
                            store.set_paged(row,
                                            floor=int(store.ts[row, 0]))
                    else:
                        store.set_paged(row, floor=min(
                            start_time_ms, nothing_down_to.get(
                                info.part_id, start_time_ms)))
                    if cnt == 0 and store.page_only[row]:
                        store.set_paged(row, ceil=max(
                            int(store.paged_ceil[row]), hi))
            # upper paging: only for rows that have never seen live ingest
            # (live rows' upper coverage is the checkpoint/replay invariant)
            if store.page_only[row] and int(store.counts[row]) > 0:
                last_mem = int(store.ts[row, int(store.counts[row]) - 1])
                ceil = max(int(store.paged_ceil[row]), last_mem)
                if end_time_ms > ceil:
                    chunks = self._read_sealed_chunks(info, ceil + 1,
                                                      end_time_ms)
                    try:
                        ts_all, cols_all = self._decode_paged_chunks(
                            store, chunks, last_mem, end_time_ms,
                            max_samples=(None if max_samples is None
                                         else max_samples - paged))
                    except PagedLimitExceeded as e:
                        raise PagedLimitExceeded(
                            max_samples, paged + e.samples_paged,
                            parts_paged) from None
                    if ts_all is not None:
                        n = store.append_row(row, ts_all, cols_all)
                        paged += n
                        if n:
                            parts_paged += 1
                        # a trimmed page-in must not claim full coverage
                        if n == len(ts_all):
                            store.set_paged(row, ceil=end_time_ms)
                        elif n > 0:
                            store.set_paged(row, ceil=int(
                                store.ts[row, int(store.counts[row]) - 1]))
                    else:
                        store.set_paged(row, ceil=end_time_ms)
        return paged

    def gather_series(self, parts: Sequence[PartitionInfo]):
        """Dense-gather rows for a single-schema partition list.
        Returns (ts [S,T], cols dict, counts [S], store)."""
        if not parts:
            return None
        schema_name = parts[0].schema_name
        store = self.stores[schema_name]
        rows = np.asarray([p.row for p in parts], dtype=np.int64)
        ts, cols, counts = store.gather_rows(rows)
        return ts, cols, counts, store

    # ---------------------------------------------------------------- recovery

    def recover_index(self) -> int:
        """Rebuild the tag index + partition registry from persisted part keys
        (ref: TimeSeriesShard.recoverIndex:600, IndexBootstrapper.scala)."""
        n = 0
        for rec in self.column_store.read_part_keys(self.dataset, self.shard_num):
            try:
                info = self.get_or_create_partition(
                    rec.part_key, rec.schema_name, rec.start_time_ms)
            except QuotaReachedException:
                self.stats.quota_dropped += 1
                continue
            if rec.end_time_ms < MAX_TIME:
                self.index.update_end_time(info.part_id, rec.end_time_ms)
            n += 1
        return n

    def recover_stream(self, batches: Iterable[Tuple[RecordBatch, int]]) -> int:
        """Replay record batches with offsets, skipping those at/below each
        group's checkpoint watermark (ref: TimeSeriesMemStore.recoverStream:147,
        doc/ingestion.md:114-133)."""
        checkpoints = self.meta_store.read_checkpoints(self.dataset, self.shard_num)
        n = 0
        for batch, offset in batches:
            # A batch is skippable for partitions in groups whose watermark is
            # >= offset.  Filter per-record by group.
            if not checkpoints:
                n += self.ingest(batch, offset)
                continue
            # group is a pure function of the partKey hash, so replay
            # filtering is correct even for partitions not yet recreated
            group_by_key = np.asarray(
                [self.group_for(pk) for pk in batch.part_keys], dtype=np.int64)
            wm = np.full(self._groups, -1, dtype=np.int64)
            for g, off in checkpoints.items():
                wm[g] = off
            keep = wm[group_by_key[batch.part_idx]] < offset
            if keep.all():
                n += self.ingest(batch, offset)
            elif keep.any():
                sub = RecordBatch(batch.schema, batch.part_keys,
                                  batch.part_idx[keep], batch.timestamps[keep],
                                  {k: v[keep] for k, v in batch.columns.items()},
                                  batch.bucket_les)
                n += self.ingest(sub, offset)
        return n

    # ---------------------------------------------------------------- memory

    def memory_usage(self) -> Dict[str, int]:
        """Byte accounting across tiers (ref: MemoryStats,
        BlockManager.scala:91)."""
        dense = sum(s.nbytes for s in self.stores.values())
        return {"dense_bytes": dense,
                "resident_bytes": self.resident.bytes_used,
                "total_bytes": dense + self.resident.bytes_used}

    def enforce_memory(self, budget_bytes: Optional[int] = None,
                       active_tail_rows: Optional[int] = None) -> int:
        """Headroom enforcement (ref: TimeSeriesShard.startHeadroomTask:1665
        + CompositeEvictionPolicy, PartitionEvictionPolicy.scala:59): when
        the dense tier exceeds its budget, seal everything via flush, then
        truncate each series to the active tail and release the freed time
        capacity.  Sealed history stays queryable from the compressed
        resident tier (RAM) or the column store (disk) via ensure_paged.
        Returns bytes released."""
        budget = (budget_bytes if budget_bytes is not None
                  else self.config.store.shard_mem_size)
        tail = (active_tail_rows if active_tail_rows is not None
                else self.config.store.active_tail_rows)
        return self._enforce_memory(budget, tail)

    def _enforce_memory(self, budget: int, tail: int) -> int:
        dense = sum(s.nbytes for s in self.stores.values())
        metrics_registry.gauge("dense_store_bytes", dataset=self.dataset,
                               shard=str(self.shard_num)).update(dense)
        if dense <= budget:
            return 0
        self.eviction_in_progress = True
        try:
            return self._enforce_memory_inner(budget, tail)
        finally:
            self.eviction_in_progress = False

    def _enforce_memory_inner(self, budget: int, tail: int) -> int:
        # Seal everything OUTSIDE the write lock: flush manages its own
        # lock phases (copy/seal brief, encode+persist lock-free).  The
        # old whole-enforcement write_lock hold spanned this full forced
        # flush — minutes at 1M series once write-buffer batching let a
        # real backlog accumulate — freezing ingest and queries (the
        # soak's p99 tail).  Racing ingest between flush and truncation
        # is safe: evict_oldest only ever drops SEALED samples.
        self.flush_all_groups()
        released = 0
        with self._write_locked("enforce_memory"):
            for store in self.stores.values():
                if store.num_series == 0:
                    continue
                excess = np.maximum(store.counts - tail, 0)
                if excess.any():
                    store.evict_oldest(excess)
                released += store.compact_time(slack=max(8, tail // 4))
        metrics_registry.gauge("dense_store_bytes", dataset=self.dataset,
                               shard=str(self.shard_num)).update(
            sum(s.nbytes for s in self.stores.values()))
        metrics_registry.counter("memory_pressure_evictions",
                                 dataset=self.dataset).increment()
        self.stats.evictions += 1
        from filodb_tpu.utils.events import journal
        journal.emit("eviction_sweep", subsystem="memstore",
                     reason="memory_pressure", dataset=self.dataset,
                     shard=self.shard_num, bytes_released=released)
        return released

    # ---------------------------------------------------------------- eviction

    def evict_ended_partitions(self, before_ms: int,
                               max_per_lock: int = 2048) -> int:
        """Evict partitions whose series ended before `before_ms`
        (ref: TimeSeriesShard.partitionsToEvict:1464).

        Candidates come from one vectorized index sweep; the per-partition
        teardown then runs in fixed-size increments of `max_per_lock`,
        releasing the write lock between increments so a mass-expiry
        (deploy churn ending 100k series at once) can't stall concurrent
        ingest and query-snapshot fallbacks behind a single multi-second
        sweep — the eviction-shaped p99 tail the r5 soak exposed.  Evicted
        pids join the tombstone queue; _prune_tombstones reclaims them
        after the reader grace period."""
        self.eviction_in_progress = True
        try:
            return self._evict_ended_inner(before_ms, max_per_lock)
        finally:
            self.eviction_in_progress = False

    def _evict_ended_inner(self, before_ms: int, max_per_lock: int) -> int:
        total = 0
        while True:
            with self._write_locked("evict_ended"):
                cand = self.index.ended_pids(before_ms)
                batch = cand[:max_per_lock]
                evicted = 0
                for pid in batch.tolist():
                    info = self.partitions[pid]
                    if info is None or not self._pid_alive[pid]:
                        continue
                    self.index.remove_partition(pid)
                    self.part_set.pop(info.part_key.to_bytes(), None)
                    # the PartitionInfo stays as a tombstone: lock-free
                    # query paths that passed the _pid_alive filter a
                    # moment ago may still deref partitions[pid] /
                    # _rv_keys[pid] — nulling the slot would crash them.
                    # Liveness is _pid_alive alone; the slot itself is
                    # reclaimed after a grace period by _prune_tombstones
                    # (called from flush, under write_lock).
                    self._pid_alive[pid] = False
                    self._evicted_tombstones.append((time.time(), pid))
                    self.resident.drop_part(pid)
                    if self.cardinality_tracker is not None:
                        sk = info.part_key.shard_key(self.schemas.part)
                        self.cardinality_tracker.series_stopped(
                            tuple(sk.get(c, "") for c in
                                  self.schemas.part.options.shard_key_columns))
                    if self._tenant_series_limit:
                        ws = info.part_key.tags_dict.get("_ws_", "")
                        if ws:
                            n = self._ws_series.get(ws, 0) - 1
                            if n > 0:
                                self._ws_series[ws] = n
                            else:
                                self._ws_series.pop(ws, None)
                    evicted += 1
                    self.stats.evictions += 1
                if evicted:
                    # evicted keys left part_set — cached key->pid
                    # resolutions (ingest) and group-id entries must not
                    # outlive them
                    self.keys_epoch += 1
                    self._key_resolve_cache.clear()
                total += evicted
                if cand.size <= max_per_lock:
                    if total:
                        from filodb_tpu.utils.events import journal
                        journal.emit("eviction_sweep",
                                     subsystem="memstore",
                                     reason="ended_partitions",
                                     dataset=self.dataset,
                                     shard=self.shard_num,
                                     partitions_evicted=total)
                    return total

    @property
    def num_partitions(self) -> int:
        return int(self._pid_alive[:len(self.partitions)].sum())

    def compact_index(self, tombstone_threshold: int = 0) -> bool:
        """Prune the tag index's tombstoned postings under the shard
        write lock (the index_compaction job's per-shard entry point) —
        compaction swaps the index's linear-state holder and rewrites
        posting containers, so it must not race ingest/eviction.  With a
        threshold, compacts only once the backlog crossed it; returns
        whether a compaction ran."""
        with self._write_locked("index_compaction"):
            if tombstone_threshold:
                return self.index.maybe_compact(tombstone_threshold)
            if self.index.tombstone_count == 0:
                return False
            self.index.compact()
            return True
