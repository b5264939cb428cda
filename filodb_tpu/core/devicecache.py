"""Device-resident mirror of a shard's dense series store.

The TPU-native analogue of the reference's block-memory working set (ref:
memory/.../BlockManager.scala — query-hot chunks live in pinned block
memory; SURVEY §7.2 'device mirror: packed [series x time-block] arrays
per schema').  Without a mirror every query re-ships the full [S, T]
matrix host→device, a transfer that dwarfs the compute.

The mirror uploads a store's live arrays once and revalidates by the
store's generation counter: unchanged generation → queries gather rows
ON DEVICE from the cached copy; changed generation → one re-upload (the
same cost the uncached path paid per query, so live-ingest workloads are
never worse off).  Timestamp offsets are rebased once to the mirror's
base, so every query shares the cached int32 offset matrix regardless of
its own chunk-scan window.

The gather is by need (MirrorGather): a leaf gets a handle that knows every
array's shape from the snapshot and its row count, and an array's rows are
taken out of the mirror when somebody first reads that array.  A fused leaf
whose padded values are cached reads none, so it launches no take.
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import weakref
from typing import Dict, Optional, Tuple

import numpy as np

from filodb_tpu.ops.timewindow import PAD_TS


@dataclasses.dataclass(frozen=True)
class _MirrorSnapshot:
    """One immutable upload generation.  _refresh builds a complete snapshot
    and publishes it with a single attribute assignment, so a lock-free
    gather_cached racing a refresh pins either the old snapshot or the new
    one in full — never a half-replaced mix of fields."""
    gen: int
    base_ms: int
    t_used: int
    # device arrays: _mirror_rows(S_live) rows, the ones past S_live padding
    ts_off: object                      # jax i32 [rows, T_used]
    cols: Dict[str, object]             # jax f [rows, T_used(, B)]
    # per-series value bases subtracted in f64 before upload, so counter
    # deltas survive the f32 downcast (ops/timewindow.series_value_base)
    vbases: Dict[str, object]
    # --- incremental-update bookkeeping (host-side, f64) ---
    shift_version: int = -1             # store.shift_version at upload
    counts: Optional[np.ndarray] = None        # int32 [S] at upload
    host_vbases: Dict[str, np.ndarray] = dataclasses.field(
        default_factory=dict)                  # f64 [S(, B)]
    # per counter column: correction state at each row's last sample, so a
    # purely-appended tail can be reset-corrected without re-reading the
    # whole row: corrected_tail = correct(seed=last_raw ++ tail) + cum_drop
    tail_last_raw: Dict[str, np.ndarray] = dataclasses.field(
        default_factory=dict)                  # f64 [S(, B)]
    tail_cum_drop: Dict[str, np.ndarray] = dataclasses.field(
        default_factory=dict)                  # f64 [S(, B)]
    # whether each row's vbase came from a real finite sample — a row that
    # was all-NaN at upload (vbase 0) must get a REAL base from its first
    # finite append or large counters land on device un-rebased
    vbase_valid: Dict[str, np.ndarray] = dataclasses.field(
        default_factory=dict)                  # bool [S(, B)]
    # --- fused-kernel eligibility (ops/pallas_fused.py preconditions) ---
    # the phase grid: every row holds the same count of samples and lies
    # on ONE base row shifted by its own phase, ts_off[s] == ts_row0 +
    # phase[s] over the counted region, 0 <= phase[s] < the base row's
    # least gap (Prometheus' per-target scrape offset inside the scrape
    # interval).  ts_row0 None: the rows fit no such grid.  One shared
    # timestamp row is the grid whose phases are all zero (phase_rows 0).
    ts_row0: Optional[np.ndarray] = None       # int32 [T] base-row offsets
    phase: Optional[np.ndarray] = None         # int32 [S] ms, host
    phase_rows: int = 0                        # rows with a phase != 0
    # [rows, 1] f32 on the device, taken by need as vbase is; None where
    # no row has a phase
    phase_dev: object = None
    # per column: no NaN anywhere in the counted region
    col_finite: Dict[str, bool] = dataclasses.field(default_factory=dict)
    # a PLACED snapshot (_place_on_grid): the rows hold other counts of
    # samples than one another (targets that come and go, scrapes that
    # failed), so column k of ts_off and of every value column is not a
    # row's k-th sample but SLOT k of the scrape grid, ts_row0[k] =
    # ts_row0[0] + k x interval; a row's sample lies in the slot of its
    # timestamp less the row's phase, the slots nobody filled hold NaN
    # (col_finite is false) and ts_off holds every slot's own time, filled
    # or not.  `counts` stays the rows' true sample counts.  0: a column
    # is a sample position, as the store has it.
    interval: int = 0
    placed_rows: int = 0                       # rows that do not fill the grid
    # a placed snapshot's fact of each row (host, bool [S]; None where the
    # snapshot is not placed): the row is WHOLE, every slot of the grid
    # holds its sample (its count is the grid's slot count: no late start,
    # early end or missed scrape) and every sample of every column is
    # finite, so the fused kernel's dense body reads no slot of it that
    # holds none.  A fact of the row over the grid, which no range, window
    # or `end` changes; `placed_rows` counts the rows short of slots.  A
    # leaf takes its rows' by need (MirrorGather.whole_first), where it
    # pads a working set; an incremental refresh keeps it (a row that
    # misses a scrape stops being whole; one that was not is not again
    # before the next full build)
    whole: Optional[np.ndarray] = None
    # the store generation whose SAMPLES the device arrays hold.  `gen`
    # moves on with bookkeeping that touches none of them (a paging attempt
    # that found nothing on disk writes paged_floor:
    # DenseSeriesStore.set_paged) and the arrays are handed on as they are;
    # `data_gen` then stays, and with it what was made from the arrays (the
    # fused leaf's padded working sets key on it)
    data_gen: int = -1

    def __post_init__(self):
        if self.data_gen < 0:
            object.__setattr__(self, "data_gen", self.gen)

    @property
    def uniform_grid(self) -> bool:
        """One shared timestamp row: the phase grid with every phase 0."""
        return self.ts_row0 is not None and self.phase_rows == 0


def _tail_state(raw: np.ndarray, corrected: np.ndarray
                ) -> Tuple[np.ndarray, np.ndarray]:
    """(last_raw, cum_drop) per series: the raw value at the last finite
    sample and the cumulative reset correction there (0 / NaN-free when a
    row has no finite samples).  raw/corrected are [S, T] or [S, T, B]."""
    v = raw if raw.ndim == 2 else np.moveaxis(raw, 2, 1)
    c = corrected if corrected.ndim == 2 else np.moveaxis(corrected, 2, 1)
    shape2 = v.shape[:-1]
    v2 = v.reshape(-1, v.shape[-1])
    c2 = c.reshape(-1, c.shape[-1])
    finite = np.isfinite(v2)
    any_f = finite.any(axis=1)
    last = np.where(any_f, v2.shape[1] - 1 -
                    np.argmax(finite[:, ::-1], axis=1), 0)
    rows = np.arange(v2.shape[0])
    lr = np.where(any_f, v2[rows, last], np.nan)
    cd = np.where(any_f, c2[rows, last] - v2[rows, last], 0.0)
    return lr.reshape(shape2), cd.reshape(shape2)


def _tails_matrix(col: np.ndarray, rows: np.ndarray, counts_old: np.ndarray,
                  counts_new: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Compact [R, L(, B)] matrix of each changed row's new samples
    (positions [counts_old, counts_new)), NaN-padded, plus the structural
    validity mask [R, L] (which distinguishes padding from genuinely-NaN
    samples).  R = len(rows)."""
    n_new = (counts_new - counts_old)[rows]
    L = int(n_new.max())
    pos = counts_old[rows][:, None] + np.arange(L)[None, :]
    valid = np.arange(L)[None, :] < n_new[:, None]
    pos_c = np.where(valid, pos, 0)
    tails = col[rows[:, None], pos_c].astype(np.float64)
    if tails.ndim == 3:
        tails[~valid] = np.nan
    else:
        tails = np.where(valid, tails, np.nan)
    return tails, valid


# f32 holds whole milliseconds exactly below this: the kernel's timestamps
# are offsets from the mirror's base plus a phase
_F32_EXACT_MS = 1 << 24
_PHASE_BLOCK = 8192         # rows compared at once (bounds the temporaries)


def _detect_phase_grid(ts_off: np.ndarray, counts: np.ndarray,
                       base_ms: int = 0
                       ) -> Tuple[Optional[np.ndarray], Optional[np.ndarray],
                                  int]:
    """(ts_row0, phase, offgrid_rows) of a store's offsets [S, T] from
    `base_ms` (PAD_TS beyond counts).  On a phase grid (see
    _MirrorSnapshot): the base row (int32 [T], PAD_TS beyond the count)
    and every row's phase off it.  The base row is the earliest row's,
    moved back to the whole multiple of its least gap since the epoch
    where every phase stays under that gap: Prometheus scrapes a target
    at `offset + k x interval` since the epoch, so the shards of one
    deployment then find ONE base row, whichever target each holds
    first, and their leaves share a plan (offsets may start below 0).
    Else (None, None, n): n rows hold another count of samples than row
    0, or do not lie on the earliest row shifted, or lie a whole gap or
    more behind it; or every row, where some row has a phase and the base
    row's last slot plus a phase is no whole millisecond in f32
    (_slot_times_exact: 1,677 slots at 10 s; booked by name on
    `device_mirror_inexact_grids_total`).  One shared row has no phase to
    add: its times are the plan's to check (FusedPlan.exact)."""
    s = ts_off.shape[0]
    if s == 0:
        return None, None, 0
    c = int(counts[0])
    # (the first column first: rows at offsets of their own differ there,
    # and are spared the whole-array compare)
    if (counts == c).all() and (ts_off[:, 0] == ts_off[0, 0]).all() \
            and (ts_off == ts_off[0:1]).all():
        # one shared timestamp row, the cheapest case first: what every
        # store loaded from a test producer is
        return ts_off[0].copy(), np.zeros(s, np.int32), 0
    if c == 0:
        return None, None, int((counts != 0).sum())
    first = ts_off[:, 0].astype(np.int64)
    r0 = int(np.argmin(np.where(counts == c, first, np.iinfo(np.int64).max)))
    base = ts_off[r0].copy()
    phase = first - int(base[0])
    gap = int(np.diff(base[:c]).min()) if c > 1 else _F32_EXACT_MS
    on = (counts == c) & (phase >= 0) & (phase < min(gap, _F32_EXACT_MS))
    for lo in range(0, s, _PHASE_BLOCK):
        blk = slice(lo, lo + _PHASE_BLOCK)
        on[blk] &= (ts_off[blk, :c] - phase[blk, None].astype(np.int32)
                    == base[None, :c]).all(axis=1)
    off = int(s - on.sum())
    if off:
        return None, None, off
    if phase.any() and not _slot_times_exact(
            int(base[c - 1]) - int(base[0]) + 2 * gap if c > 1 else 0):
        return None, None, s
    back = (base_ms + int(base[0])) % gap if c > 1 else 0
    if back and int(phase.max()) + back < min(gap, _F32_EXACT_MS):
        base[:c] -= back
        phase += back
    return base, phase.astype(np.int32), 0


def _slot_times_exact(reach_ms: int) -> bool:
    """Whether a grid whose last slot lies `reach_ms` past its first,
    counting an interval for a row's phase and one for the base row's move
    back, keeps every time a whole millisecond in f32.  A grid that does
    not is booked by name: the kernel would answer from times a
    millisecond off."""
    ok = reach_ms < _F32_EXACT_MS
    if not ok:
        from filodb_tpu.utils.metrics import registry
        registry.counter("device_mirror_inexact_grids").increment()
    return ok


def _slots_fit(n_slots: int, t_used: int, interval: int) -> bool:
    """Whether a grid of `n_slots` slots may hold rows of at most `t_used`
    samples: not over twice as many slots as the longest row has samples
    (a store whose rows are mostly holes keeps the store's layout), and
    every slot's time exact in f32 beside a phase."""
    return n_slots <= 2 * max(t_used, 1) + 64 \
        and _slot_times_exact((n_slots + 1) * interval)


def _place_on_grid(ts_off: np.ndarray, counts: np.ndarray, base_ms: int = 0):
    """Slots for a store whose rows hold other counts of samples than one
    another: (ts_row0 [n_slots] int32, phase [S] int32, need [R] the rows
    that do not fill every slot, slot [R, T] int32 each one's samples'
    slots (-1 beyond its count), interval); or the count of rows off the
    grid (at least 1) where some sample fits no slot.

    The interval is the least gap between two samples of a row, the one
    most rows agree on (one late scrape is then one row off the grid, not
    another interval); a row's phase its first sample's time since the epoch
    modulo the interval, the grid's slot 0 the earliest whole multiple of
    the interval that some row's first sample, less its phase, lies on:
    every shard of one deployment so finds one base row (as
    _detect_phase_grid's does).  A sample fits its slot exactly or not at
    all: a scrape that came late, a second interval, or a store too sparse
    for a grid (_slots_fit) leave the store off the grid.  A row without
    samples has phase 0 and fills no slot.  Row blocks bound the
    temporaries."""
    s, t = ts_off.shape
    pos = np.arange(t)[None, :]
    live = counts > 0
    least = np.full(s, _F32_EXACT_MS, np.int64)     # each row's least gap
    for lo in range(0, s, _PHASE_BLOCK):
        blk = slice(lo, lo + _PHASE_BLOCK)
        least[blk] = np.where(pos[:, 1:] < counts[blk, None],
                              np.diff(ts_off[blk], axis=1),
                              _F32_EXACT_MS).min(axis=1,
                                                 initial=_F32_EXACT_MS)
    gaps, rows_of = np.unique(least[least < _F32_EXACT_MS],
                              return_counts=True)
    if not gaps.size:
        return int(live.sum()) or 1      # no row shows an interval
    # (a tie goes to the longer gap: a scrape off its schedule shortens a
    # row's least gap, never lengthens it)
    interval = int(gaps[rows_of == rows_of.max()].max())
    first = base_ms + ts_off[:, 0].astype(np.int64)
    phase = np.where(live, first % interval, 0)
    row0 = int((first - phase)[live].min()) - base_ms    # slot 0's offset
    last = np.full(s, -1, np.int64)      # each row's last slot
    on = np.ones(s, bool)
    for lo in range(0, s, _PHASE_BLOCK):
        blk = slice(lo, lo + _PHASE_BLOCK)
        q, rem = np.divmod(ts_off[blk].astype(np.int64)
                           - phase[blk, None] - row0, interval)
        held = pos < counts[blk, None]
        on[blk] = ~((rem != 0) & held).any(axis=1)
        last[blk] = np.where(held, q, -1).max(axis=1, initial=-1)
    n_slots = int(last.max()) + 1
    if not on.all() or not _slots_fit(n_slots, t, interval):
        return max(int(s - on.sum()), 1)
    need = np.flatnonzero(counts != n_slots)
    slot = np.empty((need.size, t), np.int32)
    for lo in range(0, need.size, _PHASE_BLOCK):
        rows = need[lo:lo + _PHASE_BLOCK]
        q = (ts_off[rows].astype(np.int64) - phase[rows, None] - row0) \
            // interval
        slot[lo:lo + rows.size] = np.where(pos < counts[rows, None], q, -1)
    ts_row0 = (row0 + np.arange(n_slots, dtype=np.int64) * interval) \
        .astype(np.int32)
    return ts_row0, phase.astype(np.int32), need, slot, interval


def _placed(x: np.ndarray, rows: int, need: np.ndarray, slot: np.ndarray,
            n_slots: int) -> np.ndarray:
    """`x` [S, T] (NaN beyond a row's count) as a placed snapshot holds
    it: [rows, n_slots], the samples of each row of `need` moved to their
    slots (_place_on_grid), NaN in every slot that nobody filled and in
    the rows past the store's.  The rows not in `need` fill every slot
    already."""
    out = np.full((rows, n_slots), np.nan, x.dtype)
    out[:x.shape[0], :x.shape[1]] = x
    for lo in range(0, need.size, _PHASE_BLOCK):
        r = need[lo:lo + _PHASE_BLOCK]
        at = slot[lo:lo + r.size]
        i, j = np.nonzero(at >= 0)
        vals = x[r[i], j]
        out[r] = np.nan
        out[r[i], at[i, j]] = vals
    return out


def _note_phase_grid(shard_num: Optional[int], phase: Optional[np.ndarray],
                     offgrid_rows: int, placed_rows: int = 0) -> int:
    """Book a build's grid by shard; -> the rows with a phase."""
    from filodb_tpu.utils.metrics import registry
    phase_rows = int(np.count_nonzero(phase)) if phase is not None else 0
    shard = str(-1 if shard_num is None else shard_num)
    registry.gauge("device_mirror_phase_rows", shard=shard).update(phase_rows)
    registry.gauge("device_mirror_offgrid_rows",
                   shard=shard).update(offgrid_rows)
    registry.gauge("device_mirror_placed_rows",
                   shard=shard).update(placed_rows)
    return phase_rows


_mirror_serial = itertools.count(1)

# process-wide count of background rebuilds in flight (drives the
# device_mirror_rebuild_in_progress gauge): per-rebuild set/clear would
# let the first of two overlapping rebuilds zero the gauge while the
# second still runs
_rebuilds_lock = threading.Lock()
_rebuilds_in_flight = 0


def _note_rebuild(delta: int) -> None:
    global _rebuilds_in_flight
    from filodb_tpu.utils.metrics import registry
    with _rebuilds_lock:
        _rebuilds_in_flight += delta
        registry.gauge("device_mirror_rebuild_in_progress").update(
            _rebuilds_in_flight)

# Default mirror HBM budget — the single source for this constant (also
# mirrored by config.device_mirror_hbm_limit and subtracted by the fused
# padded-values cache budget in query/exec._fused_vals_budget).
DEFAULT_HBM_LIMIT_BYTES = 8 << 30


def _mirror_rows(series: int) -> int:
    """Rows a mirror's device arrays get for a store of `series` series:
    the next rung of the fused kernel's series ladder.  A program that
    reads a mirror array (the row take of every leaf that misses its
    padded values) compiles once a SHAPE; with the exact series count
    that is once a shard, 30 takes of 0.6 s in the first query of a chip's
    32-shard share (PERF.md section 6, PR 35), and again whenever a
    shard's series count drifts.  The rows past the store's are never
    indexed: PAD_TS offsets, NaN values, zero bases."""
    from filodb_tpu.ops.pallas_fused import pad_series_count
    return pad_series_count(series)


def _pad_rows(x: np.ndarray, rows: int, fill) -> np.ndarray:
    if x.shape[0] >= rows:
        return x
    out = np.full((rows,) + x.shape[1:], fill, x.dtype)
    out[:x.shape[0]] = x
    return out


def store_nbytes(store) -> int:
    """Estimated device bytes of a store's mirror (ts offsets + columns)."""
    t = max(store.time_used, 1)
    n = store.num_series * t * 4
    for arr in store.cols.values():
        if arr is not None:
            n += store.num_series * t * arr.itemsize * \
                (arr.shape[2] if arr.ndim == 3 else 1)
    return n


class MirrorPlacer:
    """HBM-aware shard-mirror placement across the local devices — the
    sharded DeviceMirror mode: each chip holds its shard-subset's
    columns, so a multi-shard box spreads the working set over every
    HBM instead of piling all mirrors onto device 0 (and the per-device
    fused dispatch then runs each shard's kernel on its own chip).

    A shard prefers its round-robin home (shard_num % n_devices); when
    that device's booked bytes + the incoming estimate would exceed
    device_mirror_hbm_limit_bytes, the least-booked device that fits
    takes it; when nothing fits, the least-booked device takes it anyway
    and the mirror's aggregate-occupancy check in _refresh degrades that
    store to host gathers (same stance as the single-device over-cap
    path).  assign() RESERVES the estimate on the chosen device inside
    the same lock, so concurrent first-query mirror creations see each
    other's bookings instead of all landing on one home; the caller
    hands the reservation to DeviceMirror(reserved_bytes=) and _book
    later adjusts it to the actual upload size."""

    def __init__(self):
        self._lock = threading.Lock()
        self._booked: Dict[object, int] = {}

    def assign(self, shard_num: int, est_bytes: int,
               limit_bytes: int, region: str = "hot") -> object:
        import jax
        devs = jax.local_devices()
        home = devs[shard_num % len(devs)]
        with self._lock:
            if self._booked.get(home, 0) + est_bytes <= limit_bytes:
                chosen = home
            else:
                fits = [d for d in devs
                        if self._booked.get(d, 0) + est_bytes
                        <= limit_bytes]
                chosen = min(fits or devs,
                             key=lambda d: (self._booked.get(d, 0),
                                            str(d)))
                if not fits:
                    from filodb_tpu.utils.metrics import registry
                    registry.counter(
                        "device_mirror_placement_overflow").increment()
            self._booked[chosen] = self._booked.get(chosen, 0) + est_bytes
            used = sum(1 for v in self._booked.values() if v > 0)
        from filodb_tpu.utils.metrics import registry
        registry.gauge("device_mirror_devices_used").update(used)
        from filodb_tpu.utils.devicetelem import telem
        telem.hbm_book(chosen, region, est_bytes)
        return chosen

    def book(self, device, delta: int, region: str = "hot") -> None:
        if device is None:
            return
        from filodb_tpu.utils.metrics import registry
        with self._lock:
            self._booked[device] = max(
                self._booked.get(device, 0) + delta, 0)
            used = sum(1 for v in self._booked.values() if v > 0)
        registry.gauge("device_mirror_devices_used").update(used)
        # every placer mutation pairs with one equal-delta feed into the
        # per-device, per-region HBM occupancy model (PR 18): the gauges
        # and the placer's table reconcile by construction
        from filodb_tpu.utils.devicetelem import telem
        telem.hbm_book(device, region, delta)

    def booked(self, device) -> int:
        with self._lock:
            return self._booked.get(device, 0)


placer = MirrorPlacer()

# serializes mirror creation (the check-then-set on store.device_mirror):
# two concurrent first queries would otherwise each placer.assign — the
# loser's reservation then leaks until GC collects its orphan mirror
mirror_create_lock = threading.Lock()


def _release_booking(cell) -> None:
    """weakref.finalize target: give a collected mirror's booked bytes
    back to the placer (must be module-level — a bound method would pin
    the mirror alive).  Default-device mirrors (device None) have no
    placer booking but still occupy HBM — release their occupancy-model
    bytes directly."""
    device, nbytes = cell
    if nbytes:
        if device is None:
            from filodb_tpu.utils.devicetelem import telem
            telem.hbm_book(None, "hot", -nbytes)
        else:
            placer.book(device, -nbytes)


def sharded_mirrors_enabled(config_store) -> bool:
    """Sharded placement engages when configured AND there is more than
    one local device AND the backend actually benefits (TPU chips with
    their own HBM).  FILODB_TPU_FORCE_SHARDED_MIRROR=1 forces it on host
    platforms — the CPU multi-device equivalence tests run under it."""
    import os

    import jax
    if not getattr(config_store, "device_mirror_sharded", True):
        return False
    try:
        if jax.local_device_count() < 2:
            return False
        return (jax.default_backend() == "tpu"
                or os.environ.get("FILODB_TPU_FORCE_SHARDED_MIRROR") == "1")
    except Exception:  # noqa: BLE001 — uninitialized backend
        return False


class ColdSegmentCache:
    """LRU-paged cold region of the device mirror: whole persisted-segment
    blocks uploaded on demand under a byte budget
    (`store.device_mirror_cold_limit_bytes`), evicted at SEGMENT
    granularity — the Thanos store-gateway page cache, HBM-resident.

    Invariants the longrange bench/tests counter-assert:
      - booked bytes NEVER exceed the budget: eviction runs BEFORE the
        upload (using the caller's size estimate), not after;
      - a single block larger than the whole budget degrades to a
        host-side build (`device='host'`) — served, not cached, never an
        error and never an OOM.

    Placement reuses the PR 6 MirrorPlacer so cold blocks land HBM-aware
    on the shard's owning chip (sharded-mirror mode); on single-device /
    host platforms blocks go to the default device and only this cache's
    own byte accounting applies."""

    def __init__(self, limit_bytes: int, use_placer: Optional[bool] = None):
        self.limit_bytes = int(limit_bytes)
        self._lock = threading.Lock()
        self._entries: Dict[tuple, object] = {}      # key -> block (LRU)
        self._bytes = 0
        self._use_placer = use_placer

    @property
    def bytes_booked(self) -> int:
        with self._lock:
            return self._bytes

    def _placer_on(self) -> bool:
        if self._use_placer is not None:
            return self._use_placer
        try:
            import jax
            return jax.local_device_count() > 1
        except Exception:  # noqa: BLE001 — uninitialized backend
            return False

    def _evict_until(self, need: int) -> None:
        """Caller holds the lock.  Evict LRU entries until `need` more
        bytes fit under the budget."""
        from filodb_tpu.utils.metrics import registry
        while self._entries and self._bytes + need > self.limit_bytes:
            oldest = next(iter(self._entries))
            block = self._entries.pop(oldest)
            self._bytes -= getattr(block, "nbytes", 0)
            dev = getattr(block, "device", None)
            if dev is not None and dev != "host":
                placer.book(dev, -getattr(block, "nbytes", 0),
                            region="cold")
            elif dev is None:
                from filodb_tpu.utils.devicetelem import telem
                telem.hbm_book(None, "cold",
                               -getattr(block, "nbytes", 0))
            registry.counter("device_mirror_cold_evictions").increment()

    def get(self, key: tuple, est_bytes: int, shard_num: int,
            build) -> Tuple[object, str]:
        """-> (block, verdict).  `build(device)` decodes + uploads the
        block; device is a jax Device (placed), None (default device), or
        the string 'host' for the over-budget degrade."""
        from filodb_tpu.utils.metrics import registry
        with self._lock:
            block = self._entries.get(key)
            if block is not None:
                self._entries[key] = self._entries.pop(key)   # LRU touch
                registry.counter("device_mirror_cold_hits").increment()
                return block, "cold_hit"
        if est_bytes > self.limit_bytes:
            # one block alone blows the budget: host-side segment scan —
            # slower, bounded, never an error (uncached: the next query
            # re-decodes rather than pinning an over-budget block)
            registry.counter("device_mirror_cold_over_budget").increment()
            return build("host"), "cold_paged"
        from filodb_tpu.utils.devicetelem import telem
        device = None
        none_booked = False
        with self._lock:
            # reserve BEFORE the upload so concurrent page-ins see each
            # other's bookings and the budget is never exceeded
            self._evict_until(est_bytes)
            self._bytes += est_bytes
        import time as _t
        _b0 = _t.perf_counter()
        try:
            if self._placer_on():
                device = placer.assign(shard_num, est_bytes,
                                       self.limit_bytes, region="cold")
            else:
                # default-device page-in: no placer booking exists, feed
                # the occupancy model directly (same release points)
                telem.hbm_book(None, "cold", est_bytes)
                none_booked = True
            block = build(device)
        except Exception:
            with self._lock:
                self._bytes -= est_bytes
            if device is not None:
                placer.book(device, -est_bytes, region="cold")
            elif none_booked:
                telem.hbm_book(None, "cold", -est_bytes)
            raise
        actual = getattr(block, "nbytes", est_bytes)
        telem.record_dispatch("cold_page_in", device=device,
                              shape=f"seg{est_bytes >> 10}k",
                              seconds=_t.perf_counter() - _b0,
                              bytes_in=actual, kind="transfer",
                              note=False)
        with self._lock:
            existing = self._entries.get(key)
            if existing is not None:
                # a concurrent page-in won the race: keep theirs, release
                # this build's reservation
                self._bytes -= est_bytes
                if device is not None:
                    placer.book(device, -est_bytes, region="cold")
                elif none_booked:
                    telem.hbm_book(None, "cold", -est_bytes)
                self._entries[key] = self._entries.pop(key)
                return existing, "cold_hit"
            # adjust the reservation to the measured size (still pre-
            # bounded: actual <= est for f32 uploads of the estimate)
            self._bytes += actual - est_bytes
            self._evict_until(0)
            self._entries[key] = block
        if actual != est_bytes:
            if device is not None:
                placer.book(device, actual - est_bytes, region="cold")
            elif none_booked:
                telem.hbm_book(None, "cold", actual - est_bytes)
        registry.counter("device_mirror_cold_misses").increment()
        registry.gauge("device_mirror_cold_bytes").update(self.bytes_booked)
        registry.gauge("device_mirror_cold_limit_bytes").update(
            self.limit_bytes)
        return block, "cold_paged"

    def clear(self) -> None:
        with self._lock:
            for block in self._entries.values():
                dev = getattr(block, "device", None)
                if dev is not None and dev != "host":
                    placer.book(dev, -getattr(block, "nbytes", 0),
                                region="cold")
                elif dev is None:
                    from filodb_tpu.utils.devicetelem import telem
                    telem.hbm_book(None, "cold",
                                   -getattr(block, "nbytes", 0))
            self._entries.clear()
            self._bytes = 0


class DeviceMirror:
    """One mirror per DenseSeriesStore (lazily attached).

    `device` pins every upload to that chip (sharded mode, placed by
    MirrorPlacer); None keeps the classic default-device behavior."""

    def __init__(self, hbm_limit_bytes: int = DEFAULT_HBM_LIMIT_BYTES,
                 device=None, shard_num: Optional[int] = None,
                 reserved_bytes: int = 0):
        self.hbm_limit_bytes = hbm_limit_bytes
        self.device = device
        self.shard_num = shard_num
        # reserved_bytes: the estimate MirrorPlacer.assign already booked
        # for this mirror — _book later adjusts it to the actual size
        self._booked_bytes = reserved_bytes if device is not None else 0
        # release the booking when the mirror is collected: store /
        # memstore rebuilds drop mirrors without a teardown call, and
        # leaked bookings would eventually push every device past the
        # placement limit.  Default-device mirrors register too — their
        # bytes live only in the HBM occupancy model (PR 18), which must
        # see the release just the same.
        self._booking = [device, self._booked_bytes]
        weakref.finalize(self, _release_booking, self._booking)
        self._snap: Optional[_MirrorSnapshot] = None
        # process-unique identity for external caches: id() can be reused
        # by a later allocation after this mirror is collected
        self.serial = next(_mirror_serial)
        # background full-rebuild state (post-eviction shift_version bumps:
        # the O(S*T) re-upload runs here, never on a query's critical path
        # — see request_background_refresh)
        self._bg_lock = threading.Lock()
        self._bg_thread: Optional[threading.Thread] = None

    def _nbytes(self, store) -> int:
        return store_nbytes(store)

    def _book(self, nbytes: int) -> None:
        """Track this mirror's device-HBM footprint with the placer so
        later shard placements see current occupancy.  Default-device
        mirrors (no placer booking) still feed the per-device occupancy
        model, so `device_hbm_booked_bytes{device="TPU_0",region="hot"}`
        is real on single-chip boxes too."""
        if nbytes != self._booked_bytes:
            if self.device is not None:
                placer.book(self.device, nbytes - self._booked_bytes)
            else:
                from filodb_tpu.utils.devicetelem import telem
                telem.hbm_book(None, "hot", nbytes - self._booked_bytes)
            self._booked_bytes = nbytes
            self._booking[1] = nbytes

    def _refresh(self, store) -> bool:
        import time as _time

        import jax

        from filodb_tpu.utils.metrics import (note_mirror_refresh,
                                              note_transfer,
                                              registry as metrics_registry)
        # capture the version BEFORE copying host arrays: if a mutation
        # lands mid-copy the recorded generation is stale, so the caller's
        # snapshot_read retry forces a clean re-upload (seqlock protocol,
        # see DenseSeriesStore.mutation)
        from filodb_tpu.utils.faults import faults
        faults.fire("device.upload")
        gen0 = store.generation
        nbytes = self._nbytes(store)
        if nbytes > self.hbm_limit_bytes:
            # silently-degraded path flagged in round 1: make it observable
            metrics_registry.counter("device_mirror_over_cap").increment()
            from filodb_tpu.utils.events import journal
            journal.emit("mirror_over_cap", subsystem="mirror",
                         scope="store", nbytes=nbytes,
                         limit=self.hbm_limit_bytes)
            # a stale snapshot's device arrays would keep HBM allocated
            # (and, sharded, make the zeroed booking a lie the placer
            # trusts) — drop it; host gathers serve from here
            self._snap = None
            self._book(0)
            return False
        if self.device is not None:
            # aggregate occupancy on the placed device (sharded mode):
            # RESERVE this upload's size first, then re-read the total —
            # check-then-upload would let two concurrent refreshes of
            # co-located mirrors both pass and jointly OOM the chip.
            # Over the limit means the placer found no device that fits:
            # degrade to host gathers and release our reservation so
            # better-fitting shards can take the device.
            self._book(nbytes)
            if placer.booked(self.device) > self.hbm_limit_bytes:
                metrics_registry.counter(
                    "device_mirror_device_over_cap").increment()
                from filodb_tpu.utils.events import journal
                journal.emit("mirror_over_cap", subsystem="mirror",
                             scope="device", nbytes=nbytes,
                             limit=self.hbm_limit_bytes)
                self._snap = None
                self._book(0)
                return False
        _t0 = _time.perf_counter()
        # transfer attribution times ONLY the device_put dispatches —
        # the surrounding host prep (offset/vbase/counter math) belongs
        # in exec_s, and booking it as transfer would point an operator
        # at the interconnect for a host-CPU cost
        xfer_s = 0.0

        def dput(x):
            nonlocal xfer_s
            t = _time.perf_counter()
            out = jax.device_put(x, self.device)
            xfer_s += _time.perf_counter() - t
            return out

        metrics_registry.counter("device_mirror_refreshes").increment()
        metrics_registry.gauge("device_mirror_bytes").update(nbytes)
        # occupancy vs limit on every upload: a transfer regression or a
        # store creeping toward its HBM cap is visible at /metrics without
        # a profiler (PR 3 device-side accounting)
        metrics_registry.gauge("device_mirror_hbm_limit_bytes").update(
            self.hbm_limit_bytes)
        metrics_registry.counter("device_mirror_upload_bytes",
                                 kind="full").increment(nbytes)
        s, t = store.num_series, max(store.time_used, 1)
        ts = store.ts[:s, :t]
        live = ts[ts > 0]
        base_ms = int(live.min()) if live.size else 0
        pos = np.arange(t)[None, :]
        off = np.clip(ts - base_ms, -(1 << 30), 1 << 30).astype(np.int32)
        ts_off = np.where(pos < store.counts[:s, None], off, PAD_TS)
        cols: Dict[str, object] = {}
        vbases: Dict[str, object] = {}
        host_vbases: Dict[str, np.ndarray] = {}
        last_raw: Dict[str, np.ndarray] = {}
        cum_drop: Dict[str, np.ndarray] = {}
        from filodb_tpu.ops.counter import rebase_values
        counter_cols = {c.name for c in store.schema.data_columns
                        if c.detect_drops or c.counter}
        counts = store.counts[:s].copy()
        vbase_valid: Dict[str, np.ndarray] = {}
        col_finite: Dict[str, bool] = {}
        whole = None                    # a placed build's fact of each row
        from filodb_tpu.utils.metrics import span
        with span("mirror.phase_detect"):
            ts_row0, phase, offgrid = _detect_phase_grid(ts_off, counts,
                                                         base_ms)
        dev_rows = _mirror_rows(s)
        # the cheap cases came first, at what they cost; rows that hold
        # other counts of samples (or lie a whole interval apart) are placed
        # on the slots of their scrape grid, if every sample fits one
        # (scalar columns: a histogram's buckets stay as the store has them)
        need = slot = None
        interval = 0
        if offgrid and all(a is None or a.ndim == 2
                           for a in store.cols.values()):
            with span("mirror.place_slots"):
                got = _place_on_grid(ts_off, counts, base_ms)
                if isinstance(got, tuple):
                    ts_row0, phase, need, slot, interval = got
                    offgrid = 0
                    nbytes = nbytes // t * len(ts_row0)
                    ts_off = ts_row0[None, :] + phase[:, None]
                    metrics_registry.counter(
                        "device_mirror_rows_placed").increment(need.size)
                else:
                    offgrid = got
        placed_rows = 0 if need is None else int(need.size)
        if interval:
            whole = counts == len(ts_row0)
        phase_rows = _note_phase_grid(self.shard_num, phase, offgrid,
                                      placed_rows)
        for name, arr in store.cols.items():
            if arr is not None:
                # counter columns are reset-corrected in f64 BEFORE rebasing
                # so f32 deltas are exact across resets; the leaf exec routes
                # non-counter functions on counter columns around the mirror
                is_counter = name in counter_cols
                rebased, vb, corrected = rebase_values(
                    arr[:s, :t], is_counter, return_corrected=True)
                if interval:
                    # corrected and rebased over the samples that exist, in
                    # their order (a reset across a hole is a reset; the base
                    # is a row's first sample), THEN placed
                    with span("mirror.place_slots"):
                        cols[name] = dput(_placed(rebased, dev_rows, need,
                                                  slot, len(ts_row0)))
                else:
                    cols[name] = dput(_pad_rows(rebased, dev_rows, np.nan))
                vbases[name] = dput(_pad_rows(np.asarray(vb), dev_rows, 0))
                host_vbases[name] = np.asarray(vb, np.float64)
                fin = np.isfinite(corrected)
                vbase_valid[name] = fin.any(axis=1)
                # counted region fully finite (padding beyond counts is NaN
                # by construction and doesn't disqualify)
                pos_ok = pos >= counts[:, None]
                if interval:
                    col_finite[name] = False
                    whole &= (fin | pos_ok).all(axis=1)
                else:
                    col_finite[name] = bool(
                        (fin | pos_ok[..., None] if fin.ndim == 3
                         else fin | pos_ok).all())
                if is_counter:
                    raw = np.asarray(arr[:s, :t], np.float64)
                    lr, cd = _tail_state(raw, corrected)
                    last_raw[name] = lr
                    cum_drop[name] = cd
        # single publication point (GIL-atomic): see _MirrorSnapshot
        self._snap = _MirrorSnapshot(gen0, base_ms,
                                     len(ts_row0) if interval else t,
                                     dput(_pad_rows(ts_off, dev_rows,
                                                    PAD_TS)),
                                     cols, vbases,
                                     shift_version=store.shift_version,
                                     counts=counts, host_vbases=host_vbases,
                                     tail_last_raw=last_raw,
                                     tail_cum_drop=cum_drop,
                                     vbase_valid=vbase_valid,
                                     ts_row0=ts_row0, phase=phase,
                                     phase_rows=phase_rows,
                                     phase_dev=self._phase_dev(
                                         phase, phase_rows, dev_rows, dput),
                                     col_finite=col_finite,
                                     interval=interval,
                                     placed_rows=placed_rows, whole=whole)
        # the histogram records the WHOLE refresh wall (host prep +
        # uploads: the operational "how long did the rebuild take");
        # the per-query tally gets only the device-dispatch share
        metrics_registry.histogram("device_mirror_full_upload_seconds") \
            .record(_time.perf_counter() - _t0)
        self._book(nbytes)
        # attribute the upload to whichever exec node triggered it (the
        # background-rebuild thread's tally is simply never consumed)
        note_transfer(nbytes, xfer_s)
        note_mirror_refresh("full")
        # ledger entry (kind=transfer): stats attribution is already
        # handled by note_transfer above, so note=False — the ring and
        # per-device byte counters still see the upload
        from filodb_tpu.utils.devicetelem import telem
        telem.record_dispatch("mirror_upload_full", device=self.device,
                              shape=f"S{s}xT{t}", seconds=xfer_s,
                              bytes_in=nbytes, kind="transfer",
                              note=False)
        return True

    @staticmethod
    def _phase_dev(phase, phase_rows: int, dev_rows: int, dput):
        """The phases as the fused kernel takes them: a [rows, 1] f32
        column on the device, 0 on the rows past the store's.  None where
        every phase is 0: the unphased variant reads none."""
        if not phase_rows:
            return None
        return dput(_pad_rows(phase.astype(np.float32)[:, None], dev_rows, 0))

    def is_fresh(self, store) -> bool:
        snap = self._snap
        return snap is not None and store.generation == snap.gen

    def ensure_fresh(self, store) -> bool:
        """Re-upload if the store moved on.  Callers must exclude writers
        (hold the shard write_lock) — the refresh copies host arrays and
        must not race a mutation.  Append-only changes take the incremental
        path (transfer O(new samples), not O(S*T)); anything that
        rearranged cells falls back to a full upload.  Returns False when
        the store exceeds the HBM cap (callers fall back to host gather)."""
        if self.is_fresh(store):
            return True
        snap = self._snap
        if snap is not None and snap.shift_version == store.shift_version \
                and snap.counts is not None:
            try:
                if self._refresh_incremental(store, snap):
                    return True
            except Exception as e:  # noqa: BLE001 — incremental is an
                # optimization, but its failures must be DIAGNOSABLE: a
                # bare counter hid incremental-path regressions in soaks
                # (every query silently re-paying the full upload)
                from filodb_tpu.utils.metrics import (log_error_once,
                                                      registry)
                registry.counter(
                    "device_mirror_incremental_errors").increment()
                log_error_once("device_mirror_incremental", e)
        # the full upload (host prep + device_put of the whole store), on
        # whichever thread pays it: a query's, or the background rebuild's
        from filodb_tpu.utils.metrics import span
        with span("mirror.full_upload"):
            return self._refresh(store)

    # ------------------------------------------------- background rebuild

    def can_update_inline(self, store) -> bool:
        """True when freshness is restorable without an O(S*T) full
        re-upload: the cold first build (nothing to serve from anyway)
        and append-only growth (incremental tail upload).  False exactly
        when eviction/compaction REARRANGED cells (shift_version moved) —
        the case whose inline cost was the 752 s query p99 of the
        round-5 soak (PERF.md section 7)."""
        snap = self._snap
        return snap is None or snap.shift_version == store.shift_version

    @property
    def rebuild_in_progress(self) -> bool:
        t = self._bg_thread
        return t is not None and t.is_alive()

    def request_background_refresh(self, shard, store) -> bool:
        """Kick off (at most one) background full rebuild; returns True if
        this call started it.  Queries keep serving via the host-gather
        fallback until the new snapshot publishes; the rebuild takes the
        shard write lock only for its host-copy + upload, exactly like
        the inline path did — just not on any query's critical path."""
        with self._bg_lock:
            if self._bg_thread is not None and self._bg_thread.is_alive():
                return False
            t = threading.Thread(target=self._bg_refresh,
                                 args=(shard, store), daemon=True,
                                 name=f"mirror-rebuild-{self.serial}")
            self._bg_thread = t
            t.start()
            return True

    def _bg_refresh(self, shard, store) -> None:
        from filodb_tpu.utils.events import journal
        from filodb_tpu.utils.jobs import jobs
        from filodb_tpu.utils.metrics import (log_error_once, registry,
                                              span)
        # progress gauge: >0 while rebuilds are off-path in flight, so an
        # operator watching /metrics sees the eviction recovery running
        # (the span histogram records its duration when it completes)
        _note_rebuild(+1)
        # per-shard handle: concurrent rebuilds of different shards must
        # not share tick state (one shard's success would reset another
        # persistently-failing shard's streak mid-tick)
        sn = getattr(shard, "shard_num", -1)
        job = jobs.register(
            "mirror_rebuild",
            dataset=f"{getattr(shard, 'dataset', '')}/{sn}")
        journal.emit("mirror_rebuild_started", subsystem="mirror",
                     shard=sn)
        try:
            with job.tick():
                job.set_progress(f"shard {sn}")
                with span("mirror_bg_rebuild", hist=True):
                    with shard._write_locked("mirror_bg_rebuild"):
                        ok = self.ensure_fresh(store)
            if ok:
                registry.counter("device_mirror_bg_rebuilds").increment()
            journal.emit("mirror_rebuild_done", subsystem="mirror",
                         shard=sn, ok=ok)
        except Exception as e:  # noqa: BLE001 — queries already fall back
            registry.counter("device_mirror_bg_rebuild_errors").increment()
            log_error_once("device_mirror_bg_rebuild", e)
            journal.emit("mirror_rebuild_failed", subsystem="mirror",
                         shard=sn, error=f"{type(e).__name__}: {e}")
        finally:
            _note_rebuild(-1)

    def _refresh_incremental(self, store, snap: _MirrorSnapshot) -> bool:
        """Upload only the appended tail cells.  Sound exactly when nothing
        rearranged existing cells (shift_version unchanged) and counts only
        grew; returns False to request a full refresh otherwise."""
        import time as _time

        import jax
        import jax.numpy as jnp

        from filodb_tpu.ops.counter import host_counter_correct
        from filodb_tpu.ops.timewindow import series_value_base
        from filodb_tpu.utils.metrics import (note_mirror_refresh,
                                              note_transfer,
                                              registry as metrics_registry)

        gen0 = store.generation
        s_old = snap.counts.shape[0]
        s_new = store.num_series
        t_new = max(store.time_used, 1)
        # a placed snapshot's columns are slots: it may hold more of them
        # than any row has samples, and an appended sample goes to the slot
        # of its timestamp, never to the row's count
        placed = snap.interval > 0
        if s_new < s_old or (t_new < snap.t_used and not placed):
            return False
        if set(n for n, a in store.cols.items() if a is not None) \
                != set(snap.cols):
            return False                 # a column appeared (e.g. hist alloc)
        nbytes_new = self._nbytes(store)
        if nbytes_new > self.hbm_limit_bytes:
            return False
        if self.device is not None:
            # reserve the grown size BEFORE the tail upload (same
            # check-then-upload hazard as the full path: co-located
            # mirrors appending concurrently must see each other);
            # over the aggregate limit falls through to _refresh,
            # whose own check degrades to host gathers
            self._book(nbytes_new)
            if placer.booked(self.device) > self.hbm_limit_bytes:
                return False
        counts_new = store.counts[:s_new].astype(np.int32).copy()
        counts_old = np.zeros(s_new, dtype=np.int32)
        counts_old[:s_old] = snap.counts
        delta = counts_new - counts_old
        if (delta < 0).any():
            return False
        total_new = int(delta.sum())
        if total_new == 0 and s_new == s_old and (
                placed or t_new == snap.t_used):
            # bookkeeping-only generation bump: `data_gen` stays
            self._snap = dataclasses.replace(snap, gen=gen0)
            return True
        if total_new == 0 and placed:
            return False                 # empty new rows: the full build's
        if total_new == 0:
            # series/time grew but no new cells (e.g. new rows whose batch
            # was dropped as out-of-order): pad-only, no scatter to build
            return self._refresh_pad_only(store, snap, gen0, s_new, t_new)
        if total_new > 0.5 * s_new * t_new:
            return False                 # full upload is cheaper
        rows = np.flatnonzero(delta > 0)
        # flat (row, pos) scatter indices over all new cells
        n_new = delta[rows]
        idx_r = np.repeat(rows, n_new)
        starts = counts_old[rows]
        idx_p = (np.arange(total_new)
                 - np.repeat(np.cumsum(n_new) - n_new, n_new)
                 + np.repeat(starts, n_new))
        new_ts = store.ts[idx_r, idx_p]
        off = new_ts - snap.base_ms
        if off.size and (off.min() <= -(1 << 30) or off.max() >= (1 << 30)):
            return False                 # out of int32 offset range: re-base
        phase = ts_row0 = None
        if placed:
            # a row's phase is its first sample's: a row new since the
            # snapshot (or empty in it) brings its own
            phase = np.zeros(s_new, np.int64)
            phase[:s_old] = snap.phase
            empty = counts_old[rows] == 0
            fresh = rows[empty]
            row0 = int(snap.ts_row0[0])
            at0 = np.cumsum(n_new) - n_new       # each row's first new cell
            phase[fresh] = (off[at0[empty]] - row0) % snap.interval
            idx_p, rem = np.divmod(off - phase[idx_r] - row0, snap.interval)
            t_new = max(snap.t_used, int(idx_p.max()) + 1)
            if rem.any() or idx_p.min() < 0 \
                    or not _slots_fit(t_new, int(counts_new.max()),
                                      snap.interval):
                # a sample that fits no slot (a late scrape, a row that
                # starts before the grid does): which rows are off the grid
                # is the full build's to say
                return False
            ts_row0 = (row0 + np.arange(t_new, dtype=np.int64)
                       * snap.interval).astype(np.int32)

        # device-dispatch share of the refresh (scatter/pad/upload ops);
        # host math (counter correction, vbase bookkeeping) stays out so
        # the per-query transfer attribution names actual device work
        xfer_s = 0.0
        dS, dT = s_new - s_old, t_new - snap.t_used
        # the device arrays hold _mirror_rows(series) rows: they grow by
        # rungs of that ladder, not by series
        dev_rows = _mirror_rows(s_new)
        dR = max(dev_rows - snap.ts_off.shape[0], 0)
        _td = _time.perf_counter()
        ts_dev = snap.ts_off
        if dR or dT:
            ts_dev = jnp.pad(ts_dev, ((0, dR), (0, dT)),
                             constant_values=PAD_TS)
        if placed:
            # every slot's own time, filled or not: the new slots of every
            # row, and every slot of the rows that brought a phase
            if dT:
                ts_dev = ts_dev.at[:s_new, snap.t_used:].set(
                    (ts_row0[None, snap.t_used:]
                     + phase[:, None]).astype(np.int32))
            if fresh.size:
                ts_dev = ts_dev.at[fresh].set(
                    (ts_row0[None, :] + phase[fresh, None]).astype(np.int32))
        else:
            ts_dev = ts_dev.at[idx_r, idx_p].set(off.astype(np.int32))
        xfer_s += _time.perf_counter() - _td

        # phase-grid preservation: every row appended as many samples, each
        # the base row's new offsets plus its own phase, and the base row's
        # least gap still exceeds every phase
        whole = s_new == s_old and rows.size == s_new
        if snap.ts_row0 is not None and whole and not placed \
                and bool((delta == delta[0]).all()):
            off2 = off.reshape(s_new, -1) - snap.phase[:, None]
            start0, k = int(counts_old[0]), off2.shape[1]
            grown = np.concatenate([snap.ts_row0[max(start0 - 1, 0):start0],
                                    off2[0]])
            gap = int(np.diff(grown).min()) if grown.size > 1 else 0
            if bool((off2 == off2[0:1]).all()) and (
                    not snap.phase_rows or grown.size < 2
                    or (gap > int(snap.phase.max())
                        and _slot_times_exact(int(grown[-1]) + 2 * gap
                                              - int(snap.ts_row0[0])))):
                ts_row0 = np.full(t_new, PAD_TS, np.int32)
                ts_row0[:snap.t_used] = snap.ts_row0
                ts_row0[start0:start0 + k] = off2[0].astype(np.int32)
        kept = ts_row0 is not None
        phase_dev, phase_rows = snap.phase_dev, snap.phase_rows
        placed_rows = 0
        if placed:
            phase = phase.astype(np.int32)
            placed_rows = int((counts_new != t_new).sum())
            phase_rows = _note_phase_grid(self.shard_num, phase, 0,
                                          placed_rows)
            if fresh.size or dR:
                _td = _time.perf_counter()
                phase_dev = self._phase_dev(
                    phase, phase_rows, dev_rows,
                    lambda x: jax.device_put(x, self.device))
                xfer_s += _time.perf_counter() - _td
        elif kept:
            phase = snap.phase
        if snap.ts_row0 is not None and not kept:
            # the grid is lost; which rows left it is the next full
            # build's to say: here, the rows that appended otherwise than
            # most did
            usual = int(np.bincount(delta).argmax())
            _note_phase_grid(self.shard_num, None,
                             max(int((delta != usual).sum()), 1))

        counter_cols = {c.name for c in store.schema.data_columns
                        if c.detect_drops or c.counter}
        new_cols: Dict[str, object] = {}
        new_vbases: Dict[str, object] = {}
        host_vbases = dict(snap.host_vbases)
        last_raw = dict(snap.tail_last_raw)
        cum_drop = dict(snap.tail_cum_drop)
        vbase_valid = dict(snap.vbase_valid)
        col_finite = dict(snap.col_finite)
        whole = None
        if placed:
            # a row stays whole while it fills every slot the grid gains
            # with a finite sample; a row new since the snapshot is whole
            # if it fills the grid (all its samples are among the new)
            whole = counts_new == t_new
            whole[:s_old] &= snap.whole
        for name, dev in snap.cols.items():
            arr = store.cols[name]
            hist = arr.ndim == 3
            tails, valid = _tails_matrix(arr, rows, counts_old, counts_new)
            vb = host_vbases[name]
            vb_new = np.zeros((s_new,) + vb.shape[1:], np.float64)
            vb_new[:s_old] = vb
            if name in counter_cols:
                lr = np.full((s_new,) + vb.shape[1:], np.nan)
                lr[:s_old] = last_raw[name]
                cd = np.zeros((s_new,) + vb.shape[1:], np.float64)
                cd[:s_old] = cum_drop[name]
                seed = lr[rows][:, None] if not hist else \
                    lr[rows][:, None, :]
                seeded = np.concatenate([seed, tails], axis=1)
                corr_seeded = host_counter_correct(seeded)
                corrected = corr_seeded[:, 1:] + (
                    cd[rows][:, None, :] if hist else cd[rows][:, None])
                n_lr, n_cd = _tail_state(seeded, corr_seeded)
                upd = np.isfinite(n_lr)
                lr[rows] = np.where(upd, n_lr, lr[rows])
                cd[rows] = np.where(
                    upd, (cd[rows] + n_cd), cd[rows])
                last_raw[name] = lr
                cum_drop[name] = cd
                vals = corrected
            else:
                vals = tails
            # (re)establish vbase for any row/bucket whose base never came
            # from a finite sample: the first finite appended value becomes
            # the base — without this, large counters appended to a
            # previously-all-NaN row land on device un-rebased and their
            # f32 deltas vanish
            vv = np.zeros((s_new,) + vb.shape[1:], dtype=bool)
            vv[:s_old] = vbase_valid[name]
            tail_fin = np.isfinite(vals).any(axis=1)       # [R(, B)]
            tail_base = series_value_base(vals)            # [R(, B)]
            upd_vb = (~vv[rows]) & tail_fin
            vb_changed = bool(upd_vb.any())
            if vb_changed:
                vb_new[rows] = np.where(upd_vb, tail_base, vb_new[rows])
            vv[rows] = vv[rows] | tail_fin
            vbase_valid[name] = vv
            host_vbases[name] = vb_new
            # rebased cell values, flattened to the scatter order (row-major
            # over [rows, ascending positions] — exactly idx_r/idx_p order)
            rb = vals - (vb_new[rows][:, None, :] if hist
                         else vb_new[rows][:, None])
            flat = rb[valid]
            col_finite[name] = bool(col_finite.get(name, False)
                                    and np.isfinite(flat).all())
            if placed:
                whole[rows] &= (np.isfinite(rb) | ~valid).all(axis=1)
            _td = _time.perf_counter()
            col_dev = dev
            if dR or dT:
                pad = ((0, dR), (0, dT)) + (((0, 0),) if hist else ())
                col_dev = jnp.pad(col_dev, pad, constant_values=np.nan)
            new_cols[name] = col_dev.at[idx_r, idx_p].set(
                flat.astype(col_dev.dtype))
            vb_dev = snap.vbases[name]
            if dS or vb_changed:
                new_vbases[name] = jax.device_put(
                    _pad_rows(vb_new.astype(vb_dev.dtype), dev_rows, 0),
                    self.device)
            else:
                new_vbases[name] = vb_dev
            xfer_s += _time.perf_counter() - _td

        metrics_registry.counter("device_mirror_incremental").increment()
        metrics_registry.gauge("device_mirror_bytes").update(
            self._nbytes(store))
        self._snap = _MirrorSnapshot(
            gen0, snap.base_ms, t_new, ts_dev, new_cols, new_vbases,
            shift_version=store.shift_version, counts=counts_new,
            host_vbases=host_vbases, tail_last_raw=last_raw,
            tail_cum_drop=cum_drop, vbase_valid=vbase_valid,
            ts_row0=ts_row0, col_finite=col_finite,
            # the phases are the rows' own: they stand while the grid does
            phase=phase if kept else None,
            phase_rows=phase_rows if kept else 0,
            phase_dev=phase_dev if kept else None,
            interval=snap.interval, placed_rows=placed_rows, whole=whole)
        # appended-tail transfer size: int32 ts offsets + each column's
        # per-cell bytes over the new cells only
        per_cell = 4 + sum(
            a.itemsize * (a.shape[2] if a.ndim == 3 else 1)
            for a in (store.cols[n] for n in snap.cols) if a is not None)
        metrics_registry.counter("device_mirror_upload_bytes",
                                 kind="incremental").increment(
                                     total_new * per_cell)
        note_transfer(total_new * per_cell, xfer_s)
        note_mirror_refresh("incremental")
        self._book(self._nbytes(store))
        from filodb_tpu.utils.devicetelem import telem
        telem.record_dispatch("mirror_upload_incr", device=self.device,
                              shape=f"cells{total_new}", seconds=xfer_s,
                              bytes_in=total_new * per_cell,
                              kind="transfer", note=False)
        return True

    def _refresh_pad_only(self, store, snap, gen0: int, s_new: int,
                          t_new: int) -> bool:
        """Grow the snapshot to [s_new, t_new] when no cell values changed
        (new rows registered but their samples were all dropped, or the time
        axis grew without appends).  New rows start empty: PAD_TS offsets,
        NaN values, invalid vbase."""
        import jax.numpy as jnp

        from filodb_tpu.utils.metrics import registry as metrics_registry
        dS, dT = s_new - snap.counts.shape[0], t_new - snap.t_used
        s_old = snap.counts.shape[0]
        dev_rows = _mirror_rows(s_new)      # as _refresh_incremental
        dR = max(dev_rows - snap.ts_off.shape[0], 0)
        ts_dev = jnp.pad(snap.ts_off, ((0, dR), (0, dT)),
                         constant_values=PAD_TS) if (dR or dT) else snap.ts_off
        new_cols, new_vbases = {}, {}
        host_vbases, last_raw = dict(snap.host_vbases), dict(snap.tail_last_raw)
        cum_drop, vbase_valid = dict(snap.tail_cum_drop), dict(snap.vbase_valid)

        for name, dev in snap.cols.items():
            if dR or dT:
                pad = ((0, dR), (0, dT)) + \
                    (((0, 0),) if dev.ndim == 3 else ())
                dev = jnp.pad(dev, pad, constant_values=np.nan)
            new_cols[name] = dev
            host_vbases[name] = _pad_rows(host_vbases[name], s_new, 0.0)
            vbase_valid[name] = _pad_rows(vbase_valid[name], s_new, False)
            if name in last_raw:
                last_raw[name] = _pad_rows(last_raw[name], s_new, np.nan)
                cum_drop[name] = _pad_rows(cum_drop[name], s_new, 0.0)
            vb_dev = snap.vbases[name]
            if dS:
                import jax
                vb_dev = jax.device_put(
                    _pad_rows(host_vbases[name].astype(vb_dev.dtype),
                              dev_rows, 0), self.device)
            new_vbases[name] = vb_dev

        counts_new = np.zeros(s_new, dtype=np.int32)
        counts_new[:s_old] = snap.counts
        metrics_registry.counter("device_mirror_incremental").increment()
        self._book(self._nbytes(store))
        # pad-only is only reachable with new (empty) rows — dS > 0, since
        # time_used == counts.max() makes pure time growth impossible with
        # zero new cells — and empty rows always break the grid (ts_row0
        # stays None)
        if snap.ts_row0 is not None:
            _note_phase_grid(self.shard_num, None, max(dS, 1))
        self._snap = _MirrorSnapshot(
            gen0, snap.base_ms, t_new, ts_dev, new_cols, new_vbases,
            shift_version=store.shift_version, counts=counts_new,
            host_vbases=host_vbases, tail_last_raw=last_raw,
            tail_cum_drop=cum_drop, vbase_valid=vbase_valid,
            col_finite=dict(snap.col_finite))
        return True

    def snapshot(self):
        """The current immutable snapshot (None before first refresh).
        Callers that combine gather_cached with fused_eligible MUST read
        the snapshot once and pass it to both — re-reading _snap between
        the calls can pair one snapshot's grid with another's values."""
        return self._snap

    def fused_eligible(self, col_name: str, snap=None,
                       allow_ragged: bool = False) -> Optional[np.ndarray]:
        """The base row's ts offsets (int32 [T], PAD_TS beyond counts)
        when the snapshot meets the pallas_fused preconditions for this
        column — its rows on one phase grid (_MirrorSnapshot) and (unless
        allow_ragged) a fully-finite counted region — else None.
        allow_ragged admits NaN-holed values on the grid: the
        validity-weighted fused kinds handle those
        (ops/pallas_fused.can_fuse dense=False).  Any row subset of a
        phase grid lies on the same base row with the same phases; a
        leaf takes its rows' phases with MirrorGather.deferred("phase")
        (None where the snapshot has none: one shared timestamp row)."""
        snap = snap if snap is not None else self._snap
        if snap is None or snap.ts_row0 is None:
            return None
        if not snap.col_finite.get(col_name, False) and not allow_ragged:
            return None
        return snap.ts_row0

    def col_dense(self, col_name: str, snap=None) -> bool:
        """True when the column's counted region has no NaN holes."""
        snap = snap if snap is not None else self._snap
        return bool(snap is not None
                    and snap.col_finite.get(col_name, False))

    def gather_cached(self, rows: np.ndarray, snap=None
                      ) -> Optional["MirrorGather"]:
        """The requested rows of the current snapshot as a MirrorGather —
        no device work, no host reads, no freshness check, so it runs
        outside any lock: the snapshot is immutable and was fresh when
        ensure_fresh validated it (a concurrent refresh just publishes a
        new snapshot; the handle keeps its own).  An array's rows are
        taken when it is first read.  Offsets are relative to the handle's
        base_ms; values rebased by the vbases.  Pass `snap` (from
        .snapshot()) to pin a specific snapshot when pairing with other
        per-snapshot reads.  None before the first refresh."""
        snap = snap if snap is not None else self._snap
        if snap is None:
            return None
        from filodb_tpu.utils.metrics import registry
        registry.counter("mirror_gather_deferred").increment()
        return MirrorGather(self.device, snap, rows)

    @property
    def base_ms(self) -> int:
        snap = self._snap
        return snap.base_ms if snap is not None else 0


class DeferredRows:
    """One array of a MirrorGather that nobody has read yet: its shape,
    dtype and ndim are known, its rows leave the mirror at resolve()."""
    __slots__ = ("_gather", "_array", "_col", "shape", "dtype")

    def __init__(self, gather: "MirrorGather", array: str,
                 col: Optional[str]):
        self._gather, self._array, self._col = gather, array, col
        self.shape, self.dtype = gather.spec(array, col)

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def resolve(self, rows_to: Optional[int] = None,
                whole_first: bool = False):
        """The array; with `rows_to`, its rows followed by zero rows up to
        that count; with `whole_first`, in the layout of a working set
        stored whole rows first where the rows have one (MirrorGather
        ._take, .whole_first)."""
        return self._gather._take(self._array, self._col, rows_to=rows_to,
                                  whole_first=whole_first)

    def host(self) -> np.ndarray:
        """The rows of the snapshot's host copy (`phase` keeps one): no
        device work."""
        return self._gather.snap.phase[self._gather.rows]

    @property
    def placed(self) -> bool:
        """Whether the rows come out of a placed snapshot (which keeps
        the fact of each row that `whole_first` orders a set by)."""
        return self._gather.snap.whole is not None

    def whole_first(self):
        """MirrorGather.whole_first of the rows."""
        return self._gather.whole_first()


class MirrorGather:
    """Rows of one mirror snapshot, gathered by need.

    Made by DeviceMirror.gather_cached with no device work: every array's
    shape and dtype follow from the snapshot and the row count.  The
    handle pins the snapshot it was made from — the one the leaf
    validated fresh — so a refresh that publishes a newer one before an
    array is read changes nothing that is read.  Each array (`ts_off`
    [R, T]; a column under `values`, [R, T] or [R, T, B]; its `vbase`,
    [R] or [R, B]; `phase`, [R, 1] f32) is taken on its first read, that
    array only, with the row index uploaded once for all of them; a take
    that raised is not remembered, so the next read tries again.  The
    results live on the handle: keep it no longer than the leaf's
    execution and in no cache (it holds a whole snapshot alive)."""
    __slots__ = ("device", "snap", "rows", "_idx", "_taken", "_layout")

    def __init__(self, device, snap: _MirrorSnapshot, rows: np.ndarray):
        self.device = device
        self.snap = snap
        self.rows = rows
        self._idx: Dict[Optional[int], object] = {}   # by `rows_to`
        self._taken: Dict[Tuple, object] = {}
        self._layout = ()               # whole_first's answer, once asked

    @property
    def base_ms(self) -> int:
        return self.snap.base_ms

    def _source(self, array: str, col: Optional[str]):
        if array == "ts_off":
            return self.snap.ts_off
        if array == "phase":
            return self.snap.phase_dev
        return (self.snap.cols if array == "values"
                else self.snap.vbases)[col]

    def spec(self, array: str, col: Optional[str] = None):
        """(shape, dtype) the array will have once taken."""
        src = self._source(array, col)
        return (len(self.rows),) + tuple(src.shape[1:]), src.dtype

    def deferred(self, array: str, col: Optional[str] = None
                 ) -> Optional[DeferredRows]:
        """The array as a leaf's block carries it: taken when the block's
        field of that name is first read, and counted under it.  None for
        a column the snapshot keeps no vbase of, and for the phases of a
        snapshot none of whose rows has one."""
        if array == "vbase" and col not in self.snap.vbases:
            return None
        if array == "phase" and self.snap.phase_dev is None:
            return None
        return DeferredRows(self, array, col)

    def read(self, array: str, col: Optional[str] = None):
        """The array itself, for a reader that is no block's field (a
        column beside the leaf's own): counted as `other`."""
        return self._take(array, col,
                          counted="ts_off" if array == "ts_off" else "other")

    def whole_first(self):
        """-> (at, Sw) where the handle's rows, as a fused working set,
        are stored WHOLE ROWS FIRST: row i stands at `at[i]` and the rows
        with a hole start at row `Sw`, each part padded to a rung of the
        series ladder of its own (pallas_fused.whole_first, from the
        snapshot's fact of each row); None where the snapshot keeps no
        such fact (it is not placed) or the rows are all of one kind.
        One pass over the rows at the first call, which a leaf makes
        where it pads a working set (a miss of its cache), and never a
        request that finds the set."""
        if self._layout == ():
            from filodb_tpu.ops.pallas_fused import whole_first
            fact = self.snap.whole
            self._layout = None if fact is None \
                else whole_first(fact[self.rows])
        return None if self._layout is None else self._layout[1:]

    def _take(self, array: str, col: Optional[str],
              counted: Optional[str] = None, rows_to: Optional[int] = None,
              whole_first: bool = False):
        """`rows_to`: take that many rows, the handle's own and then rows
        of zeros.  The fused leaf asks for its padded row count
        (pallas_fused.pad_series_count), so that the take and everything
        made from it compile once a rung of that ladder and not once a
        row count: a chip's 30 shards, or a selector's 20 row subsets, are
        a handful of programs.  `whole_first`: where the rows have that
        layout (`whole_first`), take them in it, each part's rows and
        then rows of zeros up to its rung, in place of `rows_to`."""
        if whole_first and self.whole_first() is not None:
            rows_to = "whole_first"
        got = self._taken.get((array, col, rows_to))
        if got is not None:
            return got
        import jax.numpy as jnp

        from filodb_tpu.utils.devicetelem import telem
        from filodb_tpu.utils.metrics import registry, span
        src = self._source(array, col)
        with span("leaf.mirror_gather") as taking:
            idx = self._idx.get(rows_to)
            if idx is None:
                rows = self.rows.astype(np.int32)
                if rows_to == "whole_first":
                    index = self._layout[0]
                    rows = np.where(index >= 0, rows[index], np.int32(
                        np.iinfo(np.int32).max))
                elif rows_to is not None:
                    # past every mirror's rows: such an index reads as the
                    # fill value
                    rows = np.concatenate([rows, np.full(
                        rows_to - rows.size, np.iinfo(np.int32).max,
                        np.int32)])
                idx = self._idx[rows_to] = jnp.asarray(rows)
            got = jnp.take(src, idx, axis=0, mode="fill", fill_value=0)
        self._taken[(array, col, rows_to)] = got
        registry.counter("mirror_gather_takes",
                         array=counted or array).increment()
        telem.record_dispatch("mirror_gather", device=self.device,
                              shape=f"rows{len(self.rows)}",
                              seconds=taking.dur_s)
        return got
