"""Pallas TPU kernel: fused windowed-rate + group-sum in one HBM pass.

The headline query shape — `sum by (...) (rate(counter[5m]))` — costs the
XLA path several passes over the [S, T] value matrix (validity mask, reset
correction scan, boundary gathers, then a scatter-add segment sum).  On a
bandwidth-bound chip the passes are the latency.  This kernel computes the
whole thing in ONE read of the values, from a few host-built window rows
(kernel_operands):

- boundary selections  v[:, first[w]], v[:, last[w]]  ->  per-128-lane-tile
  dynamic gathers at the host-built indices (_gather_cols)
- cumulative reset corrections  ->  an in-row prefix sum of the drops,
  selected by the same gathers (drops[s, t] = max(prev - cur, 0) is local
  once rows are dense)
- *_over_time window sums  ->  v @ band, band[t, w] = 1{first[w] <= t <=
  last[w]}, a 0/1 matrix built on the device: whole, before the kernel
  (the resident form), or 512 columns at a time inside it (the tiled
  form, `_band_dot`), whichever fits the larger series block (`band_form`)
- group segment-sum  ->  onehot(gids) @ rate  on the MXU

Preconditions (the caller gates, see `can_fuse`): one scrape grid across
series (the devicecache invariant: every row on one base timestamp row, or
behind it by a phase of its own, which the `phased` variant takes) and
dense rows — no NaN inside the counted region (the `ragged` variants take
NaN-holed ones).  Anything else falls back to the general
XLA path in ops/rangefns.py; semantics here match it bit-for-bit in f32
(same extrapolation rules, ref: RateFunctions.scala:37-76; same 3-phase
aggregate contract, ref: exec/AggrOverRangeVectors.scala:17-125).

Works on CPU via interpret=True (tests); on TPU via the MXU.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading
from typing import NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_LANE = 128
_MIN_BS = 32
_BS = 256
"""Series rows per grid step (VMEM-sized; a power of two, which padding
and pick_block's halving both assume).  pick_block shrinks from here
whenever the VMEM estimate demands it."""


def kernel_mode() -> Optional[bool]:
    """How the Pallas kernel may run in this process — the fused leaf's
    gate (query/leafexec.py).  False: compiled for the chip, which is
    what a `tpu` backend always gets (FILODB_TPU_FUSED_INTERPRET has no
    effect there).  True: interpret mode, only on a backend without an
    MXU and only under the test-only switch FILODB_TPU_FUSED_INTERPRET.
    None: not at all — the caller takes its general or host path."""
    if jax.default_backend() == "tpu":
        return False
    return True if os.environ.get("FILODB_TPU_FUSED_INTERPRET") else None


def _selects_by_gather(kind: str) -> bool:
    """Which kinds select their window boundaries by exact
    per-128-lane-tile dynamic gathers at host-built indices (_gather_cols:
    pure data movement) instead of one-hot selection matmuls: the rate
    family and last_over_time.  The over_time band kinds keep their
    window-sum matmuls: a cumsum + gather-difference form would change
    the f32 summation order."""
    return kind in ("rate_family", "last_over_time")


# The MXU's default single bf16 pass truncates f32 mantissas (1e-2
# relative error on counter magnitudes).  Every matmul of the kernel has
# one operand that is exact in bf16 (a 0/1 band, one-hot or validity
# matrix), so each gets the fewest passes that keep the other side's bits.
# (Mosaic lowers only DEFAULT and HIGHEST; Precision.HIGH and per-operand
# precision tuples are rejected.)

def _dot_hi(a, b):
    """values x band (the over_time kinds' window sums): full f32
    emulation, about six bf16 passes that Mosaic fuses into one schedule."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _dot_1p(a, b):
    """binary x binary (validity and presence counts): one bf16 MXU pass
    with f32 accumulation is exact on 0/1 operands."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.DEFAULT)


def _split3(x):
    """x == hi + mid + lo with hi/mid exactly bf16-representable and lo
    carrying the last ~8 mantissa bits (its own bf16 truncation error is
    ~|x|*2^-24, i.e. f32 epsilon)."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    r = x - hi
    mid = r.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, mid, r - mid


def _dot_split3(a, b):
    """binary x values (the group epilogue, the rate family's only large
    matmul): the decomposition HIGHEST would do internally, spelled out on
    the values side only, so three single passes instead of six.  The hi
    and mid passes are exact; the lo pass carries the |v|*2^-24 truncation
    that f32 storage of the values already imposes on every path."""
    hi, mid, lo = _split3(b)
    return _dot_1p(a, hi) + _dot_1p(a, mid) + _dot_1p(a, lo)


def _dot_values_split3(a, b):
    """values x binary (the tiled band's window sums, `_band_dot`):
    `_dot_split3` with the values on the left, each of their three parts
    summed on its OWN: a window's hi parts (eight bits each) add up in the
    MXU's f32 accumulator without a rounding (360 samples of size 100 need
    22 bits), the mid and lo parts are 2^-9 and 2^-18 of them, and only the
    three sums' addition rounds, once a cell.  `_dot_hi` splits both sides
    (six passes, three of them against the zeros of a 0/1 operand's mid and
    lo) into ONE accumulator, where every addition rounds at the size of
    the running sum: over an hour of a 10 s scrape rebased by 50 the mean
    came out 5e-5 off, which is all of an hourly mean near 1 that was read
    as 2e-5 relative (PERF.md section 6, PR 48)."""
    hi, mid, lo = _split3(a)
    return _dot_1p(hi, b) + _dot_1p(mid, b) + _dot_1p(lo, b)


def _pad_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _bucket_up(n: int, quantum: int, exact_below: int) -> int:
    """Round n up to a {8/8, 9/8, ..., 16/8} x 2^k geometric ladder of
    quantum multiples (adjacent rungs <= 1.125x, so padding <= 12.5%) —
    shape canonicalization so near-identical working sets share ONE
    compiled program.

    Under live ingest the series count drifts every snapshot refresh;
    without bucketing each drift changes Sp and every query pays a full
    XLA recompile (measured 43-73 s at 262k-1M in round 4) — the
    prime suspect for that round's 9x query degradation under a soak
    (PERF.md section 7, "Before the chip benchmark").  Below
    `exact_below` the plain quantum pad is kept: small shapes are cheap
    to compile and common in tests that assert exact padding."""
    if n <= exact_below:
        return _pad_to(max(n, 1), quantum)
    k = 1
    while quantum * 16 * k < n:
        k *= 2
    for m in range(8, 17):
        cand = quantum * k * m
        if cand >= n:
            return cand
    raise AssertionError("unreachable: the loop exits with 16*k*quantum >= n")


def pad_series_count(S: int) -> int:
    """Canonical padded series count: multiple of _BS (every pick_block
    block size divides it) on the geometric ladder."""
    return _bucket_up(S, _BS, 8 * _BS)


def pad_group_count(G: int) -> int:
    """Canonical padded group count for the kernel epilogue (multiple of
    8, geometric ladder above 64 so group-count drift reuses programs)."""
    return _bucket_up(max(G, 8), 8, 64)


# row order of FusedPlan.rows, the one block an enqueue uploads
_T1, _T2, _N, _N1, _WS, _WE, _I1, _I2 = range(8)
# ... and of the eight rows FusedPlan.prows adds below them, which only a
# phased dispatch uploads and reads: the boundary slots without the empty
# windows' 0 sentinel (a row's own window may hold a sample where the base
# row's holds none), the base row's timestamps at those slots and at the
# slots before them, and the two slacks a row's phase is compared with
_PI1, _PI2, _PT1, _PT2, _PT1M, _PT2M, _PS1, _PS2 = range(8, 16)
# a slack no phase passes: the slot before the first has nothing to take
_NO_SLOT = np.float32(1 << 30)


class FusedPlan(NamedTuple):
    """Host-built query plan: the shared window rows, packed for upload.
    The selection / band matrices the matmul kinds read are a pure
    function of `idx1`, `idx2` and `n1 >= 1` and are built on the device,
    inside the jitted call (kernel_operands).  Built by `build_plan`
    alone: it gives every plan its own `resident` dict."""
    rows: np.ndarray     # [8, Wp] f32  the eight rows below, in this order
    t1: np.ndarray       # [1, Wp] f32   ts at first[w]
    t2: np.ndarray       # [1, Wp] f32   ts at last[w]
    n: np.ndarray        # [1, Wp] f32   samples in window (>= 2, math-safe)
    n1: np.ndarray       # [1, Wp] f32   TRUE samples in window (0 empty)
    wstart_x: np.ndarray  # [1, Wp] f32  window start boundary (exclusive-1)
    wend_x: np.ndarray   # [1, Wp] f32
    # boundary slot indices (first[w] / last[w]; 0 sentinel for empty +
    # padded windows): the gather kinds select columns at these positions,
    # the band kinds' one-hot / step matrices are built from them
    # (_selects_by_gather)
    idx1: np.ndarray     # [1, Wp] f32
    idx2: np.ndarray     # [1, Wp] f32
    # raw shared-grid timestamps [1, Tp] f32 (0 pad tail): the ragged rate
    # family selects per-series VALID boundary timestamps in-kernel
    tsrow: np.ndarray
    wvalid: np.ndarray   # [W] bool      n >= 2 (rate family)
    wvalid1: np.ndarray  # [W] bool      n >= 1 (*_over_time family)
    W: int
    Tp: int
    # [16, Wp] f32: `rows`, then the phased rows (_PI1 ... _PS2).  Rows of
    # a phase grid lie at `ts_row + phase[s]`, 0 <= phase[s] < the row's
    # least gap, so with edges ws < t <= we a row's first / last slot is
    # the shared one or the slot before it, by one compare against a slack
    # that depends on the window alone: first = idx1 - (phase > ws -
    # ts_row[idx1 - 1]), last = idx2 - (phase > we - ts_row[idx2]).  What
    # a dispatch of phased working sets uploads in place of `rows`.
    prows: np.ndarray
    # the widest non-empty window, in slots of the shared row (0: every
    # window is empty): how far the ragged rate family's boundary fills
    # have to carry a sample (scan_steps)
    span: int
    # the call's host operands as they lie on a device, put at the first
    # enqueue that takes them there (enqueue_operands): (device, "rows" |
    # "prows" | "tsrow") -> the device array.  Nothing else refers to
    # them, so they are freed with the plan.
    resident: dict
    # what the plan weighs in a cache: its host arrays, and those three
    # again for every local device, the most `resident` can come to hold
    nbytes: int
    # the row tiles one gather of one working set visits, summed over the
    # window tiles (_tile_ranges): (on one shared row, on a phase grid).
    # Wp / 128 x Tp / 128 when every tile is visited, as an unrolled
    # gather does (gather_loops)
    tile_visits: Tuple[int, int]
    # whether every time the kernel reads (the boundary timestamps, the
    # window edges, the shared row, the phased rows' slacks) is the same
    # number in f32: whole milliseconds are exact below 2^24 (4.66 h from
    # the row's first sample), multiples of 8 ms below 2^27.  A leaf
    # declines a plan that is not (leafexec: `leaf_inexact_times_total`)
    exact: bool
    # the columns of a row that one kernel instance computes over
    # (`_col_reach`): the slots the plan's windows reach, rounded up to
    # whole 128-lane tiles; Tp where they reach the row (or all but its
    # last tile).  Static: `_run`'s compile key where Tp was.  Under Tp
    # an instance LOADS one tile more (`_load_cols`) and turns it
    Tq: int
    # the first of those columns as a launch derives it from the rows it
    # has uploaded (`_col_offset`): (on one shared row, on a phase grid).
    # Any column, not a tile's first.  Never a compile key: the device
    # computes its own
    c0: Tuple[int, int]


def build_plan(ts_row: np.ndarray, wends: np.ndarray,
               range_ms: int) -> FusedPlan:
    """Window boundary math once, host-side (shared grid: one ts row)."""
    ts_row = np.asarray(ts_row, dtype=np.int64)
    wend = np.asarray(wends, dtype=np.int64)
    wstart = wend - int(range_ms) + 1
    first = np.searchsorted(ts_row, wstart, side="left")
    last = np.searchsorted(ts_row, wend, side="right") - 1
    n = window_counts(ts_row, wend, range_ms)
    W, T = len(wend), len(ts_row)
    Wp, Tp = _pad_to(max(W, 1), _LANE), _pad_to(max(T, 1), _LANE)
    # boundary rows cover every NON-EMPTY window (n >= 1): the over_time
    # band needs single-sample windows, and the rate family is harmless on
    # them (first == last -> delta == 0 -> contributes 0; its host mask
    # wvalid stays n >= 2)
    valid = n >= 1
    fi = np.clip(first, 0, T - 1)
    la = np.clip(last, 0, T - 1)
    rows = np.zeros((8, Wp), np.float32)
    rows[_T1, :W] = np.where(valid, ts_row[fi], 0)
    rows[_T2, :W] = np.where(valid, ts_row[la], 0)
    rows[_N, :W] = np.maximum(n, 2)        # safe: invalid windows masked out
    rows[_N1, :W] = n
    rows[_WS, :W] = wstart - 1
    rows[_WE, :W] = wend
    rows[_I1, :W] = np.where(valid, fi, 0)
    rows[_I2, :W] = np.where(valid, la, 0)
    tsr = np.zeros((1, Tp), np.float32)
    tsr[0, :T] = ts_row
    # the phased rows: slots unclipped by emptiness (clipped to the row
    # only), and the slot before each
    fm = np.clip(first - 1, 0, T - 1)
    lm = np.clip(last - 1, 0, T - 1)
    prows = np.zeros((16, Wp), np.float32)
    prows[:8] = rows
    # (the first slot may lie one past the row: the slot a row that starts
    # early takes is then the row's last)
    prows[_PI1, :W], prows[_PI2, :W] = np.clip(first, 0, T), la
    prows[_PT1, :W], prows[_PT2, :W] = ts_row[fi], ts_row[la]
    prows[_PT1M, :W], prows[_PT2M, :W] = ts_row[fm], ts_row[lm]
    prows[_PS1, :W] = np.where(first >= 1, wstart - 1 - ts_row[fm], _NO_SLOT)
    prows[_PS2, :W] = np.where(last >= 0, wend - ts_row[la], _NO_SLOT)
    wvalid, wvalid1 = n >= 2, n >= 1
    operands = rows.nbytes + prows.nbytes + tsr.nbytes
    exact = _f32_holds(ts_row, wstart - 1, wend)
    Tq = _col_reach(first, la, T, Tp)
    c0 = tuple(int(_col_offset(np, r, Tp, Tq, phased))
               for phased, r in ((False, rows), (True, prows)))
    visits = tuple(
        int(np.maximum(t[1] - t[0] + 1, 0).sum()) for t in (
            _tile_ranges(np, rows, Tq, False, c0[0]),
            _tile_ranges(np, prows, Tq, True, c0[1]))) \
        if gather_loops(Tq, Wp) else ((Tq // _LANE) * (Wp // _LANE),) * 2
    return FusedPlan(rows, *(rows[i:i + 1] for i in range(8)), tsrow=tsr,
                     wvalid=wvalid, wvalid1=wvalid1, W=W, Tp=Tp,
                     prows=prows, span=int(n.max()) if W else 0, resident={},
                     nbytes=operands * (1 + jax.local_device_count())
                     + wvalid.nbytes + wvalid1.nbytes,
                     tile_visits=visits, exact=exact, Tq=Tq, c0=c0)


def _col_reach(first, la, T: int, Tp: int) -> int:
    """-> Tq: how many columns of a row hold every slot the plan's windows
    read, in whole 128-lane tiles.  The slots run from the one BEFORE the
    earliest window's first (a row of a phase grid may take it: `fm`, the
    rate family's and a band correction's) to the latest window's last,
    over every real window, empty ones too (a row's own window may hold a
    sample where the base row's holds none).  Tq depends on how MANY slots
    the windows reach and not on where they lie: the block starts AT the
    first slot read (`_col_offset`), wherever in a tile that is, so a
    dashboard whose `end` moves with the newest sample keeps ONE program
    (a width that followed the first slot's place in its tile would flip
    between two, a tile apart, every time it crossed a tile edge: the
    churned cell's opens walk it through every offset).  The price is one
    tile more to load (`_load_cols`) and one turn of the loaded block, so
    a plan is trimmed only where that still leaves a tile out: an hour of
    `[5m]` at 60 s over a 10 s grid reaches 391 slots, 512 columns of a
    768-slot row (640 loaded); six hours of `[40s]` at 30 s reach 2,165
    of 2,304: the row."""
    if not len(first):
        return Tp
    lo = max(int(np.clip(first, 0, T).min()) - 1, 0)
    Tq = _pad_to(max(int(la.max()) - lo + 1, 1), _LANE)
    return Tq if _load_cols(Tq, Tp) < Tp else Tp


def _load_cols(Tq: int, Tp: int) -> int:
    """The columns of a row a kernel instance loads to compute over Tq of
    them: the row where Tq is the row; else one tile more than Tq, since
    the loaded block starts on a tile edge (Mosaic places a block at
    multiples of 128 lanes) up to a tile before the first column read."""
    return Tp if Tq == Tp else Tq + _LANE


def _live_firsts(rows, phased: bool):
    """(live, first): the windows whose slots a launch reads, as
    `_tile_ranges` takes them, and the row of their first slots, from a
    plan's `rows` (or its `prows` where `phased`).  On one shared row the
    non-empty windows (an empty or padded one's slot is the 0 sentinel
    and its cell is masked); on a phase grid every real window (a row's
    own window may hold a sample where the base row's holds none)."""
    if phased:
        return rows[_N] >= 2.0, rows[_PI1]
    return rows[_N1] >= 1.0, rows[_I1]


def _col_offset(xp, rows, Tp: int, Tq: int, phased: bool):
    """-> i32 scalar c0: the first of the Tq columns a launch computes
    over, from a plan's `rows` (or its `prows` where `phased`): the slot
    before the earliest first that a live window reads (as `_tile_ranges`
    takes them), held inside the row.  `xp` is numpy on the host
    (build_plan: the plan's `c0`, its tile ranges) and jax.numpy inside
    `_run`'s trace (kernel_operands: made from the rows an enqueue has
    uploaded anyway, so it is no upload and, being data, no compile key).
    With Tq from `_col_reach`, [c0, c0 + Tq) holds every slot read."""
    live, lo = _live_firsts(rows, phased)
    lo = xp.where(live, lo - 1.0, float(Tp)).min()
    return xp.clip(lo, 0, Tp - Tq).astype(xp.int32)


def _f32_holds(*times) -> bool:
    """Whether f32 holds every one of these whole numbers of milliseconds,
    and with them every difference of two (a plan's slacks), exactly: all
    are multiples of 2^k (k the fewest trailing zero bits among them) and
    under 2^(24 + k) in size.  Sufficient, and two reductions an array:
    a plan is built beside five other threads, and every NumPy call over
    an array of 500 elements or more lets the interpreter lock go."""
    low, high = 0, 0
    for t in times:
        if t.size:
            a = np.abs(t)
            low |= int(np.bitwise_or.reduce(a))
            high = max(high, int(a.max()))
    if not low:
        return True
    return high < (1 << 24) * (low & -low)


def _row_steps(Tp: int) -> int:
    """The doubling steps a fill takes to cross a row of Tp slots."""
    return max(Tp - 1, 1).bit_length()


def scan_steps(plan: FusedPlan, kind: str, ragged: bool,
               phased: bool = False) -> int:
    """The doubling steps the ragged rate family's boundary fills take
    (`_fill_scan2`): the least j whose reach of 2**j - 1 slots crosses the
    plan's widest window (one slot wider on a phase grid, where a row may
    take the slot before the shared first), and never more than cross the
    row block, ceil(log2(Tq)).  Only samples inside a window count (fewer than
    two mask the cell), so a carry that travels a window's width selects
    what one that travels the row's selects.  `[5m]` over a 10 s grid: 5,
    `[1h]`: 9, a range past 512 slots: 10 at Tq 768.  Every other flavor
    runs no fill and gets 0, so none of them compiles anew."""
    if not (ragged and kind == "rate_family"):
        return 0
    span = plan.span + (1 if phased else 0)
    return min(max(span - 1, 0).bit_length(), _row_steps(plan.Tq))


def _tile_ranges(xp, rows, Tp: int, phased: bool, c0=None):
    """-> [2, Wp / 128] i32: the first and last 128-slot tile of the row
    block (`Tp` columns from column `c0`: the row itself, or a plan's
    `Tq` from its `_col_offset`) that each tile of 128 windows can read,
    from a plan's `rows` (or its `prows` where `phased`): what
    `_gather_cols` visits.  `xp` is numpy on
    the host (build_plan: what a launch will visit, for the counter) and
    jax.numpy inside `_run`'s trace (kernel_operands: the kernel's scalar
    operand, made from the rows an enqueue has uploaded anyway, so it is
    no upload and no compile key).  A tile's windows read from the least
    first slot to the greatest last one of its NON-EMPTY windows (an empty
    or padded window's slot is the 0 sentinel and its cell is masked: it
    reads 0 unvisited); on a phase grid from one slot before the first
    (a row may take the slot before the shared one) over every real
    window (a row's own window may hold a sample where the base row's
    holds none).  A tile of no such window visits nothing: (Tp / 128, -1)."""
    if phased:
        live, lo, hi = rows[_N] >= 2.0, rows[_PI1] - 1.0, rows[_PI2]
    else:
        live, lo, hi = rows[_N1] >= 1.0, rows[_I1], rows[_I2]
    if c0 is not None:
        lo, hi = lo - c0, hi - c0
    lo = xp.where(live, lo, float(Tp)).reshape(-1, _LANE).min(axis=1)
    hi = xp.where(live, hi, -1.0).reshape(-1, _LANE).max(axis=1)
    return xp.stack([xp.floor(xp.clip(lo, 0, Tp) / _LANE),
                     xp.floor(xp.clip(hi, -1, Tp - 1) / _LANE)]
                    ).astype(xp.int32)


def kernel_operands(rows, tsrow, Tp: int, kind: str, phased: bool = False,
                    ragged: bool = False, Tq: Optional[int] = None,
                    tiled: bool = False):
    """The 12 operands `_kernel` reads after (vals, vbase, gids), from a
    plan's uploaded rows; `phased` (rows is the plan's [16, Wp] `prows`):
    13, the last being the rows themselves for the kernel's slacks, with
    the boundary slots and timestamps those of the phased rows and `n` the
    base row's true count for every kind.  `Tq` under Tp (a plan whose
    windows reach fewer columns than the row has, `_col_reach`): the
    kernel computes over the Tq columns from column c0 (`_col_offset`,
    computed here from the rows), so the slots, the tile ranges, the
    bands and `tsrow` are made relative to c0, and one operand more comes
    last, [2] i32, which `_run_set` prefetches into scalar memory: the
    tile the loaded block starts at (for its index map) and how many
    columns below c0 that is (for the kernel's turn of the block).  `Tq`
    None or Tp: the row, and nothing of this.  Traceable: `_run` calls it
    inside its jit (and with it every caller that composes `run_kernel`
    under its own trace, parallel/mesh._device_fused_call), so an enqueue
    ships the [8, Wp] rows and nothing else of the plan.

    The band kinds' selection matrices are built here, on the device:
    o[t, w] = 1{t == idx[w]} and l[t, w] = 1{t <= idx[w]} over the
    non-empty windows (n1 >= 1), 0 elsewhere.  The gather kinds
    (_selects_by_gather) read none of them and get [8, 128] stand-ins,
    which frees their ~1.5 MB of VMEM for larger series blocks; the
    `ragged` rate family alone reads one, the [Tp, Wp] band, first of
    the four.  `n`
    resolves to the true counts for the over_time kinds; `tsrow` None
    (every kind but the ragged rate family leaves it unread) becomes
    zeros."""
    def row(i):
        return rows[i:i + 1]

    trimmed = Tq is not None and Tq != Tp
    if trimmed:
        c0 = _col_offset(jnp, rows, Tp, Tq, phased)
        # (the windows `_col_offset` took c0 from; another's slot is the
        # 0 sentinel and stays 0: its cell is masked)
        live = _live_firsts(rows, phased)[0][None, :]

        def rel(i):
            return jnp.where(live, row(i) - c0.astype(jnp.float32), 0.0)
    else:
        Tq, c0, rel = Tp, None, row

    stand_in = jnp.zeros((8, _LANE), jnp.float32)
    band_only = ragged and kind == "rate_family"
    if _selects_by_gather(kind) and not band_only:
        sel = (stand_in,) * 4
    elif tiled and not band_only:
        valid = row(_N1) >= 1.0
        sel = (jnp.where(valid, rel(_I1), float(Tq)),
               jnp.where(valid, rel(_I2), -1.0)) + (stand_in,) * 2
    else:
        # (in this order: a resident program's text is its parent's)
        t = jax.lax.broadcasted_iota(jnp.int32, (Tq, rows.shape[1]), 0)
        valid = row(_N1) >= 1.0

        def slot(i):
            return jnp.where(valid, rel(i).astype(jnp.int32), -1)

        def mat(i, leq):
            return ((t <= slot(i)) if leq else (t == slot(i))).astype(
                jnp.float32)

        if band_only:
            # a row's valid samples a window are one product with the
            # band the over_time kinds make in the kernel (l2 - l1 + o1):
            # it rides in o1's place
            band = (t >= slot(_I1)) & (t <= slot(_I2))
            sel = (band.astype(jnp.float32),) + (stand_in,) * 3
        else:
            sel = (mat(_I1, False), mat(_I2, False), mat(_I1, True),
                   mat(_I2, True))
    if tsrow is None:
        tsrow = jnp.zeros((1, Tq), jnp.float32)
    elif trimmed:
        tsrow = jax.lax.dynamic_slice_in_dim(tsrow, c0, Tq, axis=1)
    tiles = _tile_ranges(jnp, rows, Tq, phased, c0)
    at = ()
    if trimmed:
        # the loaded block: whole tiles from the tile c0 lies in, held
        # inside the row (then c0 lies a tile further in)
        tile = jnp.minimum(c0 // _LANE, (Tp - _load_cols(Tq, Tp)) // _LANE)
        at = (jnp.stack([tile, c0 - tile * _LANE]),)
    if phased:
        return sel + (row(_PT1), row(_PT2), row(_N1), row(_WS), row(_WE),
                      tsrow, rel(_PI1), rel(_PI2), tiles, rows) + at
    return sel + (row(_T1), row(_T2),
                  row(_N1 if kind in OVER_TIME_FNS else _N),
                  row(_WS), row(_WE), tsrow, rel(_I1), rel(_I2), tiles) + at


def merge_gid_cols(gids, offsets):
    """Traceable: P panels' [Sp, 1] gid columns -> one [Sp, P] matrix over
    DISJOINT group-id ranges (panel p's ids shifted by offsets[p], the
    sum of the earlier panels' group counts; -1 pad rows stay -1).  The
    kernel epilogue turns the columns into one multi-hot matrix, so P
    groupings cost ONE dispatch.  One operand passes through unchanged:
    a single panel's offset is 0."""
    if len(gids) == 1:
        return gids[0]
    return jnp.concatenate(
        [jnp.where(col >= 0, col + offsets[p], -1)
         for p, col in enumerate(gids)], axis=1)


def _committed_device(arr):
    """The single device `arr` is committed to, else None — uncommitted
    arrays follow jax's default placement, no pin needed.  Used to route
    an enqueue's plan rows to the chip that already holds the working set
    (sharded DeviceMirror mode), so the jit call runs there."""
    try:
        if getattr(arr, "committed", False):
            devs = arr.devices()
            if len(devs) == 1:
                return next(iter(devs))
    except Exception:  # noqa: BLE001 — non-jax arrays (numpy fallback)
        pass
    return None


_RESIDENT_LOCK = threading.Lock()    # a plan operand's first put (below)


def gathers(kind: str, ragged: bool, phased: bool) -> int:
    """The `_gather_cols` calls one working set's kernel makes: the rate
    family's two boundaries (values and, on ragged rows, timestamps),
    last_over_time's one (and its validity on ragged rows), and on a phase
    grid the two corrections of every band product."""
    band = 2 if phased else 0           # one `corrected` product
    if kind == "rate_family":
        return 4 + band if ragged else 2
    if kind == "last_over_time":
        return 2 if ragged else 1
    return 2 * band if ragged else band


def enqueue_operands(plan: FusedPlan, device, kind: str, ragged: bool,
                     offsets=None, sets: int = 1,
                     phased: bool = False,
                     cols: Optional[int] = None,
                     band_tiles: int = 0, set_rows=(),
                     whole_rows=None) -> tuple:
    """-> (rows, tsrow, offsets) on `device`: everything one `_run` call
    takes from the host, as device arrays (so the call itself transfers
    nothing).  The plan's own operands are put on a device once and stay
    with the plan (`plan.resident`): the panels of an open and the leaves
    of a request share the plan object, so only the first enqueue of a
    (plan, device, operand) uploads.  Enqueues that miss together wait
    for the one that puts (`_RESIDENT_LOCK`, taken on a miss alone: a
    put is two small arrays a new grid) and take its array.  `offsets` belongs to
    the call and is put every time.  Counted on /metrics: enqueues,
    working sets, and the puts really made (uploads over enqueues is the
    plan's miss share).  `tsrow` rides only where the kernel reads it
    (the ragged rate family), `offsets` only where some set has several
    panels; the others are None.  `sets`: the working sets the call
    carries.  `phased`: the rows are the plan's [16, Wp] `prows`.
    A ragged rate-family call also books its scan_steps on
    `fused_ragged_scan_steps_total` (5 a launch where the windows are
    `[5m]` of a 10 s grid, ceil(log2(Tp)) where they span the row: whether
    a deployment's windows let the fills stop short).  Every call books
    its real window count on `fused_windows_total` (over the enqueues: the
    windows a launch, 61 for an hour at 60 s, 721 for six hours at 30 s)
    and the row tiles its gathers visit on `fused_gather_tile_visits_total`
    (working sets x gathers x the plan's tile ranges: Wp / 128 x Tq / 128 a
    gather when the narrowed gather does not engage), and the columns of a
    row each of its kernel instances loads on `fused_columns_read_total`
    (`_load_cols` of `cols`, the launch's Tq: the plan's unless the flavor
    takes the row; over the enqueues: 640 a launch, of which 512 are
    computed over, for an hour of `[5m]` at 60 s over a 768-slot row; 768
    before a block followed the windows' reach), and the tiles of band its
    program builds (`band_tiles`, from `launch_band_tiles`) on
    `fused_band_tiles_total`: 0 where every set holds its band resident.
    `set_rows` / `whole_rows`: the padded rows of each of the call's sets
    and (`_run`'s `splits`; None: none is split) the rows of each that the
    dense body runs over, two stored ints a set: their sums go on
    `fused_set_rows_total` and `fused_whole_rows_total` (the second over
    the first: the share of a deployment's rows that fill every slot of
    their grid, 0 where no mirror is placed).  A split set's gathers visit
    tiles as its rows do: the dense body's gathers over the whole part's
    share of the rows, the ragged body's over the rest."""
    from filodb_tpu.utils.metrics import registry
    registry.counter("fused_enqueues").increment()
    registry.counter("fused_enqueue_sets").increment(sets)
    registry.counter("fused_windows").increment(plan.W)
    registry.counter("fused_columns_read").increment(
        _load_cols(cols or plan.Tq, plan.Tp))
    whole = sum(whole_rows or ())
    registry.counter("fused_set_rows").increment(sum(set_rows))
    registry.counter("fused_whole_rows").increment(whole)
    own, dense = (gathers(kind, r, phased) for r in (ragged, False))
    visits = sets * own
    if whole:
        visits = sum(own + (dense - own) * w / n
                     for n, w in zip(set_rows, whole_rows))
    visits = round(visits * plan.tile_visits[phased])
    if visits:
        registry.counter("fused_gather_tile_visits").increment(visits)
    steps = scan_steps(plan, kind, ragged, phased)
    if steps:
        registry.counter("fused_ragged_scan_steps").increment(steps)
    if band_tiles:
        registry.counter("fused_band_tiles").increment(band_tiles)
    held, uploads = plan.resident, 0

    def resident(which):
        nonlocal uploads
        arr = held.get((device, which))
        if arr is None:
            # one put an operand: the panels of an open reach a new plan's
            # first enqueue together once nothing makes them wait in turn
            # (0.35 puts a request became 0.51 when the churned cell
            # stopped waiting for the device: PERF.md section 6, PR 50),
            # and the others wait for the first's array and take it
            with _RESIDENT_LOCK:
                arr = held.get((device, which))
                if arr is None:
                    uploads += 1
                    arr = held[(device, which)] = jax.device_put(
                        getattr(plan, which), device)
        return arr

    rows = resident("prows" if phased else "rows")
    tsrow = resident("tsrow") if ragged and kind == "rate_family" else None
    if offsets is not None:
        uploads += 1
        offsets = jax.device_put(np.asarray(offsets, np.int32), device)
    if uploads:
        registry.counter("fused_enqueue_uploads").increment(uploads)
    return rows, tsrow, offsets


def _shift_r(x, k: int, fill):
    return jnp.concatenate([jnp.full_like(x[:, :k], fill), x[:, :-k]],
                           axis=1)


def _shift_l(x, k: int, fill):
    return jnp.concatenate([x[:, k:], jnp.full_like(x[:, :k], fill)],
                           axis=1)


def _fill_scan(x, ok, left: bool):
    """Forward (left=False) / backward (left=True) fill of valid values
    along time in log2(T) shift-and-select steps — the in-kernel form of a
    lax.associative_scan carry, Pallas-friendly (static shapes, no dynamic
    control flow).  Positions with no valid neighbor on the fill side keep
    their input value; callers mask those via window valid-counts.

    Validity travels as f32 0/1, NOT bool: Mosaic cannot shift/concat i1
    vregs on real TPU (`tpu.bitcast_vreg vector<8x128xi1> -> i32` is
    rejected as an invalid vector register cast; interpret mode accepted
    the bool form, which hid this until the first on-chip ragged compile).
    Returns (filled x, f32 validity)."""
    shift = _shift_l if left else _shift_r
    okf = ok.astype(jnp.float32)
    k = 1
    while k < x.shape[1]:
        xs = shift(x, k, 0.0)
        oks = shift(okf, k, 0.0)
        x = jnp.where(okf > 0, x, xs)
        okf = jnp.maximum(okf, oks)
        k *= 2
    return x, okf


def _fill_scan2(x, y, steps: int, left: bool):
    """Forward (left=False) / backward (left=True) fill of two carriers
    over `steps` doubling steps: a slot ends holding the nearest sample
    within 2**steps - 1 slots on the fill side (scan_steps: as far as the
    widest window is wide).  The ragged rate path fills values `x` and
    timestamps `y` together, and validity rides in `y` itself, NaN where a
    slot holds no sample (`x` holds 0 there): no third carrier to shift, so
    a step is two shifts, one compare and two selects, all f32 (Mosaic
    rejects i1 vreg shifts: _fill_scan).  A slot no sample reaches reads 0
    in `x` and NaN in `y`; the caller zeroes the few it gathers."""
    shift = _shift_l if left else _shift_r
    for j in range(steps):
        k = 1 << j
        keep = y == y
        x = jnp.where(keep, x, shift(x, k, 0.0))
        y = jnp.where(keep, y, shift(y, k, jnp.nan))
    return x, y


def _cumsum_lanes(x):
    """Inclusive prefix sum along time (Hillis-Steele doubling shifts)."""
    k = 1
    while k < x.shape[1]:
        x = x + _shift_r(x, k, 0.0)
        k *= 2
    return x


_UNROLLED_VISITS = 8


def gather_loops(Tp: int, Wp: int) -> bool:
    """Whether `_gather_cols` walks each window tile's OWN row tiles in a
    loop (`_tile_ranges`) or visits every (window tile, row tile) pair
    unrolled: the pairs decide.  Up to 8 pairs (an hour of a 10 s scrape
    under one tile of windows: 1 x 6) the unrolled body is short and
    measured faster on the chip, 1.60 against 1.92 ms a launch of four
    sets at 262,144 x 768 (the loop reads a tile at an address only the
    launch knows, and one visit cannot overlap the next); past that the
    loop wins by what it skips, 4.35 against 18.04 ms at 81,920 x 2,304
    under 768 windows, 22 of 108 pairs visited (PERF.md section 6,
    PR 44)."""
    return (Tp // _LANE) * (Wp // _LANE) > _UNROLLED_VISITS


def _gather_cols(src, idx, tiles):
    """out[s, w] = src[s, idx[0, w]], or src[s, idx[s, w]] where `idx` has
    a row a series (the phased variant: a row's slot is the shared one or
    the one before it) — the one-hot selection matmul as pure
    data movement.  Mosaic lowers take_along_axis to tpu.dynamic_gather
    only within one 128-lane vreg (the cross-vreg form fails to compile),
    so the row is gathered per 128-lane tile and
    the right tile selected per window.  Exact: no arithmetic touches
    the values.

    `idx` is the plan's shared row as its REF ([1, Wp] f32: a tile of it
    is loaded at its lane offset, which Mosaic lowers at any Wp; a slice
    of the loaded VALUE at lane offset 128, broadcast down the sublanes,
    it refuses: "Invalid input layout") or a [bs, Wp] i32 value.

    `tiles` None (few pairs, `gather_loops`): `src` is a [bs, Tp] value
    and every row tile is visited for every window tile, unrolled.  Else
    `tiles` is [2, Wp / 128] i32 in scalar memory (`_tile_ranges`): window
    tile j's slots lie in row tiles tiles[0, j] .. tiles[1, j], the loop
    visits those alone (a tile of 128 windows at a 30 s step over a 10 s
    grid reads 388 slots: 4 or 5 of a 2,304-slot row's 18), and `src` is a
    REF, the values block itself or a scratch block a computed array was
    parked in (`_kernel`'s `park`), because a tile of it is read at a lane
    offset that only the launch knows.  There a window outside its tile's
    range (an empty or padded one, whose slot is the 0 sentinel) reads 0,
    not slot 0: its cell is masked either way."""
    bs, Tp = src.shape
    Wp = idx.shape[1]
    chunks = []
    for j, wc in enumerate(range(0, Wp, _LANE)):
        ic = jnp.broadcast_to(idx[:, wc:wc + _LANE].astype(jnp.int32),
                              (bs, _LANE))

        # (both are traced before `ic` moves on to the next window tile)
        def visit(k, tile, acc):
            g = jnp.take_along_axis(tile, jnp.clip(ic - k, 0, _LANE - 1),
                                    axis=1, mode="promise_in_bounds")
            return jnp.where((ic >= k) & (ic < k + _LANE), g, acc)

        def visit_at(kt, acc):
            k = pl.multiple_of(kt * _LANE, _LANE)
            return visit(k, src[:, pl.ds(k, _LANE)], acc)

        acc = jnp.zeros((bs, _LANE), jnp.float32)
        if tiles is None:
            for k in range(0, Tp, _LANE):
                acc = visit(k, src[:, k:k + _LANE], acc)
        else:
            acc = jax.lax.fori_loop(tiles[0, j], tiles[1, j] + 1, visit_at,
                                    acc)
        chunks.append(acc)
    return chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks, axis=1)


_BAND_COLS = 512
"""The columns of a row one product of the tiled band covers: a [512, Wp]
tile of 0/1 is 256 KB at one tile of windows, where the whole band of a
13-hour row is 2.4 MB and the resident form holds five of its size."""


def _band_dot(src, first, last, products):
    """[dot(f(src), band) for (dot, f) in products], band[t, w] =
    1{first[w] <= t <= last[w]}, without the band: `_BAND_COLS` columns
    of the [bs, Tq] REF `src` at a time (each mapped by `f`, None: as they
    are) against the tile of the band that two compares of an iota with
    the [1, Wp] slot rows make, summed in tile order.  The whole tiles in
    a loop (one body, so one set of temporaries: unrolled, Mosaic keeps
    every tile's operands and the kernel's scoped memory grows with the
    row), the columns left over after them once more.  What the resident
    form's one product over [Tq, Wp] selects, in another order of f32
    sums (exact where `dot` counts 0/1)."""
    first, last = first.astype(jnp.int32), last.astype(jnp.int32)
    (bs, Tq), Wp = src.shape, first.shape[1]

    def tile(k, cols, accs):
        t = jax.lax.broadcasted_iota(jnp.int32, (cols, Wp), 0) + k
        band = ((t >= first) & (t <= last)).astype(jnp.float32)
        x = src[:, pl.ds(k, cols)]
        return tuple(acc + dot(x if f is None else f(x), band)
                     for acc, (dot, f) in zip(accs, products))

    accs = (jnp.zeros((bs, Wp), jnp.float32),) * len(products)
    whole = Tq // _BAND_COLS
    if whole:
        accs = jax.lax.fori_loop(
            0, whole, lambda i, accs: tile(
                pl.multiple_of(i * _BAND_COLS, _BAND_COLS), _BAND_COLS,
                accs), accs)
    if Tq % _BAND_COLS:
        accs = tile(whole * _BAND_COLS, Tq % _BAND_COLS, accs)
    return accs


def _gathers_values(kind: str, ragged: bool, phased: bool,
                    with_drops: bool) -> bool:
    """Whether some `_gather_cols` of the flavor reads the values block
    itself and not an array computed from it: dense rows' boundaries
    (unless the kernel corrects resets first), last_over_time's, and a
    phase grid's band corrections."""
    if ragged:
        return False
    if kind == "rate_family":
        return not with_drops
    return kind == "last_over_time" or phased


def parked(kind: str, ragged: bool, phased: bool, with_drops: bool,
           looped: bool = True, turned: bool = False,
           tiled: bool = False) -> int:
    """The [bs, Tp] scratch blocks one grid step of `_kernel` parks
    computed arrays in for a `_gather_cols` that loops (`gather_loops`;
    the values block itself is gathered in place; an unrolled gather reads
    values and parks nothing): the reset-corrected values; on ragged rows
    the filled values and timestamps of either side (and the validity a
    phase grid's count correction reads), last_over_time's zeroed values
    and validity, a phase grid's two band corrections' sources.  `turned`
    (a trimmed plan's block, `_load_cols`): the values a gather reads in
    place are no longer the loaded block but its turn, a computed array
    like the others: one block more wherever they are (`_gathers_values`)."""
    if not looped:
        # (the tiled band reads its columns off a block whatever the
        # gathers do: the turn is parked for it)
        return int(tiled and turned)
    # (... and where the gathers loop, unless one had it parked already)
    turn = int(turned and (tiled or _gathers_values(
        kind, ragged, phased, with_drops)))
    if kind == "rate_family":
        return (4 + phased if ragged else int(with_drops)) + turn
    if kind == "last_over_time" or phased:
        return (2 if ragged else 0) + turn
    return 0


def _kernel(vals_ref, vbase_ref, gids_ref, o1_ref, o2_ref, l1_ref, l2_ref,
            t1_ref, t2_ref, n_ref, ws_ref, we_ref, ts_ref, i1_ref, i2_ref,
            tiles_ref, *out_refs,
            num_groups: int, is_counter: bool, is_rate: bool,
            with_drops: bool, kind: str = "rate_family",
            ragged: bool = False, per_series: bool = False,
            phased: bool = False, steps: int, tiled: bool = False,
            at_ref=None):
    turned = at_ref is not None
    # (under the tiled band the block is read a tile at a time: whole
    # only to be turned, or for a ragged phased row's corrections)
    v = vals_ref[:] if not tiled or turned or (ragged and phased) \
        else None                                     # [BS, Tp]
    if turned:
        # a trimmed plan's block (`_load_cols`): one tile wider than the
        # Tq columns the plan computes over and begun on a tile edge,
        # at_ref[1] columns below the first of them.  One turn of the
        # lanes (the XLU's, by an amount only the launch knows) brings
        # that column to lane 0; the tile that falls off is read by no
        # window.  Pure data movement: the values are the row's
        from jax.experimental.pallas import tpu as pltpu
        load, below = v.shape[1], at_ref[1]
        v = pltpu.roll(v, jnp.where(below > 0, load - below, 0),
                       1)[:, :load - _LANE]
    looped = gather_loops(ts_ref.shape[1], i1_ref.shape[1])
    gather = functools.partial(_gather_cols,
                               tiles=tiles_ref if looped else None)
    # after the outputs come the scratch blocks (`parked` of them): where
    # the gather loops, a computed [BS, Tp] array is stored in one to be
    # gathered from
    free = list(out_refs[len(out_refs) - parked(
        kind, ragged, phased, with_drops, looped, turned, tiled):])
    out_refs = out_refs[:len(out_refs) - len(free)]

    def park(x, always=False):
        if not (looped or always):
            return x
        ref = free.pop()
        ref[...] = x
        return ref
    # what a gather reads the values block as: in place where it loops
    # (the turned block is no operand: it is parked where it is read)
    vsrc = vals_ref if looped and not turned else v
    if turned and _gathers_values(kind, ragged, phased, with_drops):
        vsrc = park(v)
    if phased:
        # rows of a phase grid (FusedPlan.prows): after the 12 operands
        # come the plan's [16, Wp] rows and the working set's [BS, 1] phase
        # column.  Which of the two slots a row takes at either edge is one
        # compare of its phase with the window's slack; its slot count
        # follows, and with it presence, a row a cell (the second output,
        # as on ragged rows)
        pr_ref, ph_ref, *out_refs = out_refs
        ph = ph_ref[:]                                # [BS, 1]
        early1 = ph > pr_ref[_PS1:_PS1 + 1, :]        # [BS, Wp] first - 1
        early2 = ph > pr_ref[_PS2:_PS2 + 1, :]        #          last - 1
        e1 = early1.astype(jnp.float32)
        e2 = early2.astype(jnp.float32)
        idx1 = i1_ref[:].astype(jnp.int32) - early1.astype(jnp.int32)
        idx2 = i2_ref[:].astype(jnp.int32) - early2.astype(jnp.int32)
        slots = n_ref[:] + e1 - e2                    # [BS, Wp] own count
    if phased and (not _selects_by_gather(kind)
                   or (ragged and kind == "rate_family")):
        # a product with the base row's band (the over_time kinds' sums
        # and counts, the ragged rate family's counts) is a row's own by
        # two gathered corrections: a row that starts a slot early adds
        # the sample before the band, which is its own first, one that
        # ends a slot early takes the band's last, the slot after its own,
        # away (an empty band leaves the one sample a row may hold, at
        # last == first - 1, to them alone).  Both slots are gathered by
        # a row's own [BS, Wp] index: Mosaic lowers no shared [1, Wp] one
        # past 128 windows, and folds `idx2 + early2` back into one
        after = jnp.where(early2, idx2 + 1, idx2)

        def corrected(total, src):
            return total \
                + jnp.where(early1, gather(src, idx1), 0.0) \
                - jnp.where(early2, gather(src, after), 0.0)
    if kind == "last_over_time":
        # instant-vector selector (`sum by (x) (metric)` with staleness
        # lookback): the last sample in each window, gathered at last[w];
        # empty windows contribute 0 and are masked by counts.
        # Ragged keeps SLOT semantics deliberately — a NaN in the newest
        # slot is a staleness marker that makes the series absent, not a
        # hole to skip (unlike the rate family's range-vector filtering)
        if not phased:
            idx2, slots = i2_ref, n_ref[:]
        if ragged:
            m = v == v
            sel = gather(park(jnp.where(m, v, 0.0)), idx2)
            # empty windows gather column idx 0 (a plan sentinel): the
            # true-count mask zeroes their presence
            pres = gather(park(m.astype(jnp.float32)), idx2) \
                * jnp.minimum(slots, 1.0)
            out = (sel + vbase_ref[:]) * pres
            _epilogue(gids_ref, out, pres, out_refs, num_groups, per_series)
            return
        sel = gather(vsrc, idx2) * jnp.minimum(slots, 1.0)
        out = sel + vbase_ref[:] * jnp.minimum(slots, 1.0)
        _epilogue(gids_ref, out, jnp.minimum(slots, 1.0) if phased else None,
                  out_refs, num_groups, per_series)
        return
    if kind in ("sum_over_time", "avg_over_time", "count_over_time"):
        # window sums as ONE matmul against the band matrix
        # band[t, w] = 1{first[w] <= t <= last[w]} = l2 - l1 + o1;
        # the ABSOLUTE sum re-adds the per-series base as vb * n.
        # Ragged (NaN-holed) rows: validity-weighted variant — zero the
        # holes, take per-(series, window) counts from a second matmul of
        # the validity mask against the same band (VERDICT r2 item 2).
        def validity(x):
            return (x == x).astype(jnp.float32)       # NaN-aware

        def zeroed(x):
            return jnp.where(x == x, x, 0.0)

        if not tiled:
            band = l2_ref[:] - l1_ref[:] + o1_ref[:]
        if ragged and v is not None:
            validf, vz = validity(v), zeroed(v)
        if tiled:
            # ... the same band, never whole: made a tile of columns at a
            # time from its two slot rows, which ride in o1's and o2's
            # place (kernel_operands), a product a tile (`_band_dot`) of
            # the values where they lie: the block, or its turn, parked
            vref = vals_ref
            if turned:
                vref = vsrc if looped and _gathers_values(
                    kind, ragged, phased, with_drops) else park(v, True)
            prods = _band_dot(
                vref, o1_ref[:], o2_ref[:],
                ((_dot_values_split3, zeroed), (_dot_1p, validity))
                if ragged else ((_dot_values_split3, None),))
        else:
            prods = (_dot_hi(vz, band), _dot_1p(validf, band)) if ragged \
                else (_dot_hi(v, band),)
        s = prods[0]
        if ragged:
            n = prods[1]                               # [BS, Wp] valid counts
            if phased:
                s = corrected(s, park(vz))
                n = corrected(n, park(validf))
            pres = (n > 0).astype(jnp.float32)
        elif phased:
            s = corrected(s, vsrc)
            n = slots
            pres = (n > 0).astype(jnp.float32)
        else:
            n = n_ref[:]                              # [1, Wp] true counts
            pres = None
        if kind == "sum_over_time":
            out = s + vbase_ref[:] * n
        elif kind == "avg_over_time":
            out = s / jnp.maximum(n, 1.0) + vbase_ref[:]
            if pres is not None:
                out = out * pres      # no vbase leak into absent cells
        else:                                         # count_over_time
            out = n * jnp.ones_like(s)
            if ragged:
                # count's presence is SLOT-based: a window whose grid slots
                # exist but hold only NaN emits 0, not absent (ref:
                # AggrOverTimeFunctions.scala:367-382), unlike sum/avg
                pres = ((slots if phased else n_ref[:]) > 0).astype(
                    jnp.float32) * jnp.ones_like(s)
        _epilogue(gids_ref, out, pres, out_refs, num_groups, per_series)
        return
    pres = None
    if ragged:
        # ragged rate family: NaN holes are ABSENT samples (upstream
        # filters staleness markers out of range vectors before the rate
        # math, ref: RateFunctions.scala:140-196 iterates stored samples
        # only) — so the boundaries are each series' first/last VALID
        # sample inside the window.  Forward/backward fill scans make the
        # shared first/last window slots carry those boundary values, so
        # the same gathers as the dense path select them, in one HBM pass.
        m = v == v
        vz = jnp.where(m, v, 0.0)
        if with_drops:
            fv, fok = _fill_scan(vz, m, left=False)
            prev = _shift_r(fv, 1, 0.0)
            pok = _shift_r(fok, 1, 0.0)                # f32 validity
            # reset vs the previous VALID value; correction adds the full
            # previous RAW value (prev + vbase), cumulative across the row
            d = jnp.where(m & (pok > 0) & (vz < prev),
                          prev + vbase_ref[:], 0.0)
            c = vz + jnp.where(m, _cumsum_lanes(d), 0.0)
        else:
            c = vz
        tsb = jnp.where(m, ts_ref[:] + ph if phased
                        else jnp.broadcast_to(ts_ref[:], v.shape), jnp.nan)
        f_c, f_t = _fill_scan2(c, tsb, steps, left=False)
        b_c, b_t = _fill_scan2(c, tsb, steps, left=True)
        # exact selections at the first/last window slots, and the count
        # of valid samples between them as one product with the 0/1 band
        # (kernel_operands puts it in o1's place) on the MXU this branch
        # leaves idle: integers under 2^24 in f32 accumulation, exact.
        # Empty windows gather slot 0 and count 0, so presence masks them.
        if not phased:
            idx1, idx2 = i1_ref, i2_ref
        mf = m.astype(jnp.float32)
        nv = _dot_1p(mf, o1_ref[:])
        if phased:
            nv = corrected(nv, park(mf))
        v1 = gather(park(b_c), idx1)
        v2 = gather(park(f_c), idx2)
        t1 = gather(park(b_t), idx1)
        t2 = gather(park(f_t), idx2)
        # a slot no sample reached reads 0, as its value does: such a cell
        # holds fewer than two samples and is masked, and no NaN goes on
        t1 = jnp.where(t1 == t1, t1, 0.0)
        t2 = jnp.where(t2 == t2, t2, 0.0)
        n = jnp.maximum(nv, 2.0)                      # math-safe; masked
        pres = (nv >= 2.0).astype(jnp.float32)
    else:
        if not phased:
            idx1, idx2 = i1_ref, i2_ref
        if with_drops:
            # the first column has no predecessor.  A reset adds the FULL
            # previous RAW value = prev + vbase (rebased rows; ref:
            # DoubleVector.scala:328); the corrections up to first[w] /
            # last[w] are the row's prefix sum selected there
            prev = jnp.concatenate([v[:, :1], v[:, :-1]], axis=1)
            d = jnp.where(v < prev, prev + vbase_ref[:], 0.0)
            col = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
            d = jnp.where(col == 0, 0.0, d)
            c = park(v + _cumsum_lanes(d))
        else:
            c = vsrc
        v1 = gather(c, idx1)                           # [BS, Wp]
        v2 = gather(c, idx2)
        if phased:
            # a row's boundary timestamps: the base row's at its own slot,
            # plus its phase (exact in f32: offsets stay under 2^24 ms)
            t1 = jnp.where(early1, pr_ref[_PT1M:_PT1M + 1, :], t1_ref[:]) + ph
            t2 = jnp.where(early2, pr_ref[_PT2M:_PT2M + 1, :], t2_ref[:]) + ph
            n = jnp.maximum(slots, 2.0)               # math-safe; masked
            pres = (slots >= 2.0).astype(jnp.float32)
        else:
            t1, t2 = t1_ref[:], t2_ref[:]             # [1, Wp]
            n = n_ref[:]
    ws, we = ws_ref[:], we_ref[:]

    dur_start = (t1 - ws) / 1000.0
    dur_end = (we - t2) / 1000.0
    sampled = jnp.maximum((t2 - t1) / 1000.0, 1e-9)
    avg_between = sampled / (n - 1.0)
    delta = v2 - v1
    if is_counter:
        va = v1 + vbase_ref[:]                        # absolute first value
        dur_zero = sampled * (va / jnp.where(delta == 0.0, jnp.inf, delta))
        take_zero = (delta > 0) & (va >= 0) & (dur_zero < dur_start)
        dur_start = jnp.where(take_zero, dur_zero, dur_start)
    threshold = avg_between * 1.1
    extrap = sampled \
        + jnp.where(dur_start < threshold, dur_start, avg_between / 2) \
        + jnp.where(dur_end < threshold, dur_end, avg_between / 2)
    out = delta * (extrap / sampled)
    if is_rate:
        out = out / jnp.maximum(we - ws, 1.0) * 1000.0
    if pres is not None:
        # no NaN into the MXU (a masked cell divides by the floor of
        # `sampled`: an inf there must not meet the 0)
        out = jnp.where(pres > 0, out, 0.0)

    _epilogue(gids_ref, out, pres, out_refs, num_groups, per_series)


def _epilogue(gids_ref, out, pres, out_refs, num_groups: int,
              per_series: bool):
    """Shared epilogue.  Group mode: one-hot segment-sum on the MXU,
    accumulated across sequential grid steps (pad rows carry gid -1: no
    match); `pres` (ragged presence [BS, Wp]) feeds a second accumulated
    output so present-counts ride the same kernel.  Per-series mode
    (agg min/max: sum is the MXU's semiring, min is not): write the raw
    [BS, Wp] block and let an XLA segment reduction finish on the
    T/W-times-smaller output."""
    if per_series:
        out_refs[0][:] = out
        if pres is not None:
            out_refs[1][:] = pres
        return
    gids = gids_ref[:]                                # [BS, P] int32
    groups = jax.lax.broadcasted_iota(jnp.int32, (num_groups, out.shape[0]),
                                      0)
    onehot = (groups == gids[:, 0][None, :]).astype(jnp.float32)
    # multi-grouping batch (merge_gid_cols): each extra column is another
    # panel's grouping over DISJOINT group-id ranges, so the sum stays a
    # 0/1 matrix and P dashboard panels ride ONE kernel dispatch
    for p in range(1, gids.shape[1]):
        onehot = onehot + (groups == gids[:, p][None, :]).astype(jnp.float32)
    part = _dot_split3(onehot, out)                   # [Gp, Wp]

    @pl.when(pl.program_id(0) == 0)
    def _():
        for r in out_refs:
            r[:] = jnp.zeros_like(r)
    out_refs[0][:] += part
    if pres is not None:
        out_refs[1][:] += _dot_1p(onehot, pres)


def _takes_row(kind: str, with_drops: bool, ragged: bool,
               phased: bool) -> bool:
    """Whether a flavor computes over the row's Tp whatever the plan's
    windows reach (`_flavor` says why): a kernel that corrects resets
    itself, and the dense gather kinds on one shared row."""
    return with_drops or (_selects_by_gather(kind) and not ragged
                          and not phased)


def _set_parts(sets, splits, ragged: bool) -> list:
    """The Pallas calls one `_run` makes: (set index, first row, rows,
    ragged) each.  A set is one call over its rows, of the launch's
    flavor; a set stored whole rows first (`PaddedValues.split`: the row
    its holed part starts at, a static int of the launch) is two, the
    dense body over [0, split) and the ragged one over the rest."""
    parts = []
    for k, st in enumerate(sets):
        Sp, Sw = st[0].shape[0], splits[k] if splits else 0
        if Sw:
            parts += [(k, 0, Sw, False), (k, Sw, Sp - Sw, True)]
        else:
            parts.append((k, 0, Sp, ragged))
    return parts


def _part_forms(sets, num_groups, splits, Tq: Optional[int], Tp: int,
                Wp: int, kind: str, ragged: bool, phased: bool,
                with_drops: bool) -> list:
    """(part, the columns it computes over, whether it takes the tiled
    band) for every part of a `_run` call (`_set_parts`; `band_form`, by
    the columns loaded): what `_run` traces by and an enqueue books
    (`launch_band_tiles`).  The columns are the launch's `Tq`, but for
    the dense part of a split set whose dense flavor keeps the row
    (`_takes_row`)."""
    out = []
    for part in _set_parts(sets, splits, ragged):
        k, _, _, rag = part
        cols = Tp if rag != ragged and _takes_row(
            kind, with_drops, rag, phased) else (Tq or Tp)
        out.append((part, cols, band_form(
            _load_cols(cols, Tp), Wp, num_groups[k], kind, rag,
            len(sets[k][2]), phased, with_drops)[1]))
    return out


def _run_shape_sig(sets, plan, num_groups, kind: str, ragged: bool,
                   phased: bool = False, steps: int = 0,
                   Tq: Optional[int] = None, splits=None) -> str:
    """The compile-cache shape signature recorded with jit compile
    events (utils/devicetelem): the padded dims + static flags that key
    the trace cache, so a recompile storm names the shape that drove it.
    A call of several sets names their summed rows and groups and how
    many they were.  `T` is the row block's width, the launch's `Tq`
    (the row's Tp where it is left out); `whole`, the rows that sets
    stored whole rows first run the dense body over."""
    Sp = sum(st[0].shape[0] for st in sets)
    return (f"S{Sp}xT{Tq or plan.Tp}xW{plan.t1.shape[1]}"
            f"xG{sum(num_groups)}:{kind}"
            + (":ragged" if ragged else "") + (":phased" if phased else "")
            + (f":{steps}steps" if steps else "")
            + (f":{len(sets)}sets" if len(sets) > 1 else "")
            + (f":{sum(splits)}whole" if splits and any(splits) else ""))


@functools.partial(jax.jit, static_argnames=(
    "num_groups", "is_counter", "is_rate", "with_drops", "interpret",
    "kind", "ragged", "per_series", "phased", "steps", "Tq", "splits"))
def _run(sets, offsets, rows, tsrow, *,
         num_groups: Tuple[int, ...], is_counter: bool, is_rate: bool,
         with_drops: bool, interpret: bool, kind: str = "rate_family",
         ragged: bool = False, per_series: bool = False,
         phased: bool = False, steps: int, Tq: Optional[int] = None,
         splits: Optional[Tuple[int, ...]] = None):
    """One fused dispatch, whole: the plan's kernel operands
    (kernel_operands), built once, then for every working set of `sets`
    its group merge (merge_gid_cols) and its own Pallas call, in one
    trace.  A set is `(vals_p, vbase_p, gid columns)` with its padded
    group count in `num_groups`; `offsets` holds the traced int32
    group-id shifts of every set's columns one after the other (None
    when no set has more than one), so a new group count compiles
    nothing; `rows` / `tsrow` are the plan's uploaded rows, shared by all
    sets.  `phased`: every set is a working set on a phase grid and
    carries its [Sp, 1] phase column as a fourth member, and `rows` is
    the plan's [16, Wp] `prows`; unphased sets, operands and program are
    what they were before the variant existed.  Returns the sets' outputs
    concatenated on the group axis (a pair of them, sums and present
    counts, when `ragged` or `phased`): set i's rows start at
    sum(num_groups[:i]).  A set's block is what a call of that set alone
    returns, bit for bit: the sets meet only in the concatenation.
    `Tq` (the flavor's, `_flavor`; None: the row's Tp): the columns of a
    row each kernel instance computes over, from the column the rows say
    (`_col_offset`: data, so a dashboard that moves along the row with
    the newest sample runs one program).
    `splits` (a ragged launch's; None: no set is split): for every set
    the row its holed part starts at, 0 for a set that has none
    (`PaddedValues.split`).  A split set is TWO Pallas calls over the one
    array (`_set_parts`: no row is copied, a part is a range of blocks),
    the dense body of the launch's kind and phase over the rows that fill
    every slot and the ragged one over the rest, their sums and present
    counts added: a row's cells are what the ragged body gives it, bit
    for bit (a row without a hole fills nothing and counts every slot),
    and only the order in which rows enter a group's f32 sum differs.
    Static, from the set's stored shape: a launch classifies nothing."""
    Tp = sets[0][0].shape[1]
    Wp = rows.shape[1]
    if per_series and splits and any(splits):
        raise ValueError("a per-series run takes a set's rows in the "
                         "set's order: not one stored whole rows first")
    # the band's form is a part's own (`band_form`: its group count has a
    # say), the operands are a form's: built once for each (body, columns,
    # form) some part takes, and nearly always that is one
    forms = _part_forms(sets, num_groups, splits, Tq, Tp, Wp, kind, ragged,
                        phased, with_drops)
    operands = {
        (rag, cols, tiled): kernel_operands(
            rows, tsrow if rag == ragged else None, Tp, kind, phased, rag,
            cols, tiled)
        for rag, cols, tiled in {(part[3], cols, tiled)
                                 for part, cols, tiled in forms}}
    paired = ragged or phased
    outs, p0 = [], 0
    for (k, row0, nrows, rag), cols, tiled in forms:
        vals_p, vbase_p, gids = sets[k][:3]
        Gp = num_groups[k]
        if row0 == 0:
            offs = None
            if offsets is not None and len(gids) > 1:
                offs = offsets[p0:p0 + len(gids)]
            p0 += len(gids)
            merged = merge_gid_cols(gids, offs)
        out = _run_set(
            vals_p, vbase_p, merged, operands[(rag, cols, tiled)], Wp, Gp,
            sets[k][3] if phased else None,
            is_counter=is_counter, is_rate=is_rate,
            with_drops=with_drops, interpret=interpret, kind=kind,
            ragged=rag, per_series=per_series, phased=phased,
            steps=steps if rag else 0, row0=row0, nrows=nrows)
        if paired and not (rag or phased):
            # the dense body on one shared row counts nothing: a group's
            # present rows are its rows of this part, a window at a time
            out = (out, _dense_counts(merged[:nrows], rows, Gp, kind))
        if row0 == 0:
            outs.append(out)
        else:
            outs[-1] = tuple(a + b for a, b in zip(outs[-1], out))
    if len(outs) == 1:
        return tuple(outs[0]) if paired else outs[0]
    if paired:
        return tuple(jnp.concatenate([o[k] for o in outs], axis=0)
                     for k in (0, 1))
    return jnp.concatenate(outs, axis=0)


def _dense_counts(gids, rows, Gp: int, kind: str):
    """[Gp, Wp] present counts of dense rows on one shared row, inside
    `_run`'s trace: the rows of each group (every panel's column; a pad
    row's -1 counts nowhere) times the shared window validity, `n1 >= 1`
    for the over_time kinds and `n >= 2` for the rate family, as the host
    makes them for a dense launch (`FusedDispatch.enqueue`)."""
    size = jnp.zeros((Gp,), jnp.float32).at[
        jnp.where(gids >= 0, gids, Gp).reshape(-1)].add(1.0, mode="drop")
    valid = rows[_N1:_N1 + 1] >= (1.0 if kind in OVER_TIME_FNS else 2.0)
    return size[:, None] * valid.astype(jnp.float32)


def _merge_hist_sets(res, rows, inv, perm, num_groups: Tuple[int, ...],
                     B: int, kind: str, paired: bool):
    """The cross-shard `hist_sum` merge inside a trace: `_run`'s output
    over histogram working sets (set p's [Gp_p, Wp] f32 sums from row
    sum(num_groups[:p]), slot = group x B + bucket, pad slots 0; beside
    them the kernel's present counts when `paired`) -> (merged bucket sums
    [Gm_p, B, Wp], present [Gm_p or 1, Wp] bool).

    `inv` int32 [sets, Gm_p] and `perm` int32 [sets] are made on the host
    from execbase._merge_layout's index (FusedDispatch.hist_layout):
    inv[p, m] is the row of set p's group that is merged group m, or
    `_ABSENT`, a row past any set's groups (taken as zeros: a group a
    shard lacks adds zero); perm[j] is the set that is the reduce's child
    j (the sets of a call are sorted by shape).  A set's sums are masked to its present
    cells as the host presenter masks them (a dense set's by the shared
    window validity, a ragged set's by the kernel's count of its bucket-0
    slot: a series' buckets share one validity), taken into the merged
    groups' order, and added set by set in CHILD order, f32.  `present`:
    some series of the group in the window (the merged count the host path
    NaNs by; on dense rows every group holds a series, so it is the
    windows' validity, and a pad group's total of 0 is NaN by the
    quantile's own rule)."""
    Wp = rows.shape[1]
    sums, counts = res if paired else (res, None)
    valid = rows[_N1:_N1 + 1] >= (1.0 if kind in OVER_TIME_FNS else 2.0)
    parts, cnts, lo = [], [], 0
    for p, Gp in enumerate(num_groups):
        Gq = -(-Gp // B)              # whole groups of B slots, pad rows 0

        def by_group(x, lo=lo, Gp=Gp, Gq=Gq):
            x = x[lo:lo + Gp]
            if Gq * B != Gp:
                x = jnp.pad(x, ((0, Gq * B - Gp), (0, 0)))
            return x.reshape(Gq, B, Wp)

        part = by_group(sums)
        if paired:
            cnt = by_group(counts)[:, 0, :]
            part = jnp.where(cnt[:, None, :] > 0, part, 0.0)
            cnts.append(jnp.take(cnt, inv[p], axis=0, mode="fill",
                                 fill_value=0.0))
        else:
            part = jnp.where(valid[None], part, 0.0)
        parts.append(jnp.take(part, inv[p], axis=0, mode="fill",
                              fill_value=0.0))
        lo += Gp
    ordered = jnp.take(jnp.stack(parts), perm, axis=0)
    merged = ordered[0]
    for j in range(1, len(parts)):
        merged = merged + ordered[j]
    return merged, sum(cnts) > 0 if paired else valid


@functools.partial(jax.jit, static_argnames=(
    "num_groups", "is_counter", "is_rate", "with_drops", "interpret",
    "kind", "ragged", "per_series", "phased", "steps", "Tq", "splits"))
def _run_hist_quantile(sets, offsets, rows, tsrow, inv, perm, q, les,
                       **flags):
    """`histogram_quantile(q, sum by (..)(rate(h[..])))` whole, in ONE
    device program: `_run` over the request's histogram working sets (its
    arguments and its body, untouched), then on its outputs, which never
    leave the device, the cross-shard bucket merge (`_merge_hist_sets`)
    and the quantile (ops/hist._histogram_quantile_jax, the traceable twin
    of the host's NumPy one, the buckets moved last for it) -> [Gm_p, Wp]
    f32, NaN where no series of the group was present in the window.  The
    host reads that block and none of the sets'.  `flags`: `_run`'s
    keyword arguments, every one static, handed on as they came.

    `q` is a traced f32 scalar in [0, 1] and `les` [B] f32 an operand, so
    the p50 / p90 / p99 of one grouping are one program; the merged group
    count Gm_p (`pad_group_count` of the merged groups) is the one static
    dimension beside `_run`'s, carried by `inv`'s shape.  Named so that
    the device program is `jit__run_hist_quantile`: a trace's `^jit__run`
    events are the fused launches, this one among them."""
    from filodb_tpu.ops.hist import _histogram_quantile_jax
    if flags.get("per_series"):
        raise ValueError("the histogram epilogue merges group-mode sums")
    res = _run(sets, offsets, rows, tsrow, **flags)
    merged, present = _merge_hist_sets(
        res, rows, inv, perm, flags["num_groups"], les.shape[0],
        flags["kind"], flags["ragged"] or flags["phased"])
    return jnp.where(
        present, _histogram_quantile_jax(q, jnp.moveaxis(merged, 1, -1), les),
        jnp.nan)


@functools.partial(jax.jit, static_argnames=(
    "Wp", "Gp", "is_counter", "is_rate", "with_drops", "interpret", "kind",
    "ragged", "per_series", "phased", "steps", "row0", "nrows"))
def _run_set(vals_p, vbase_p, gids_p, operands, Wp: int, Gp: int,
             phase_p=None, *,
             is_counter: bool, is_rate: bool, with_drops: bool,
             interpret: bool, kind: str, ragged: bool, per_series: bool,
             phased: bool = False, steps: int, row0: int, nrows: int):
    """One working set's Pallas call inside `_run`'s trace: its own
    series block, grid and group count over the shared plan operands.
    Called from `_run` and nowhere else.  It is a jit only so that sets
    of one shape are traced ONCE inside that trace: a request's 30 sets
    sit on five rungs of the row ladder, and tracing the kernel body 30
    times is 1.5 s under the interpreter lock at every process start
    (0.2 s so; no persistent cache keeps a trace).  The device program
    is still `_run`'s alone.  `row0` / `nrows`: the range of the set's
    rows the call covers (`_set_parts`: the set, or a part of one stored
    whole rows first): the grid's blocks start `row0` rows in, which
    every block size divides (a part begins on a rung of the row ladder),
    and the arrays are the set's own, uncopied."""
    from jax.experimental.pallas import tpu as pltpu

    Sp = nrows
    o1, o2, l1, l2, t1, t2, n, ws, we, ts, idx1, idx2, tiles = operands[:13]
    # the columns computed over: the plan's Tq (kernel_operands made `ts`
    # that wide), the row's own Tp where the windows reach the row; the
    # columns loaded: a tile more, or the row
    Tq = ts.shape[1]
    trimmed = Tq != vals_p.shape[1]
    load = _load_cols(Tq, vals_p.shape[1])
    # adaptive series block: the ragged rate family's scan temporaries
    # scale with bs*Tp, so long rows shrink the block instead of OOMing
    # scoped vmem (or being rejected by the eligibility gate).  All
    # shapes here are static at trace time; Sp is padded to _BS, which
    # every smaller power-of-two block divides.
    # (sized by the columns LOADED: the block's two buffers are that wide,
    # and a bound on them bounds the narrower arrays computed from them)
    bs, tiled = band_form(load, Wp, Gp, kind, ragged,
                          panels=gids_p.shape[1], phased=phased,
                          with_drops=with_drops)
    if bs is None:
        if interpret:
            bs = _MIN_BS            # no scoped-vmem limit off-chip
        else:
            # fail loudly here rather than with an opaque Mosaic
            # scoped-vmem OOM at lowering: the gated caller (leafexec)
            # never reaches this, but direct fused_rate_groupsum users can
            raise ValueError(
                f"fused kernel shape exceeds VMEM budget at every block "
                f"size (Tp={load}, Wp={Wp}, Gp={Gp}, kind={kind}, "
                f"ragged={ragged}); use the general path")
    grid = Sp // bs
    space = {} if interpret else {"memory_space": pltpu.VMEM}
    # (an index map also gets the prefetched scalar where there is one)
    blk0 = row0 // bs
    at_block = (lambda i, *_: (i + blk0, 0)) if blk0 \
        else (lambda i, *_: (i, 0))
    if trimmed:
        # `load` columns of the row from a tile only the launch knows: the
        # last operand (kernel_operands: that tile, and c0's place past
        # it) is prefetched into scalar memory and the block is placed by
        # ELEMENT offsets (a blocked index counts in blocks, and the tile
        # is no multiple of the block), a multiple of 128 lanes by
        # construction
        row_spec = pl.BlockSpec(
            (pl.Element(bs), pl.Element(load)),
            # (a product with the block's rows: Mosaic must see that the
            # sublane tiling divides the row offset)
            (lambda i, at: ((i + blk0) * bs, at[0] * _LANE)) if blk0
            else (lambda i, at: (i * bs, at[0] * _LANE)), **space)
    else:
        row_spec = pl.BlockSpec((bs, load), at_block, **space)
    col_spec = pl.BlockSpec((bs, 1), at_block, **space)
    # gids may carry P grouping columns (multi-panel batch)
    gid_spec = pl.BlockSpec((bs, gids_p.shape[1]), at_block, **space)
    fix = lambda shape: pl.BlockSpec(shape, lambda i, *_: (0, 0), **space)  # noqa: E731
    # the gathers' tile ranges are scalars the kernel branches on
    tile_spec = fix(tiles.shape) if interpret else pl.BlockSpec(
        memory_space=pltpu.SMEM)
    kern = functools.partial(_kernel, num_groups=Gp, is_counter=is_counter,
                             is_rate=is_rate, with_drops=with_drops,
                             kind=kind, ragged=ragged, per_series=per_series,
                             phased=phased, steps=steps, tiled=tiled)
    with_counts = ragged or phased       # presence rides a second output
    if per_series:
        out_spec = pl.BlockSpec((bs, Wp), lambda i, *_: (i, 0), **space)
        out_shape = jax.ShapeDtypeStruct((Sp, Wp), jnp.float32)
    else:
        out_spec = fix((Gp, Wp))
        out_shape = jax.ShapeDtypeStruct((Gp, Wp), jnp.float32)
    out_specs = [out_spec, out_spec] if with_counts else out_spec
    out_shapes = [out_shape, out_shape] if with_counts else out_shape
    # selection-matrix specs follow the operands' actual shapes: the
    # gather kinds get tiny stand-ins for the unread o1/o2/l1/l2
    call = dict(
        grid=(grid,),
        in_specs=[row_spec, col_spec, gid_spec,
                  fix(o1.shape), fix(o2.shape), fix(l1.shape),
                  fix(l2.shape),
                  fix((1, Wp)), fix((1, Wp)), fix((1, Wp)), fix((1, Wp)),
                  fix((1, Wp)), fix((1, Tq)), fix((1, Wp)), fix((1, Wp)),
                  tile_spec]
        # the phased variant's two: the plan's rows, the set's phases
        + ([fix(operands[13].shape), col_spec] if phased else []),
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((bs, Tq), jnp.float32)] * parked(
            kind, ragged, phased, with_drops, gather_loops(Tq, Wp),
            trimmed, tiled))
    args = (vals_p, vbase_p, gids_p, o1, o2, l1, l2, t1, t2, n, ws, we, ts,
            idx1, idx2, tiles, *((operands[13], phase_p) if phased else ()))
    if trimmed:
        # the kernel body is the row's own but for the turn of the block
        return pl.pallas_call(
            lambda at_ref, *refs: kern(*refs, at_ref=at_ref),
            grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1,
                                                   **call),
            out_shape=out_shapes, interpret=interpret)(operands[-1], *args)
    return pl.pallas_call(kern, out_shape=out_shapes, interpret=interpret,
                          **call)(*args)


VMEM_BUDGET = 12 << 20          # per-core VMEM is ~16MB; leave headroom


def vmem_estimate(Tp: int, Wp: int, Gp: int, kind: str = "rate_family",
                  ragged: bool = False, bs: int = _BS,
                  panels: int = 1, phased: bool = False,
                  with_drops: bool = False, tiled: bool = False) -> int:
    """Rough resident-bytes model for one grid step: the band kinds' 4
    selection matrices and band temporary (the gather kinds ship 4 KB
    stand-ins; the ragged rate family reads ONE [Tp, Wp] band, held in
    the pipeline's two buffers: 8 * Tp * Wp bytes, 12 MiB at 1536 x 1024,
    so a long range of many windows diverts here and does not fail at
    lowering), the double-buffered values block, the group one-hot +
    accumulator, and [bs, Wp] f32 temporaries.  The ragged rate family's
    19 [bs, Tp] temporaries are an upper bound kept on purpose.  Mosaic's
    scoped allocation for the kernel's temporaries at Tp=768, Wp=128 on a
    phase grid (the compiler's own report for a described v5e, re-read by
    tests/test_chip_compile.py) was 14.56 MiB at bs=256 and 6.80 at 128
    with three fill carriers over ten steps, which is the 19; it is 10.55
    and 5.16 MiB (8.03 and 3.91 on one shared row) since the fills carry
    two over the steps a window needs, under 14 of them.  So 256 rows
    would fit.  They are not taken: on the chip the larger block
    measured 14.28 against 14.52 ms a launch of
    promchurn-counters-262k.open's four sets (PERF.md section 6, PR 43),
    and a block of other rows regroups the epilogue's f32 sums, so the
    answers would stop being the smaller block's bit for bit.
    Fitted at Tp=768, Wp=128 and, since PR 44, held against the same
    report at a Grafana dashboard's shape, Tp=2304 x Wp=768 (six hours of
    a 10 s scrape under 721 windows): the dense rate family takes 3.09 MiB
    of temporaries beside 2.25 of block buffers at 128 rows (256 rows are
    over the budget), the dense phased one 5.31 + 2.25, against estimates
    of 6.9 and 9.3 MiB.  The band kinds' resident form holds five [Tp, Wp]
    matrices, 35 MB there and 12.1 MB at a 13-hour row under one tile of
    windows (Tp=4736, Wp=128); `tiled` estimates the form that holds none
    (`_band_dot`: one [512, Wp] tile of band at a time and what a tile's
    product reads), and `band_form` takes whichever fits the larger block.
    Mosaic's own need for the tiled program at Tp=4736, Wp=128, Gp=1024,
    128 rows is between 5.7 and 6.0 MiB (the block's two buffers 4.6 of
    it) against 8.62 estimated; tests/test_chip_compile.py compiles it,
    and the shapes beside it, under a scoped limit SET AT the estimate.
    The ragged rate family on one shared row still diverts past Wp=256 at
    Tp=2304 (its band twice: 14 MB).
    A trimmed plan's kernel (`_col_reach`) is estimated at the columns it
    LOADS (`_load_cols`: `_run_set` picks its block by them), a tile more
    than it computes over: at 640 of 768 Mosaic takes 0.62 MiB of buffers
    and 3.40 of temporaries on a phase grid, the turned block among them.
    Callers divert to the general XLA path when this exceeds VMEM_BUDGET
    instead of failing at kernel lowering; _run shrinks its series block
    (pick_block) before giving up, so the gate must test the SMALLEST
    block, not _BS."""
    sel = 4 * 8 * _LANE * 4 if _selects_by_gather(kind) else \
        5 * Tp * Wp * 4
    vals = 2 * bs * Tp * 4
    if tiled and not _selects_by_gather(kind):
        # the tiled band (`_band_dot`): of the band one tile of columns,
        # as an iota, two compares and the 0/1 they make; of the values,
        # beside the block's two buffers, what a tile's product reads
        # (the arrays a ragged or phased row's band is taken over are
        # whole: the holes zeroed, the validity, the turned block)
        sel = 4 * min(_BAND_COLS, Tp) * Wp * 4
        if ragged:
            vals += 3 * bs * Tp * 4
        elif phased:
            vals += bs * Tp * 4
        else:
            vals += 3 * bs * min(_BAND_COLS, Tp) * 4
    if ragged and kind == "rate_family":
        sel += 2 * Tp * Wp * 4
        vals += 19 * bs * Tp * 4
    else:
        # the scratch blocks the gathers read computed arrays from (the
        # ragged rate family's five lie inside its 19; a dense rate kernel
        # parks one only where it corrects resets itself, `with_drops`)
        vals += parked(kind, ragged, phased, with_drops,
                       gather_loops(Tp, Wp)) * bs * Tp * 4
    # multi-panel epilogue (merge_gid_cols): each extra grouping column
    # builds another [Gp, bs] one-hot compare temporary feeding the
    # accumulated multi-hot — a large merged batch that fit the P=1
    # model could still exceed scoped VMEM at Mosaic lowering on-chip
    group = Gp * (Wp * 8 + bs * 4 * max(panels, 1))
    # the phased variant keeps a row's own slots, counts and presence
    # beside them: eight more [bs, Wp] temporaries, and the second output
    inter = (20 if phased else 12) * bs * Wp * 4
    if _selects_by_gather(kind) and not (ragged and kind == "rate_family"):
        # the gather kinds but for the ragged rate family, as Mosaic
        # reports them at Tp 768 and 2304 x Wp 256 and 768
        # (tests/test_chip_compile.py): beside the block's two buffers two
        # [bs, Tp] temporaries more, and under 3 [bs, Wp] ones (under 8.5
        # on a phase grid), counted 6 (12)
        vals += 2 * bs * Tp * 4
        inter = (12 if phased else 6) * bs * Wp * 4
    return sel + vals + group + inter + (Gp * Wp * 8 if phased else 0)


@functools.lru_cache(maxsize=1024)
def band_form(Tp: int, Wp: int, Gp: int, kind: str = "rate_family",
              ragged: bool = False, panels: int = 1, phased: bool = False,
              with_drops: bool = False) -> Tuple[Optional[int], bool]:
    """-> (bs, tiled): the largest series block whose vmem_estimate fits
    VMEM_BUDGET (None when even _MIN_BS does not: the caller must divert
    to the general path), and the form of the band it was sized by.  The
    over_time kinds ("band kinds") hold the [Tp, Wp] band whole, as four
    matrices and their difference (the resident form), or never (the
    tiled form, `_band_dot`).  From the shape alone: the form that fits
    the larger block, and the resident one where both fit the same, so a
    plan the resident form took whole blocks of keeps its program and its
    answers bit for bit (an hour of a 10 s scrape under one tile of
    windows, Tp 768 x Wp 128: 256 rows either way).  The five matrices
    are 12.1 MB at a 13-hour row (Tp 4,736 x Wp 128) and 35 MB at a
    Grafana dashboard's six hours (2,304 x 768): the tiled form alone fits
    there.  A smaller row or group count never gets a smaller block (both
    estimates grow with either), so a gate passed at the row's Tp holds
    at the columns a trimmed plan loads.  The other kinds have no band:
    (their block, False)."""
    def fit(tiled):
        bs = _BS
        while bs >= _MIN_BS:
            if vmem_estimate(Tp, Wp, Gp, kind, ragged, bs=bs, panels=panels,
                             phased=phased, with_drops=with_drops,
                             tiled=tiled) <= VMEM_BUDGET:
                return bs
            bs //= 2
        return None

    whole = fit(False)
    if _selects_by_gather(kind) or whole == _BS:
        return whole, False
    tiles = fit(True)
    if (tiles or 0) > (whole or 0):
        return tiles, True
    return whole, False


def pick_block(Tp: int, Wp: int, Gp: int, kind: str = "rate_family",
               ragged: bool = False, panels: int = 1,
               phased: bool = False,
               with_drops: bool = False) -> Optional[int]:
    """Largest series-block size whose vmem_estimate fits VMEM_BUDGET
    under the band form that fits the larger one (`band_form`; None when
    even _MIN_BS doesn't — the caller must divert to the general path).
    The ragged rate family's scan temporaries scale with bs*Tp, so long
    rows fuse fine at a smaller block: at Tp=768 the dense kernel keeps
    bs=256 while ragged rate drops to 128 instead of falling off the
    fused path entirely."""
    return band_form(Tp, Wp, Gp, kind, ragged, panels, phased,
                     with_drops)[0]


def window_counts(ts_row: np.ndarray, wends: np.ndarray,
                  range_ms: int) -> np.ndarray:
    """Per-window sample counts over one shared grid — the single source
    of the window-inclusion convention ((wend-range, wend], matching
    build_plan and ops/timewindow.window_bounds)."""
    ts_row = np.asarray(ts_row, dtype=np.int64)
    wend = np.asarray(wends, dtype=np.int64)
    first = np.searchsorted(ts_row, wend - int(range_ms) + 1, side="left")
    last = np.searchsorted(ts_row, wend, side="right") - 1
    return np.maximum(last - first + 1, 0)


FUSABLE_FNS = ("rate", "increase", "delta", "sum_over_time",
               "avg_over_time", "last_over_time", "count_over_time",
               "min_over_time", "max_over_time")
OVER_TIME_FNS = ("sum_over_time", "avg_over_time", "last_over_time",
                 "count_over_time")
# kinds whose validity-weighted variant handles NaN-holed (ragged) rows
RAGGED_FNS = ("sum_over_time", "avg_over_time", "count_over_time")
# kinds served by the XLA reduce_window path (min-plus is not the MXU's
# semiring; reduce_window is the TPU-native windowed order-statistic)
MINMAX_FNS = ("min_over_time", "max_over_time")
FUSABLE_AGGS = ("sum", "avg", "count", "min", "max")


def can_fuse(fn_name: str, agg_op: str, shared_grid: bool,
             dense: bool) -> bool:
    """Leaf fused-path eligibility (VERDICT r2 item 2 broadened set).

    dense=False means a shared scrape grid whose VALUES have NaN holes.
    Every fusable kind now takes ragged rows (VERDICT r3 item 2): the
    over_time family is validity-weighted, min/max ride reduce_window,
    the rate family finds per-series valid boundaries with in-kernel fill
    scans, and last_over_time keeps slot/staleness semantics via a
    validity one-hot.  `dense` no longer gates anything but stays in the
    signature: callers still route on it (kernel variant selection) and
    the parameter documents the eligibility contract they must compute."""
    del dense
    return (shared_grid and agg_op in FUSABLE_AGGS
            and fn_name in FUSABLE_FNS)


# traceable entry for callers composing the kernel under a trace of their
# own (parallel/mesh._device_fused_call); the jit wrapper inlines there.
# One working set; gids_p is one [Sp, P] matrix, merged by the caller.
# `steps` is the plan's scan_steps; a caller without the plan at hand
# leaves it out and the ragged rate family's fills cross the whole row,
# which selects the same samples (never 0 there: a fill of no steps
# reads a hole at a window's edge as a sample).
def run_kernel(vals_p, vbase_p, gids_p, rows, tsrow=None, *,
               num_groups: int, kind: str = "rate_family",
               ragged: bool = False, steps: Optional[int] = None, **kw):
    if steps is None:
        steps = _row_steps(vals_p.shape[1]) \
            if ragged and kind == "rate_family" else 0
    return _run(((vals_p, vbase_p, (gids_p,)),), None, rows, tsrow,
                num_groups=(num_groups,), kind=kind, ragged=ragged,
                steps=steps, **kw)


class PreparedInputs(NamedTuple):
    """Padded device-resident query inputs — build once per working set
    (the pad is a full [S, T] device copy; never pay it per query)."""
    vals_p: jax.Array    # [Sp, Tp] f32
    vbase_p: jax.Array   # [Sp, 1] f32
    gids_p: jax.Array    # [Sp, 1] int32 (-1 pad rows)
    gsize: np.ndarray    # [num_groups] series per group
    phase_p: Optional[jax.Array] = None   # as PaddedValues.phase_p


class PaddedValues(NamedTuple):
    """The grouping-independent (and byte-dominant) half of PreparedInputs
    — cacheable once per (working set, column) across grouping variants.
    `phase_p` says which kernel variant the set runs: None for rows on
    one shared timestamp row (every phase zero), else the rows' phases on
    the plan's base row and the phased variant."""
    vals_p: jax.Array    # [Sp, Tp] f32
    vbase_p: jax.Array   # [Sp, 1] f32
    phase_p: Optional[jax.Array] = None   # [Sp, 1] f32 ms (0 pad rows)
    # a ragged set stored WHOLE ROWS FIRST (`whole_first`): the rows that
    # fill every slot of their grid stand in [0, split), padded to a rung
    # of the row ladder of their own, the rows with a hole behind them,
    # padded to theirs, and a launch runs the dense body over the first
    # part and the ragged one over the second (`_run`'s `splits`).  0: the
    # set's rows stand in the set's order and run one body
    split: int = 0
    # where each of the set's rows stands in the arrays (host, int32 [S]);
    # None: row i stands at i.  What a group column is laid out by
    at: Optional[np.ndarray] = None


def whole_first(whole: np.ndarray):
    """The layout of a working set stored whole rows first, from the fact
    of its rows (`whole[i]`: row i fills every slot of the grid, a fact of
    the mirror's build): -> (index [Sw + Sh] int32: the set's row at each
    place, -1 at the places that pad a part to its rung; at [S] int32:
    where each row stands; Sw, the holed part's first row), the rows of
    either part in the set's order; None where the set has no holed row
    or no whole one and is stored as it was.  Each part is padded to a
    rung of the row ladder of its own (`pad_series_count`), so the split
    row is a static shape that moves a rung at a time and never with one
    series.  One pass over the set's rows, made where a working set is
    padded and never on a request."""
    pad = pad_series_count
    whole = np.asarray(whole, bool)
    n_whole = int(np.count_nonzero(whole))
    if not 0 < n_whole < whole.size:
        return None
    Sw = pad(n_whole)
    at = np.empty(whole.size, np.int32)
    at[whole] = np.arange(n_whole, dtype=np.int32)
    at[~whole] = Sw + np.arange(whole.size - n_whole, dtype=np.int32)
    index = np.full(Sw + pad(whole.size - n_whole), -1, np.int32)
    index[at] = np.arange(whole.size, dtype=np.int32)
    return index, at, Sw


class PaddedGroups(NamedTuple):
    """The small grouping-dependent half — one per (by, without) variant."""
    gids_p: jax.Array    # [Sp, 1] int32 (-1 pad rows)
    gsize: np.ndarray    # [num_groups]


def pad_values(vals, vbase, plan: FusedPlan, device=None,
               phase=None, split=None) -> PaddedValues:
    """`phase`: the rows' phases on the plan's base row, a host [S] array
    of whole milliseconds (all zero is None: the data decide the variant)
    or the [Sp, 1] f32 column already on the device.  `split`: the rows
    come whole rows first, laid out by `whole_first` (its (at, Sw)), each
    part on its rung already: they are padded along the row alone."""
    S = vals.shape[0]
    Sp = S if split else pad_series_count(S)
    phase_p = None
    if getattr(phase, "ndim", 1) == 2:
        phase_p = phase
    elif phase is not None and np.any(phase):
        col = np.zeros((Sp, 1), np.float32)
        col[:S, 0] = phase
        phase_p = (jnp.asarray(col) if device is None
                   else jax.device_put(col, device))
    if device is not None:
        # commit the inputs straight to the owning chip so the pad
        # computes (and its result lives) there — staging through
        # jnp.asarray would materialize the full [S, T] block on the
        # default device first and pay the copy twice; uncommitted
        # operands then follow the committed ones
        v = jax.device_put(np.asarray(vals, np.float32), device)
        vb = jax.device_put(np.asarray(vbase, np.float32), device)
    else:
        v = jnp.asarray(vals, jnp.float32)
        vb = jnp.asarray(vbase, jnp.float32)
    vals_p = jnp.zeros((Sp, plan.Tp), jnp.float32)
    vals_p = vals_p.at[:S, :vals.shape[1]].set(v)
    vbase_p = jnp.zeros((Sp, 1), jnp.float32)
    vbase_p = vbase_p.at[:S, 0].set(vb)
    if split:
        return PaddedValues(vals_p, vbase_p, phase_p, int(split[1]),
                            split[0])
    return PaddedValues(vals_p, vbase_p, phase_p)


def pad_groups(gids, S: int, num_groups: int,
               device=None, at=None, rows: Optional[int] = None
               ) -> PaddedGroups:
    """`at` / `rows`: the column has `rows` rows and series i stands at
    `at[i]` (a part of a working set, or a set stored whole rows first:
    `PaddedValues.at`); the rows nobody stands at belong to no group, as
    the rows that pad a set to its rung do.  None: series i stands at i
    of `pad_series_count(S)` rows."""
    Sp = pad_series_count(S) if rows is None else rows
    gids_np = np.asarray(gids, np.int32)
    # padded on the host: one upload of a [Sp, 1] column, and no program
    # that would compile once a row count
    col = np.full((Sp, 1), -1, np.int32)
    col[slice(S) if at is None else at, 0] = gids_np
    gids_p = (jnp.asarray(col) if device is None
              else jax.device_put(col, device))
    gsize = np.bincount(gids_np, minlength=num_groups)[:num_groups]
    return PaddedGroups(gids_p, gsize)


def pad_inputs(vals, vbase, gids, plan: FusedPlan,
               num_groups: int, device=None, phase=None) -> PreparedInputs:
    v = pad_values(vals, vbase, plan, device=device, phase=phase)
    g = pad_groups(gids, vals.shape[0], num_groups, device=device)
    return PreparedInputs(v.vals_p, v.vbase_p, g.gids_p, g.gsize, v.phase_p)


def fused_rate_groupsum(vals, vbase, gids, plan: FusedPlan,
                        num_groups: int, fn_name: str = "rate",
                        precorrected: bool = False,
                        interpret: bool = False,
                        prepared: Optional[PreparedInputs] = None,
                        ragged: bool = False,
                        device=None, phase=None
                        ) -> Tuple[jax.Array, np.ndarray]:
    """-> (sums [G, W] device array, counts [G, W] numpy).

    vals: [S, T] f32 rebased values (dense, shared grid); ignored when
    `prepared` is given.  vbase: [S] f32 per-series value base (absolute
    = rebased + vbase).  Present-count is shared across series under the
    dense/shared-grid precondition: counts[g, w] = |group g| * 1{n[w] >= 2}
    — NaN where 0, matching ops/agg.py present().  ragged=True runs the
    validity-aware kernel variant instead; counts then come back from the
    kernel's per-cell presence output.  `phase` ([S] whole ms, see
    pad_values): rows at `plan`'s timestamp row plus their phase; counts
    come back from the kernel then too.

    `device` pins every operand (values, plan rows) to that chip so the
    jit executes THERE: a leaf over a sharded mirror runs on its
    mirror's chip (doc/multichip.md).
    """
    over_time = fn_name in OVER_TIME_FNS
    if prepared is None:
        prepared = pad_inputs(vals, vbase, gids, plan, num_groups,
                              device=device, phase=phase)
    elif device is None:
        # caller-prepared inputs may already be pinned (sharded mirror
        # mode) — put the plan rows on the same chip
        device = _committed_device(prepared.vals_p)
    Gp = pad_group_count(num_groups)
    phased = prepared.phase_p is not None
    res, _ = _enqueue_run(
        plan, device, (_kernel_set(prepared, (prepared.gids_p,)),), None,
        (Gp,),
        **_flavor(plan, fn_name, precorrected, interpret, ragged,
                  phased)._asdict())
    if ragged or phased:
        sums, cnts = res
        counts = np.asarray(cnts, np.float64)[:num_groups, :plan.W]
    else:
        sums = res
        wvalid = plan.wvalid1 if over_time else plan.wvalid
        counts = prepared.gsize[:, None].astype(np.float64) * \
            wvalid[None, :].astype(np.float64)
    return sums[:num_groups, :plan.W], counts


def warmup_compile(S: int, T: int, W: int, G: int,
                   fn_name: str = "rate") -> float:
    """Compile (or cache-deserialize) the fused kernel for the canonical
    padded shape of (S series, T samples, W windows, G groups) using
    device zeros — the boot-warmup hook behind config.warmup_shapes.
    Returns wall seconds spent.  The compiled program is keyed by the
    BUCKETED shape, so any production working set in the same buckets
    hits it."""
    import time
    t0 = time.perf_counter()
    step = 10_000
    W = max(min(W, T), 1)
    ts_row = np.arange(T, dtype=np.int64) * step
    wends = ts_row[-1] - np.arange(W, dtype=np.int64)[::-1] * step
    plan = build_plan(ts_row, wends, 300_000)
    vals = jnp.zeros((S, T), jnp.float32)
    vbase = jnp.zeros((S,), jnp.float32)
    gids = (np.arange(S) % max(G, 1)).astype(np.int32)
    interpret = jax.default_backend() != "tpu"   # leafexec's gate, exactly
    sums, _ = fused_rate_groupsum(vals, vbase, gids, plan, max(G, 1),
                                  fn_name, precorrected=True,
                                  interpret=interpret)
    sums.block_until_ready()
    # also warm the general XLA path at this shape: any non-fusable query
    # over the same working-set shape hits it.  Like the kernel above, a
    # compile the device refuses raises (boot fails loudly on it)
    from filodb_tpu.ops import agg as agg_ops
    from filodb_tpu.ops.rangefns import evaluate_range_function
    from filodb_tpu.ops.timewindow import to_offsets

    ts_one = to_offsets(ts_row[None, :], np.full(1, T), 0)

    @jax.jit
    def _general(ts_off, v, vb, g, w):
        res = evaluate_range_function(ts_off, v, w, 300_000, fn_name,
                                      shared_grid=True, vbase=vb,
                                      precorrected=True)
        return agg_ops.aggregate("sum", res, g, max(G, 1))

    _general(jnp.asarray(ts_one), vals, vbase, jnp.asarray(gids),
             jnp.asarray(wends.astype(np.int32))).block_until_ready()
    return time.perf_counter() - t0


def present_sum(sums, counts) -> np.ndarray:
    """Finish the 3-phase contract host-side: NaN where no contributors."""
    s = np.asarray(sums, np.float64)
    return np.where(counts > 0, s, np.nan)


def jit_cache_stats() -> dict:
    """Entry counts of the jitted query kernels' compile caches.  Kept
    for ad-hoc inspection; the /metrics surface no longer samples this
    at scrape time — utils/devicetelem pushes compile events in at
    compile time (watched_call around every dispatch), so events between
    scrapes or before a restart are never lost."""
    out = {}
    for name, fn in (("fused_run", _run),
                     ("fused_minmax", _fused_minmax_jit)):
        try:
            out[name] = int(fn._cache_size())
        except Exception:  # noqa: BLE001 — private jax API: best-effort
            pass
    return out


# ------------------------------------------------------- broadened leaf API
# (VERDICT r2 item 2: count/avg/min/max group-aggs, min/max_over_time via
# reduce_window, ragged/NaN working sets)

def uniform_window_geometry(ts_row: np.ndarray, wends: np.ndarray,
                            range_ms: int):
    """(first0, stride_samples, width_samples, t_needed) when every window
    covers a constant-width, constant-stride span of the (conceptually
    extended) uniform grid — the precondition for lax.reduce_window — else
    None.  Closed-form from the grid spacing, so windows hanging past the
    data's right edge (the `end=now` dashboard shape) stay uniform:
    t_needed > len(ts_row) tells the caller to NaN-pad that tail and run
    the ragged variant.  Irregular grids/steps or left-clipped windows
    fall back to the general path."""
    ts_row = np.asarray(ts_row, dtype=np.int64)
    wend = np.asarray(wends, dtype=np.int64)
    T = ts_row.size
    if wend.size == 0 or T < 2:
        return None
    d = int(ts_row[1] - ts_row[0])
    if d <= 0 or (np.diff(ts_row) != d).any():
        return None
    t0 = int(ts_row[0])
    if wend.size > 1:
        s = int(wend[1] - wend[0])
        if s <= 0 or (np.diff(wend) != s).any() or s % d:
            return None
        stride = s // d
    else:
        stride = 1
    f0 = -(-(int(wend[0]) - int(range_ms) + 1 - t0) // d)      # ceil div
    l0 = (int(wend[0]) - t0) // d
    width = l0 - f0 + 1
    if f0 < 0 or width < 1:
        return None
    t_needed = l0 + stride * (wend.size - 1) + 1
    return f0, stride, width, t_needed


def fused_minmax_agg(vals, vbase, gids, f0: int, stride: int, width: int,
                     W: int, fn_name: str, agg_op: str, num_groups: int,
                     ragged: bool):
    """Compile-watched wrapper over the jitted body (_fused_minmax_jit):
    the trace-cache delta around the call pushes compile events into the
    device telemetry ledger at compile time (utils/devicetelem)."""
    from filodb_tpu.utils.devicetelem import watched_call
    shape = (f"S{vals.shape[0]}xT{vals.shape[1]}xW{W}xG{num_groups}"
             f":{fn_name}" + (":ragged" if ragged else ""))
    return watched_call(
        "fused_minmax", _fused_minmax_jit, shape,
        lambda: _fused_minmax_jit(vals, vbase, gids, f0, stride, width,
                                  W, fn_name, agg_op, num_groups,
                                  ragged),
        device=_committed_device(vals))


@functools.partial(jax.jit, static_argnames=(
    "f0", "stride", "width", "W", "fn_name", "agg_op", "num_groups",
    "ragged"))
def _fused_minmax_jit(vals, vbase, gids, f0: int, stride: int, width: int,
                      W: int, fn_name: str, agg_op: str, num_groups: int,
                      ragged: bool):
    """min/max_over_time + group aggregation in ONE jit: a strided
    lax.reduce_window over the values (one HBM pass; the VPU's native
    windowed order-statistic) straight into the 3-phase map (segment
    reduction on the T/W-times-smaller [S, W] intermediate) with no host
    round trip.  Runs on any backend — pure XLA, no Pallas.

    vals [S, T] (absolute values = vals + vbase broadcast), gids [S].
    Returns partial components [G, W, C] per ops/agg.AGGREGATORS.
    """
    from jax import lax

    from filodb_tpu.ops import agg as agg_ops

    is_min = fn_name == "min_over_time"
    seg = vals[:, f0:f0 + stride * (W - 1) + width]
    if vbase is not None:
        seg = seg + vbase[:, None]
    init = jnp.inf if is_min else -jnp.inf
    valid = ~jnp.isnan(seg)
    x = jnp.where(valid, seg, init) if ragged else seg
    red = lax.reduce_window(
        x, init, lax.min if is_min else lax.max,
        window_dimensions=(1, width), window_strides=(1, stride),
        padding="VALID")                               # [S, W]
    if ragged:
        # absence = no VALID sample in the window, counted explicitly — a
        # sentinel check on `red` would misreport windows whose real
        # samples are themselves +/-Inf (legal float samples)
        cnt = lax.reduce_window(
            valid.astype(jnp.float32), 0.0, lax.add,
            window_dimensions=(1, width), window_strides=(1, stride),
            padding="VALID")
        red = jnp.where(cnt > 0, red, jnp.nan)
    return agg_ops.map_phase(agg_op, red, gids, num_groups)


def fused_leaf_agg(plan: FusedPlan, prepared: PreparedInputs,
                   gids: np.ndarray, num_groups: int, fn_name: str,
                   agg_op: str, precorrected: bool = False,
                   interpret: bool = False, ragged: bool = False
                   ) -> np.ndarray:
    """One fused leaf evaluation -> partial components [G, W, C] (float64,
    ops/agg.AGGREGATORS layout) for any (fusable fn, agg) combination on
    the matmul kernel path.  agg sum/avg/count ride the group matmul;
    agg min/max use the kernel's per-series output mode plus an XLA
    segment reduction (ops/agg.map_phase) on the small [S, W] result.
    Single-panel form of fused_leaf_agg_batch."""
    values = PaddedValues(prepared.vals_p, prepared.vbase_p,
                          prepared.phase_p)
    groups = PaddedGroups(prepared.gids_p, prepared.gsize)
    return fused_leaf_agg_batch(
        plan, values, [(groups, num_groups, agg_op)], fn_name,
        precorrected=precorrected, interpret=interpret, ragged=ragged,
        num_series=len(gids))[0]


@functools.partial(jax.jit, static_argnames=(
    "ops", "num_groups", "S", "W", "minsamp"))
def _per_series_aggs(res, rows, gids, *, ops, num_groups, S: int, W: int,
                     minsamp: int):
    """Finish a per-series-mode run for its min/max panels in one jit:
    mask the [Sp, Wp] kernel output to present cells (the kernel's
    presence output on ragged rows, else the shared window validity
    n1 >= minsamp off the plan rows) and segment-reduce it per panel
    (ops/agg.map_phase) -> one [G, W, C] partial a panel."""
    from filodb_tpu.ops import agg as agg_ops
    if isinstance(res, (tuple, list)):
        present = res[1][:S, :W] > 0
        res = res[0]
    else:
        present = rows[_N1:_N1 + 1, :W] >= minsamp
    per = jnp.where(present, res[:S, :W], jnp.nan)
    return tuple(agg_ops.map_phase(op, per, g[:S, 0], G)
                 for op, g, G in zip(ops, gids, num_groups))


def launch_band_tiles(plan: FusedPlan, sets, num_groups, kind: str,
                      ragged: bool, phased: bool, with_drops: bool,
                      Tq: int, splits=None) -> int:
    """The tiles of band one `_run` call over `sets` builds: for every
    part (`_set_parts`) whose block `band_form` sizes by the tiled band
    (as `_run` asks it, by the columns loaded), ceil(its columns /
    _BAND_COLS); 0 for a part that holds its band resident, and for the
    kinds that have none."""
    if _selects_by_gather(kind):
        return 0
    return sum(-(-cols // _BAND_COLS) for _, cols, tiled in _part_forms(
        sets, num_groups, splits, Tq, plan.Tp, plan.t1.shape[1], kind,
        ragged, phased, with_drops) if tiled)


def _enqueue_run(plan: FusedPlan, device, sets, offsets, num_groups,
                 splits=None, hist=None, **flags):
    """One `_run` dispatch: the small host operands put explicitly
    (enqueue_operands), then the one jit call over `sets`, compile-
    watched.  -> (the call's lazy result, the uploaded rows).  A call
    whose program builds its band in tiles says so on the host's line of
    a trace (`filodb-part:leaf.band_tiled` around the jit call) and in the
    shape a compile event names.  `splits`: `_run`'s (None: no set of the
    call is stored whole rows first).  `hist`: the histogram epilogue's
    device operands (inv, perm, q, les), for which the call is
    `_run_hist_quantile` and its result the [Gm_p, Wp] quantiles."""
    from filodb_tpu.utils.devicetelem import watched_call
    from filodb_tpu.utils.metrics import span_part
    kind, ragged, phased = flags["kind"], flags["ragged"], flags["phased"]
    tiles = launch_band_tiles(plan, sets, num_groups, kind, ragged, phased,
                              flags["with_drops"], flags["Tq"], splits)
    set_rows = [st[0].shape[0] for st in sets]
    with span_part("leaf.enqueue_pack"):
        rows, tsrow, offs = enqueue_operands(plan, device, kind, ragged,
                                             offsets, sets=len(sets),
                                             phased=phased, cols=flags["Tq"],
                                             band_tiles=tiles,
                                             set_rows=set_rows,
                                             whole_rows=splits)
    with span_part("leaf.enqueue_jit"), (
            span_part("leaf.band_tiled") if tiles
            else contextlib.nullcontext()):
        sig = _run_shape_sig(sets, plan, num_groups, kind, ragged, phased,
                             flags["steps"], flags["Tq"], splits) \
            + (f":{tiles}bandtiles" if tiles else "")
        # a call that ends in the histogram epilogue: the same arguments
        # with the epilogue's operands behind them, and its merged groups
        # in the shape a compile event names
        run = _run if hist is None else _run_hist_quantile
        if hist is not None:
            sig += f":quantile{hist[0].shape[1]}g"
        res = watched_call(
            "fused_run", run, sig,
            lambda: run(sets, offs, rows, tsrow, *(hist or ()),
                        num_groups=num_groups, splits=splits, **flags),
            device=device)
    return res, rows


class _FlavorFlags(NamedTuple):
    is_counter: bool
    is_rate: bool
    with_drops: bool
    interpret: bool
    kind: str
    ragged: bool
    phased: bool
    steps: int
    Tq: int


def _flavor(plan: FusedPlan, fn_name: str, precorrected: bool,
            interpret: bool, ragged: bool,
            phased: bool = False) -> _FlavorFlags:
    """`_run`'s static flags of one (function, precorrected, interpret,
    ragged, phased) flavor over `plan`, whose windows say how far the
    ragged rate family's fills reach (scan_steps: 0 for the others) and
    how many columns of a row they reach at all (`Tq`).  Two flavors take
    the row's Tp whatever the windows reach.  A kernel that corrects
    resets itself: the corrections up to a slot are the ROW'S prefix sum,
    which no block that starts later holds, and a prefix started later
    rounds otherwise.  And the dense gather kinds on one shared row
    (`rate` / `increase` / `delta`, `last_over_time`, the histogram rows):
    one or two gathers over the values are all their kernel does along
    the row, and turning the block costs them more than two tiles of
    six give: 1.685 against 1.625 ms a launch at the hour-long cells'
    shape, 2.30 against 2.19 over 64-bucket rows, 1.44 against 1.42 for
    last_over_time, where the phased, ragged and band kinds gain 10 to
    29% (PERF.md section 6, PR 46)."""
    is_counter = fn_name in ("rate", "increase")
    kind = fn_name if fn_name in OVER_TIME_FNS else "rate_family"
    with_drops = is_counter and not precorrected
    return _FlavorFlags(
        is_counter, fn_name == "rate", with_drops,
        interpret, kind, ragged, phased,
        scan_steps(plan, kind, ragged, phased),
        plan.Tp if _takes_row(kind, with_drops, ragged, phased)
        else plan.Tq)


def _kernel_set(values, gid_cols: tuple) -> tuple:
    """One working set as `_run` takes it: (vals_p, vbase_p, gid columns),
    and the phase column behind them where the set has one."""
    st = (values.vals_p, values.vbase_p, gid_cols)
    return st if values.phase_p is None else st + (values.phase_p,)


def _splits_of(values) -> Optional[Tuple[int, ...]]:
    """`_run`'s `splits` for a call over these working sets: each one's
    stored split row; None where none is stored whole rows first, which
    is the call as it was."""
    splits = tuple(getattr(v, "split", 0) for v in values)
    return splits if any(splits) else None


# an `inv` entry of the histogram epilogue for a group its set lacks: a row
# past any set's groups, which `jnp.take(mode="fill")` reads as zeros
_ABSENT = np.iinfo(np.int32).max


class FusedDispatch:
    """The group-mode panels (`sum`, `avg`, ragged `count`) of the working
    sets that share a plan, a function flavor and a device, as ONE `_run`
    call, ONE blocking readback and ONE presentation: `add` each set's
    panels (fused_leaf_agg_batch does), `enqueue` once, `fetch` once,
    then `comps(k)` hands set k its panels' [G, W, C] partials, views of
    the one array.  What a request's shard leaves cost the host is then
    one dispatch, not one a shard (ROADMAP A1 [A7]); one set alone is
    the same path.

    The sets of a call are ordered by (Sp, Gp, panels) before the jit
    call, so the trace cache is keyed by the multiset of their shapes
    and not by the order the leaves were prepared in.  Every set is its
    own Mosaic kernel in the one XLA program; 30 sets compile cold in
    8.7 to 9.3 s on the chip's host against the server's 120 s query
    budget (PERF.md section 6, PR 36), so a call is never split.

    `hist_quantile` (before `enqueue`): the sets are the histogram leaves
    of one `sum` reduce under a `histogram_quantile`; the call then ends
    in the merge and the quantile (`_run_hist_quantile`) over the device
    operands handed in (`hist_layout` makes the two that follow the sets'
    order), `fetch` reads the [G, W] answer (`answer`) and nothing of the
    sets', and `comps` is not to be asked."""

    def __init__(self, plan: FusedPlan, fn_name: str,
                 precorrected: bool = False, interpret: bool = False,
                 ragged: bool = False, device=None, phased: bool = False):
        self.plan = plan
        self.key = (fn_name, precorrected, interpret, ragged, phased)
        self.device = device
        self.flags = _flavor(plan, *self.key)
        # per set: (values, [(groups, G, op)], offsets, the panels' G summed)
        self._sets: list = []
        self._res = None
        self._counts = None         # dense rows: [rows, W] f64
        self._lo: list = []         # per set: its panels' first rows
        self._comps = None
        self._hist = None           # ((inv, perm, q, les) on the device, G)
        self.answer = None          # the epilogue's [G, W] f64, once fetched

    def __len__(self):
        return len(self._sets)

    def add(self, values: PaddedValues, panels) -> int:
        """Queue one working set's group-mode panels
        [(PaddedGroups, num_groups, agg_op)]; -> its index for `comps`."""
        panels = list(panels)
        # each panel's first group within the set ([0] for the one panel
        # a request's leaf brings), and the groups of all its panels
        offs, total = [], 0
        for _, G, _ in panels:
            offs.append(total)
            total += int(G)
        self._sets.append((values, panels, offs, total))
        return len(self._sets) - 1

    def _order(self):
        """(the sets' order in the jit call: by shape, so that a program
        is keyed by the shapes and not by the order the leaves came in;
        every set's padded groups)."""
        gps = [pad_group_count(total) for *_, total in self._sets]
        return sorted(range(len(self._sets)), key=lambda k: (
            self._sets[k][0].vals_p.shape[0], gps[k],
            len(self._sets[k][1]), self._sets[k][0].split)), gps

    def hist_layout(self, children, index, groups: int, B: int):
        """The histogram epilogue's `inv` int32 [sets, Gm_p] and `perm`
        int32 [sets] (`_merge_hist_sets` says what they hold), host arrays
        in the order this call hands its sets to the jit: `children` the
        call's sets (`add`'s indices) in the reduce's child order, every
        one of them, one `sum` panel of (group, bucket) slots each;
        `index` every child's every group's merged row, child order
        (execbase._merge_layout's); `groups` the merged groups; `B` the
        buckets.  What they hold follows from `index` and the sets' order
        alone: neither the flavor nor the rows' data."""
        if sorted(children) != list(range(len(self._sets))) \
                or any(len(panels) != 1 or panels[0][2] != "sum"
                       for _, panels, _, _ in self._sets):
            raise ValueError("the histogram epilogue takes a call whose "
                             "sets are the reduce's children, a panel each")
        # (plain lists and one array each at the end: every NumPy call
        # that lets the interpreter lock go costs a request a hand-off,
        # and a fancy assignment a set read 6 ms a request on the chip)
        at = {k: p for p, k in enumerate(self._order()[0])}
        inv = [[_ABSENT] * pad_group_count(groups) for _ in at]
        merged, lo = index.tolist(), 0
        for k in children:
            G = self._sets[k][3] // B           # the set's groups
            row = inv[at[k]]
            for g in range(G):
                row[merged[lo + g]] = g
            lo += G
        return (np.array(inv, np.int32),
                np.array([at[k] for k in children], np.int32))

    def hist_quantile(self, operands, groups: int) -> None:
        """End the call in the histogram epilogue: `operands` = (inv,
        perm, q f32 scalar, les f32 [B]) ON THE CALL'S DEVICE (the jit
        call transfers nothing), `groups` the merged groups `fetch` cuts
        the answer to."""
        self._hist = (tuple(operands), groups)

    def enqueue(self) -> None:
        """Issue the one jit call over everything added; reads nothing
        back.  Nothing added (every panel min/max or a dense count): no
        call."""
        if not self._sets or self._res is not None:
            return
        order, gps = self._order()
        # (an epilogue's present cells are made on the device)
        dense = not (self.flags.ragged or self.flags.phased
                     or self._hist is not None)
        # the groups' sizes down the output's rows (a set's panels, then
        # its pad rows at 0): the counts of dense rows are made from it
        gsize = np.zeros(sum(gps)) if dense else None
        sets, offsets = [], []
        self._lo = [None] * len(order)
        base = 0
        for k in order:
            values, panels, offs, _ = self._sets[k]
            sets.append(_kernel_set(
                values, tuple(g.gids_p for g, _, _ in panels)))
            offsets += offs
            self._lo[k] = lo = [base + o for o in offs]
            if dense:
                for (g, G, _), at in zip(panels, lo):
                    gsize[at:at + G] = g.gsize
            base += gps[k]
        multi = len(offsets) > len(sets)
        self._res, _ = _enqueue_run(
            self.plan, self.device, tuple(sets), offsets if multi else None,
            tuple(gps[k] for k in order),
            _splits_of(self._sets[k][0] for k in order),
            None if self._hist is None else self._hist[0],
            **self.flags._asdict())
        if dense:
            # dense rows on one timestamp row: the counts are |group| x
            # the shared window validity, nothing of the result: made
            # while the device works
            plan = self.plan
            wvalid = (plan.wvalid1 if self.flags.kind in OVER_TIME_FNS
                      else plan.wvalid)
            self._counts = gsize[:, None] * wvalid[None, :].astype(
                np.float64)

    def fetch(self) -> None:
        """The one synchronizing readback, and the panels' presentation
        over the whole array: sums masked to the present cells beside
        their counts (the kernel's presence output on ragged or phased rows,
        else |group| x the shared window validity), f64 on the host."""
        if self._comps is not None or self._res is None \
                or self.answer is not None:
            return
        W = self.plan.W
        if self._hist is not None:
            # f32 widens exactly; NaN where no series was present
            self.answer = np.asarray(self._res)[:self._hist[1], :W] \
                .astype(np.float64)
            return
        if self.flags.ragged or self.flags.phased:
            sums, counts = (r[:, :W] for r in jax.device_get(self._res))
        else:
            sums, counts = np.asarray(self._res)[:, :W], self._counts
        # f32 sums x 0/1 and f32 counts widen exactly: written straight
        # into the f64 [rows, W, 2] block (at 6 in flight every NumPy
        # call that lets the interpreter lock go costs a hand-off)
        comps = np.empty(sums.shape + (2,), np.float64)
        np.multiply(sums, counts > 0, out=comps[..., 0])
        comps[..., 1] = counts
        self._comps = comps

    def comps(self, k: int) -> list:
        """Set k's per-panel [G, W, C] partials (ops/agg.AGGREGATORS
        layout), in the order they were added."""
        self.fetch()
        out = []
        for (_, G, op), lo in zip(self._sets[k][1], self._lo[k]):
            blk = self._comps[lo:lo + G]
            out.append(blk[..., 1:] if op == "count" else blk)
        return out


def fused_leaf_agg_batch(plan: FusedPlan, values: PaddedValues, panels,
                         fn_name: str, precorrected: bool = False,
                         interpret: bool = False, ragged: bool = False,
                         num_series: Optional[int] = None,
                         lazy: bool = False,
                         dispatch: Optional[FusedDispatch] = None):
    """Evaluate P aggregation panels over ONE working set in at most two
    kernel dispatches — the dashboard case (same metric + window grid,
    different `by (...)` groupings / agg ops), where the per-call
    dispatch latency dominates device time (doc/kernels.md).

    panels: [(PaddedGroups, num_groups, agg_op)].  All panels share
    (plan, values, fn_name, precorrected, ragged).  sum/avg/count panels
    merge into one group-mode run (merge_gid_cols inside the jit: disjoint
    id spaces, multi-hot epilogue); min/max panels share one per-series-
    mode run finished by one jit of per-panel segment reductions
    (_per_series_aggs); each run is ONE jit call whose host operands
    (enqueue_operands) are put explicitly; dense count panels are
    host-only math.  Returns per-panel [G, W, C] float64 components in
    input order (ops/agg.AGGREGATORS layout).

    `dispatch`: a FusedDispatch of the same plan, flavor and device that
    the CALLER enqueues once every working set has been through here:
    this set's group-mode run then rides that one call beside the other
    sets' (query/fusedbatch.py: a request's shard leaves).  Without one
    the set is a call of its own, enqueued before returning.

    lazy=True returns a zero-arg finisher instead: the kernel work is
    DISPATCHED before returning (or by the caller's `dispatch.enqueue`),
    but the synchronizing host readback waits until the finisher is
    called — so a multi-shard batch whose working sets live on different
    chips (sharded DeviceMirror mode) dispatches everything first and
    the chips compute concurrently."""
    over_time = fn_name in OVER_TIME_FNS
    wvalid = plan.wvalid1 if over_time else plan.wvalid
    # rows on a phase grid: presence is a row's own, so the kernel counts
    # it (as on ragged rows) and `count` rides the group-mode run
    phased = values.phase_p is not None
    own = dispatch is None
    if own:
        # sharded DeviceMirror mode: the working set is committed to its
        # shard's chip — the plan rows go there too, so the call runs there
        dispatch = FusedDispatch(plan, fn_name, precorrected, interpret,
                                 ragged, _committed_device(values.vals_p),
                                 phased)
    elif dispatch.plan is not plan or dispatch.key != (
            fn_name, precorrected, interpret, ragged, phased):
        raise ValueError("fused dispatch shared across plans or flavors")

    def dense_counts(groups):
        return groups.gsize[:, None].astype(np.float64) * \
            wvalid[None, :].astype(np.float64)

    mm_idx = [i for i, (_, _, op) in enumerate(panels)
              if op in ("sum", "avg")
              or (op == "count" and (ragged or phased))]
    ps_idx = [i for i, (_, _, op) in enumerate(panels)
              if op in ("min", "max")]
    bad = [op for _, _, op in panels
           if op not in ("sum", "avg", "count", "min", "max")]
    if bad:
        raise ValueError(f"unsupported fused agg {bad[0]}")

    out: list = [None] * len(panels)
    # ---- dispatch phase: every device call is issued here (the group-
    # mode one by the caller when the dispatch is shared), nothing is
    # read back — all results below are lazy device arrays
    slot = None
    if mm_idx:
        slot = dispatch.add(values, [panels[i] for i in mm_idx])
        if own:
            dispatch.enqueue()
    ps_comps = ()
    if ps_idx:
        from filodb_tpu.utils.metrics import span_part
        S = num_series
        if S is None:
            gp0 = panels[ps_idx[0]][0].gids_p[:, 0]
            S = int(np.asarray(gp0 >= 0).sum())
        # one shared per-series run: the [S, W] output is group-agnostic
        # (and its rows are the set's in the set's order: `_run` refuses a
        # set stored whole rows first, which the leaf keeps from here)
        res, rows = _enqueue_run(
            plan, dispatch.device,
            (_kernel_set(values, (panels[ps_idx[0]][0].gids_p,)),), None,
            (8,), _splits_of((values,)), per_series=True,
            **dispatch.flags._asdict())
        with span_part("leaf.enqueue_jit"):
            ps_comps = _per_series_aggs(
                res, rows, tuple(panels[i][0].gids_p for i in ps_idx),
                ops=tuple(panels[i][2] for i in ps_idx),
                num_groups=tuple(int(panels[i][1]) for i in ps_idx),
                S=int(S), W=plan.W, minsamp=1 if over_time else 2)

    # ---- finish phase: synchronizing host readbacks + assembly
    def finish():
        if slot is not None:
            for i, comp in zip(mm_idx, dispatch.comps(slot)):
                out[i] = comp
        for i, comp in zip(ps_idx, ps_comps):
            out[i] = np.asarray(comp, np.float64)
        for i, (groups, G, op) in enumerate(panels):
            if out[i] is None:          # dense count: pure host math
                out[i] = dense_counts(groups)[..., None]
        return out

    return finish if lazy else finish()
