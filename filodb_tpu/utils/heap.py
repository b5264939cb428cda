"""The interpreter's cycle collector, kept off the requests' path.

A node's heap is a few million long-lived objects (part keys, index
postings, group keys: 1.5 M at 262,144 series) beside the few thousand a
request makes.  The collector's full pass walks all of them with the
interpreter lock held: 0.5 s at that size, twice in 20 s of dashboard
traffic, and every request in flight waits for it (PERF.md section 6,
PR 35: six requests at +0.5 and +1.1 s set the 32-shard cell's p95).

`settle_heap()` runs ONE full pass at a time of the caller's choosing, on
the caller's thread (a background job's), and then freezes what survived:
the collector's own passes, between two settles, walk only what was made
since, which takes tens of milliseconds.  Nothing is leaked: each settle
thaws the frozen objects first, so cycles that died among them are found
by its pass.

The full passes that the interpreter starts on its own, between two
settles, are booked by name: a `gc.callbacks` hook counts every
generation-2 pass and its seconds (`gc_full_passes_total`,
`gc_full_pass_seconds_total`), and inside a profiler session shows it as
the annotation `filodb:gc.full_pass` on the line of the thread it
interrupted, as a span would be, so a stalled window's trace says whether
the collector stalled it.  The hook opens no span: it runs at an
arbitrary bytecode boundary of that thread, inside a span's own enter or
the booking loop as well as anywhere else.  A settle's own pass is not
booked twice: its caller times it (`flush.heap_settle`,
`heap_settle_seconds_total`).
"""
import gc
import threading
import time
from typing import Optional

from filodb_tpu.utils.metrics import enter_annotation, registry

# a full pass a minute is plenty; a scheduler that ticks faster (a test's,
# or a dataset's beside another's) finds the heap settled already
MIN_INTERVAL_S = 30.0
_lock = threading.Lock()
_last = [float("-inf")]
_settling = [0]                 # the thread inside settle_heap's pass
# resolved here: the hook runs on a thread that may hold the registry's lock
_passes = registry.counter("gc_full_passes")
_pass_seconds = registry.counter("gc_full_pass_seconds")
_open = []                      # the pass under way: (start, annotation)


def _on_gc(phase: str, info: dict) -> None:
    """Generations 0 and 1 (some tens a request) return at once.  The
    interpreter runs one pass at a time, so one slot holds the open one."""
    if info["generation"] != 2 or _settling[0] == threading.get_ident():
        return
    if phase == "start":
        _open.append((time.perf_counter_ns(),
                      enter_annotation("gc.full_pass")))
    elif _open:
        t0, ann = _open.pop()
        took = time.perf_counter_ns() - t0
        if ann is not None:
            ann.__exit__(None, None, None)
        _passes.increment()
        _pass_seconds.increment(took * 1e-9)


gc.callbacks.append(_on_gc)


def settle_heap(min_interval_s: float = MIN_INTERVAL_S) -> Optional[float]:
    """Thaw, collect everything, freeze the survivors.  Returns the
    seconds it took, or None when the last settle was under
    `min_interval_s` ago."""
    with _lock:
        t0 = time.perf_counter()
        if t0 - _last[0] < min_interval_s:
            return None
        _settling[0] = threading.get_ident()
        try:
            gc.unfreeze()
            gc.collect()
            gc.freeze()
        finally:
            _settling[0] = 0
        _last[0] = time.perf_counter()
        took = _last[0] - t0
    registry.counter("heap_settles").increment()
    registry.counter("heap_settle_seconds").increment(took)
    registry.gauge("heap_frozen_objects").update(gc.get_freeze_count())
    return took
