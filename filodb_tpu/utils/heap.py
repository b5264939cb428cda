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
"""
import gc
import threading
import time
from typing import Optional

# a full pass a minute is plenty; a scheduler that ticks faster (a test's,
# or a dataset's beside another's) finds the heap settled already
MIN_INTERVAL_S = 30.0
_lock = threading.Lock()
_last = [float("-inf")]


def settle_heap(min_interval_s: float = MIN_INTERVAL_S) -> Optional[float]:
    """Thaw, collect everything, freeze the survivors.  Returns the
    seconds it took, or None when the last settle was under
    `min_interval_s` ago."""
    from filodb_tpu.utils.metrics import registry
    with _lock:
        t0 = time.perf_counter()
        if t0 - _last[0] < min_interval_s:
            return None
        gc.unfreeze()
        gc.collect()
        gc.freeze()
        _last[0] = time.perf_counter()
        took = _last[0] - t0
    registry.counter("heap_settles").increment()
    registry.counter("heap_settle_seconds").increment(took)
    registry.gauge("heap_frozen_objects").update(gc.get_freeze_count())
    return took
