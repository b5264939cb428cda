"""`utils/heap.settle_heap` (ISSUE 35): the cycle collector's full pass is
taken by a background job, at the end of a flush pass, and what survives is
frozen, so that the collector's own passes walk only what was made since."""
import gc

import pytest

from filodb_tpu.utils import heap
from filodb_tpu.utils.metrics import registry


@pytest.fixture(autouse=True)
def thaw():
    yield
    gc.unfreeze()
    heap._last[0] = float("-inf")


def test_a_settle_collects_cycles_and_freezes_the_survivors():
    gc.unfreeze()
    heap._last[0] = float("-inf")
    keep = [[i] for i in range(1000)]
    cycle = []
    cycle.append(cycle)                  # garbage only a collector finds
    ident = id(cycle)
    del cycle
    n0 = registry.counter("heap_settles").value
    took = heap.settle_heap()
    assert took is not None and took >= 0
    assert registry.counter("heap_settles").value - n0 == 1
    assert gc.get_freeze_count() >= len(keep)
    assert registry.gauge("heap_frozen_objects").value == gc.get_freeze_count()
    assert all(id(o) != ident for o in gc.get_objects())
    # the collector's own full pass now walks what was made since, only
    young = [[i] for i in range(10)]
    assert len(gc.get_objects()) < gc.get_freeze_count()
    assert len(young) == 10


def test_a_second_settle_inside_the_interval_does_nothing_and_nothing_leaks():
    assert heap.settle_heap() is not None
    frozen = gc.get_freeze_count()
    assert heap.settle_heap() is None           # under 30 s ago
    assert gc.get_freeze_count() == frozen
    # a cycle among frozen objects dies at the next settle: it thaws first
    a, b = [], []
    a.append(b), b.append(a)
    assert heap.settle_heap(min_interval_s=0.0) is not None
    ident = id(a)
    del a, b
    assert heap.settle_heap(min_interval_s=0.0) is not None
    assert all(id(o) != ident for o in gc.get_objects())


def test_a_flush_pass_ends_with_a_settle(monkeypatch):
    from filodb_tpu.core import flush
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    calls = []
    monkeypatch.setattr(flush, "settle_heap", lambda: calls.append(1))
    ms = TimeSeriesMemStore()
    ms.setup("prometheus", 0)
    sched = flush.FlushScheduler(ms, "prometheus", interval_s=0.6).start()
    try:
        import time
        deadline = time.time() + 5
        while not calls and time.time() < deadline:
            time.sleep(0.02)
    finally:
        sched.stop(final_flush=False)
    assert calls
