"""A plan's kernel operands stay on the device (ISSUE 41).

`pf.enqueue_operands` puts a plan's `rows` / `prows` / `tsrow` on a device
at the first enqueue that takes them there and keeps the arrays with the
plan object (`plan.resident`), so the panels of an open and the leaves of
a request, which share the plan, upload once.  Interpret-mode kernels on
the CPU; `fused_enqueue_uploads_total` counts the puts really made."""
import gc
import sys
import threading
import time
import weakref

import numpy as np
import pytest

import jax

from filodb_tpu.ops import pallas_fused as pf
from filodb_tpu.ops.timewindow import make_window_ends
from filodb_tpu.utils.metrics import registry

STEP = 10_000
RANGE = 30 * STEP
S, T, G = 24, 80, 3


def _uploads():
    return registry.counter("fused_enqueue_uploads").value


def _plan(seed=0):
    """A new plan object over a small shared grid (equal for equal seeds)."""
    ts_row = np.arange(T, dtype=np.int64) * STEP + seed * 7
    return pf.build_plan(
        ts_row, make_window_ends(35 * STEP, (T - 5) * STEP, 5 * STEP), RANGE)


def _values(flavor, seed=1):
    """-> (vals f32 [S, T], kwargs of fused_rate_groupsum) of one flavor."""
    rng = np.random.default_rng(seed)
    vals = np.cumsum(rng.exponential(5.0, (S, T)), axis=1).astype(np.float32)
    kw = dict(fn_name="rate", precorrected=True, interpret=True)
    if flavor == "ragged-rate":
        vals[rng.random((S, T)) < 0.15] = np.nan
        kw["ragged"] = True
    elif flavor == "phased":
        kw["phase"] = (np.arange(S) * 997) % STEP
    elif flavor == "sum_over_time":
        kw.update(fn_name="sum_over_time", precorrected=False)
    return vals, kw


def _call(plan, flavor, device=None):
    vals, kw = _values(flavor)
    sums, counts = pf.fused_rate_groupsum(
        vals, np.zeros(S, np.float32), np.arange(S) % G, plan, G,
        device=device, **kw)
    return np.asarray(sums), np.asarray(counts)


FLAVORS = {"dense-rate": 1, "sum_over_time": 1, "phased": 1,
           "ragged-rate": 2}               # ragged rate reads tsrow too


@pytest.mark.parametrize("flavor", list(FLAVORS))
def test_a_second_enqueue_puts_nothing_and_answers_as_a_fresh_plan(flavor):
    plan = _plan()
    u0 = _uploads()
    first = _call(plan, flavor)
    assert _uploads() - u0 == FLAVORS[flavor]
    held = dict(plan.resident)
    assert len(held) == FLAVORS[flavor]
    u1 = _uploads()
    second = _call(plan, flavor)
    assert _uploads() == u1                 # the rows were there already
    assert all(plan.resident[k] is v for k, v in held.items())
    fresh = _call(_plan(), flavor)          # an equal plan that uploads
    assert _uploads() - u1 == FLAVORS[flavor]
    for a, b, c in zip(first, second, fresh):
        assert a.tobytes() == b.tobytes() == c.tobytes()
    assert np.isfinite(first[0]).any()


def test_rows_prows_and_tsrow_are_resident_apart():
    """A dense call, a phased one and a ragged rate call on one plan: each
    brings the one operand the plan lacks on the device, then none."""
    plan = _plan()
    u0 = _uploads()
    for flavor in ("dense-rate", "phased", "ragged-rate"):
        before = _uploads()
        _call(plan, flavor)
        assert _uploads() - before == 1, flavor
    assert _uploads() - u0 == 3
    assert set(plan.resident) == {(None, "rows"), (None, "prows"),
                                  (None, "tsrow")}
    for (_, which), arr in plan.resident.items():
        assert np.asarray(arr).tobytes() == getattr(plan, which).tobytes()
    for flavor in ("dense-rate", "phased", "ragged-rate"):
        _call(plan, flavor)
    assert _uploads() - u0 == 3


def test_offsets_are_the_calls_own_and_put_every_time():
    plan = _plan()
    offs = [0, 3]
    u0 = _uploads()
    rows0, ts0, offs0 = pf.enqueue_operands(plan, None, "rate_family", False,
                                            offs, sets=1)
    rows1, ts1, offs1 = pf.enqueue_operands(plan, None, "rate_family", False,
                                            offs, sets=1)
    assert _uploads() - u0 == 3             # rows once, offsets twice
    assert rows0 is rows1 and ts0 is None and ts1 is None
    assert offs0 is not offs1 and offs0.dtype == np.int32
    np.testing.assert_array_equal(np.asarray(offs1), offs)
    assert set(plan.resident) == {(None, "rows")}


@pytest.mark.multichip
def test_two_devices_hold_a_copy_each():
    d0, d1 = jax.devices()[:2]
    plan = _plan()
    u0 = _uploads()
    on0, on1 = _call(plan, "ragged-rate", d0), _call(plan, "ragged-rate", d1)
    assert _uploads() - u0 == 4             # rows + tsrow, a device
    assert set(plan.resident) == {(d, w) for d in (d0, d1)
                                  for w in ("rows", "tsrow")}
    for (dev, _), arr in plan.resident.items():
        assert arr.devices() == {dev}
    assert _call(plan, "ragged-rate", d1)[0].tobytes() == on1[0].tobytes()
    assert _uploads() - u0 == 4
    assert on0[0].tobytes() == on1[0].tobytes()


def test_six_first_enqueues_together_make_one_put_and_share_its_array(
        monkeypatch):
    """Six requests of an open may miss together: ONE puts (the others wait
    at `pf._RESIDENT_LOCK`, ISSUE 50; the counter says so) and all six go
    on with its array."""
    plan = _plan()
    real_put, puts, missed = jax.device_put, [], threading.Barrier(6)

    def slow_put(x, device=None, **kw):
        if x is plan.rows:
            puts.append(threading.get_ident())
            time.sleep(0.2)                 # the others arrive meanwhile
        return real_put(x, device, **kw)

    monkeypatch.setattr(jax, "device_put", slow_put)
    got, errors = [None] * 6, []

    def one(i):
        try:
            missed.wait(timeout=60)         # all six before any enqueue
            rows, _, _ = pf.enqueue_operands(plan, None, "rate_family",
                                             False)
            got[i] = (rows, _call(plan, "dense-rate"))
        except Exception as e:  # noqa: BLE001 — reported by the assert
            errors.append(e)

    u0 = _uploads()
    threads = [threading.Thread(target=one, args=(i,)) for i in range(6)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors and len(puts) == 1
    assert _uploads() - u0 == 1             # one real put, five waits
    assert set(plan.resident) == {(None, "rows")}
    kept = plan.resident[(None, "rows")]
    assert all(rows is kept for rows, _ in got)
    assert len({res[0].tobytes() for _, res in got}) == 1


def test_an_evicted_plan_takes_its_device_arrays_with_it(monkeypatch):
    """Nothing but the plan holds the resident arrays: once the plan cache
    evicts it and the requests that held it are gone, so are they."""
    from filodb_tpu.query import execbase
    cache = execbase._FUSED_PLAN_CACHE
    keys = [("plan", "test_plan_resident", i) for i in (0, 1)]
    with execbase._FUSED_CACHE_LOCK:
        plan = cache.insert(keys[0], _plan())
    _call(plan, "ragged-rate")
    refs = [weakref.ref(a) for a in plan.resident.values()]
    assert len(refs) == 2 and all(r() is not None for r in refs)
    # weighed with room for the copies on every local device
    assert execbase._plan_nbytes(plan) >= (1 + jax.local_device_count()) \
        * sum(a.nbytes for a in plan.resident.values())
    monkeypatch.setattr(cache, "_budget", lambda: 1)
    with execbase._FUSED_CACHE_LOCK:
        cache.insert(keys[1], _plan(1))     # over the budget: evicts
        assert keys[0] not in cache
        del cache[keys[1]]
    del plan
    gc.collect()
    assert all(r() is None for r in refs)
    # and no module-level table of plans or device arrays beside the caches
    assert not [n for n, v in vars(pf).items()
                if isinstance(v, dict) and any(
                    isinstance(x, jax.Array) for x in v.values())]
