"""Batched fused-leaf dispatch: merge a dashboard's compatible panels
into single kernel launches (phase-2 of engine.query_range_batch).

Split from query/leafexec.py (round 4, no behavior change); see
doc/kernels.md "Dashboard batching" for the design and on-chip numbers.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from filodb_tpu.query.execbase import (AggPartial, _lru_touch,
                                       _merge_layout, _reduced_token)
from filodb_tpu.query.rangevector import ResultBlock
from filodb_tpu.utils.metrics import span

@dataclasses.dataclass
class FusedCall:
    """A fused matmul-kernel leaf evaluation with everything resolved
    except the kernel dispatch itself — the unit of merging for
    engine.query_range_batch.  Compatible calls (same plan + device
    values + function flavor) become panels of ONE
    ops/pallas_fused.fused_leaf_agg_batch dispatch: the dashboard case,
    where per-call dispatch latency dominates device time
    (doc/kernels.md round-4 measurements)."""
    plan: object                  # pf.FusedPlan
    values: object                # pf.PaddedValues (device-resident)
    groups: object                # pf.PaddedGroups
    gkeys: List
    wends: np.ndarray
    fn: str
    op: str
    precorrected: bool
    interpret: bool
    ragged: bool
    num_series: int
    # semantic identity (mirror serial + snapshot gen + column + row
    # subset + window params): lets equal-but-distinct plan/values
    # objects merge when the LRU caches declined to share them
    cache_key: Optional[tuple] = None
    # histogram leaf (sum(rate(bucket_metric[...]))): groups carry
    # (group, bucket) SLOTS; the finisher reshapes sums to [G, W, B] and
    # appends the present-series count (AggPartial op "hist_sum")
    bucket_les: Optional[np.ndarray] = None
    num_buckets: int = 1
    # keys-identity token for the produced AggPartial (execbase.agg_token
    # semantics) — rides through _present so kernel-path join operands
    # hit the exprfuse index-map cache like the host-routed ones
    cache_token: Optional[tuple] = None

    @property
    def phased(self) -> bool:
        """The working set's rows lie on a phase grid: the kernel's
        phased variant, which counts presence a row (pf.PaddedValues)."""
        return self.values.phase_p is not None

    def compat_key(self):
        base = (self.fn, self.precorrected, self.interpret, self.ragged)
        if self.cache_key is not None:
            return ("k",) + base + (self.cache_key,)
        return ("id",) + base + (id(self.plan), id(self.values.vals_p))


@dataclasses.dataclass
class HistQuantileCall:
    """`histogram_quantile(q, sum by (..)(rate(h[..])))` over calls that
    are the `sum` reduce's children, asked of finish_fused_calls as the
    epilogue of their one device call.  query/exprfuse.py recognises the
    tree (`node`: its reduce, where the answer is parked) and, once the
    request's live calls are listed, says where the children stand in that
    list (`calls`, in child order).  Answered: `block` is the finished
    [G, W] ResultBlock (merged keys in execbase._merge_layout's first-seen
    order, the reduce's cache_token) and each child's partial is a
    hist_sum AggPartial WITHOUT `comp` (its sums never left the device).
    Declined (`block` stays None, `hist_device_quantile_declined{reason}`
    booked): the children are finished as any other call."""
    q: float
    calls: List[int] = dataclasses.field(default_factory=list)
    node: object = None
    block: Optional[ResultBlock] = None


def decline_hist_quantile(reason: str) -> None:
    """One histogram quantile that takes the host path, and why."""
    from filodb_tpu.utils.metrics import registry
    registry.counter("hist_device_quantile_declined",
                     reason=reason).increment()


def finish_fused_calls(calls: List[FusedCall],
                       quantiles: Sequence[HistQuantileCall] = ()
                       ) -> List[AggPartial]:
    """Phase-2 of engine.query_range_batch and of a single request's
    hoisted leaves: dispatch every FusedCall.  Compatible calls (one
    working set) merge into one kernel run; a merged set whose combined
    group count would blow the VMEM budget is split back into singleton
    runs instead of degrading to the general path (the per-panel gate in
    _try_fused already passed).  The sets that share a plan object, a
    flavor and a device then ride ONE device program and ONE readback
    (pf.FusedDispatch): a request's shard leaves cost the host one
    dispatch, not one a shard.  `quantiles`: the histogram quantiles to
    finish as the epilogue of their children's device call
    (HistQuantileCall), where those children are that call's sets and
    nothing else is."""
    from filodb_tpu.ops import pallas_fused as pf
    from filodb_tpu.utils.metrics import registry
    out: List[Optional[AggPartial]] = [None] * len(calls)
    # dedup identical panels first — a quantile dashboard's p50/p90/p99
    # queries differ only ABOVE the leaf (histogram_quantile transformer),
    # so their leaf calls are the same work: compute once, share the comp
    prim: Dict[tuple, int] = {}
    alias: Dict[int, int] = {}
    for i, fc in enumerate(calls):
        k = fc.compat_key() + (id(fc.groups.gids_p), fc.op, fc.num_buckets)
        if k in prim:
            alias[i] = prim[k]
        else:
            prim[k] = i
    if alias:
        registry.counter("fused_batch_deduped").increment(len(alias))
    by_key: Dict[tuple, List[int]] = {}
    for i, fc in enumerate(calls):
        if i in alias:
            continue
        by_key.setdefault(fc.compat_key(), []).append(i)
    def slots(i):
        # histogram panels aggregate over (group, bucket) SLOTS
        return len(calls[i].gkeys) * calls[i].num_buckets

    def in_group_mode(i):
        # which panels join the merged group-mode dispatch: min/max
        # run per-series (Gp-independent) and dense count is host
        # math, so neither counts toward the multi-hot group total
        op = calls[i].op
        return op in ("sum", "avg") or (
            op == "count" and (calls[i].ragged or calls[i].phased))

    # the working sets: per compat key the panels one kernel run can hold
    sets: List[List[int]] = []
    for idxs in by_key.values():
        fc0 = calls[idxs[0]]
        while idxs:
            take = idxs
            if len(idxs) > 1:
                Tp = fc0.plan.Tp
                Wp = pf._pad_to(max(fc0.plan.W, 1), pf._LANE)
                kind = (fc0.fn if fc0.fn in pf.OVER_TIME_FNS
                        else "rate_family")
                while len(take) > 1:
                    n_group = sum(1 for i in take if in_group_mode(i))
                    total = sum(slots(i) for i in take
                                if in_group_mode(i))
                    if total == 0 or pf.pick_block(
                            Tp, Wp, pf.pad_group_count(total), kind,
                            fc0.ragged, panels=max(n_group, 1),
                            phased=fc0.phased) is not None:
                        break
                    take = take[:max(1, len(take) // 2)]
            if len(take) > 1:
                # observability of the batching win: actual kernel
                # launches this merged set costs (group-mode + per-series
                # mode), and how many panels shared them
                launches = (any(in_group_mode(i) for i in take)
                            + any(calls[i].op in ("min", "max")
                                  for i in take))
                registry.counter("fused_batch_dispatches") \
                    .increment(launches)
                registry.counter("fused_batch_merged_panels") \
                    .increment(len(take))
            sets.append(take)
            idxs = idxs[len(take):]

    # one device program for the sets that share a plan, a flavor and a
    # device: a request's shard leaves (one plan object since PR 35, the
    # shards' working sets on one chip) are ONE jit call and ONE readback,
    # not one of each a shard.  Sharded DeviceMirrors give each chip its
    # own call.
    by_call: Dict[tuple, List[List[int]]] = {}
    for take in sets:
        fc0 = calls[take[0]]
        by_call.setdefault(
            (id(fc0.plan), fc0.fn, fc0.precorrected, fc0.interpret,
             fc0.ragged, fc0.phased,
             pf._committed_device(fc0.values.vals_p)),
            []).append(take)

    # two-phase execution: phase A dispatches every call's kernel work
    # WITHOUT reading anything back, phase B synchronizes.  With sharded
    # DeviceMirrors a multi-shard query's leaves hold their working sets
    # on different chips — dispatching everything first lets those chips
    # compute concurrently instead of serializing on each call's host
    # readback (the per-device dispatch contract, doc/multichip.md).
    # a histogram quantile rides its children's call where the call's sets
    # are those children, one unshared set each, and nothing else is
    home = {take[0]: takes for takes in by_call.values() for take in takes
            if len(take) == 1 and take[0] not in alias.values()}
    epilogue_of: Dict[int, HistQuantileCall] = {}
    for hq in quantiles:
        takes = home.get(hq.calls[0])
        if takes is None or len(takes) != len(hq.calls) \
                or any(home.get(i) is not takes for i in hq.calls):
            decline_hist_quantile("dispatch")
        elif not _epilogue_fits(calls, hq):
            decline_hist_quantile("size")
        else:
            epilogue_of[id(takes)] = hq
    pending = []
    for (*_, device), takes in by_call.items():
        fc0 = calls[takes[0][0]]
        hq = epilogue_of.get(id(takes))
        with span("leaf.kernel_enqueue") as enqueue:
            disp = pf.FusedDispatch(fc0.plan, fc0.fn, fc0.precorrected,
                                    fc0.interpret, fc0.ragged, device,
                                    fc0.phased)
            finishers = []
            for take in takes:
                fc = calls[take[0]]
                finishers.append(pf.fused_leaf_agg_batch(
                    fc.plan, fc.values,
                    [(calls[i].groups, slots(i), calls[i].op)
                     for i in take], fc.fn,
                    precorrected=fc.precorrected, interpret=fc.interpret,
                    ragged=fc.ragged, num_series=fc.num_series, lazy=True,
                    dispatch=disp))
            merge = None if hq is None else _ask_epilogue(
                calls, hq, takes, disp)
            disp.enqueue()
        pending.append((takes, finishers, disp, enqueue.dur_s, hq, merge))
    from filodb_tpu.utils.devicetelem import telem
    for takes, finishers, disp, disp_s, hq, merge in pending:
        # the np.asarray that blocks on the device and copies out, once
        # for the call, and the panels' presentation over the whole array
        # (a call that ends in a histogram quantile: its [G, W] answer is
        # the request's, and no set's block is read or presented)
        with span("leaf.result_fetch") as fetch:
            disp.fetch()
            comps = [] if hq else [finisher() for finisher in finishers]
        if hq is not None:
            parts, gkeys, token = merge
            for i, part in zip(hq.calls, parts):
                out[i] = part
            hq.block = ResultBlock(gkeys, parts[0].wends, disp.answer,
                                   cache_token=token)
            comps = [[disp.answer]]
        else:
            with span("leaf.present"):
                for take, set_comps in zip(takes, comps):
                    for i, comp in zip(take, set_comps):
                        out[i] = _present(calls[i], comp)
        # kernel enqueue + result readback, attributed to the node that
        # triggered it AND recorded in the per-chip kernel ledger
        # (utils/devicetelem) — record_dispatch feeds the exec tally, so
        # QueryStats.device_seconds is the two spans' seconds
        fc0 = calls[takes[0][0]]
        telem.record_dispatch(
            f"fused_{fc0.fn}", device=disp.device,
            shape=(f"S{sum(calls[t[0]].num_series for t in takes)}"
                   f"xW{len(fc0.wends)}x{sum(map(len, takes))}p"
                   f"x{len(takes)}s" + (":ragged" if fc0.ragged else "")
                   + (":phased" if fc0.phased else "")),
            seconds=disp_s + fetch.dur_s,
            bytes_in=sum(int(getattr(calls[t[0]].values.vals_p, "nbytes", 0))
                         for t in takes),
            bytes_out=sum(int(getattr(c, "nbytes", 0))
                          for set_comps in comps for c in set_comps))
    for i, j in alias.items():
        src = out[j]
        out[i] = dataclasses.replace(src) if src is not None else None
    return out


def _hollow_hist_partial(fc: FusedCall) -> AggPartial:
    """A histogram leaf's partial whose sums stayed on the device (the
    call's epilogue merged them): its keys, windows, scheme and token."""
    return AggPartial("hist_sum", fc.gkeys, fc.wends,
                      bucket_les=fc.bucket_les, cache_token=fc.cache_token)


def _epilogue_fits(calls: List[FusedCall], hq: HistQuantileCall) -> bool:
    """Whether the epilogue's temporaries (a merged [Gm_p, B, Wp] f32
    block for every set) stay under what the call reads anyway, the sets'
    padded values: it is then never what decides whether the program fits
    the device.  The merged groups are at most the children's in all."""
    from filodb_tpu.ops import pallas_fused as pf
    fcs = [calls[i] for i in hq.calls]
    fc0 = fcs[0]
    Wp = pf._pad_to(max(fc0.plan.W, 1), pf._LANE)
    merged = pf.pad_group_count(sum(len(fc.gkeys) for fc in fcs))
    return len(fcs) * merged * fc0.num_buckets * Wp * 4 <= sum(
        int(getattr(fc.values.vals_p, "nbytes", 0)) for fc in fcs)


# the epilogue's small operands on the device, least recently used first
_EPILOGUE_OPERANDS: Dict[tuple, object] = {}
_EPILOGUE_OPERANDS_MAX = 512
_EPILOGUE_OPERANDS_LOCK = threading.Lock()


def _resident(array: np.ndarray, device):
    """`array` on `device`, put once and found again by its CONTENT (its
    bytes, dtype and shape are the key: a merge layout's `inv` and `perm`,
    a `q`, a scheme's `les`, some hundred bytes each), so that a histogram
    request that ends on the device transfers nothing once its grouping
    was seen: an explicit put a call costs the request milliseconds of
    waiting on this backend (PERF.md section 6, PR 41 and PR 51).  Keyed
    by what the array IS, an entry cannot be stale, whatever a token
    leaves unnamed.  A put books `fused_enqueue_uploads_total`; of two
    threads that miss together the first insert stays."""
    import jax

    from filodb_tpu.utils.metrics import registry
    key = (array.tobytes(), array.dtype.str, array.shape, device)
    with _EPILOGUE_OPERANDS_LOCK:
        held = _lru_touch(_EPILOGUE_OPERANDS, key)
    if held is None:
        registry.counter("fused_enqueue_uploads").increment()
        made = jax.device_put(array, device)
        with _EPILOGUE_OPERANDS_LOCK:
            held = _EPILOGUE_OPERANDS.setdefault(key, made)
            while len(_EPILOGUE_OPERANDS) > _EPILOGUE_OPERANDS_MAX:
                del _EPILOGUE_OPERANDS[next(iter(_EPILOGUE_OPERANDS))]
    return held


def _ask_epilogue(calls: List[FusedCall], hq: HistQuantileCall, takes,
                  disp) -> tuple:
    """Hand `disp` the histogram epilogue of `hq` -> (the children's
    partials, which hold no sums; the merged keys; the reduce's token).
    The layout is execbase._merge_layout's, remembered by the children's
    tokens: the merged keys in first-seen order and every child group's
    merged row; a hit hashes no key."""
    with span("leaf.hist_epilogue"):
        parts = [_hollow_hist_partial(calls[i]) for i in hq.calls]
        token = _reduced_token(parts)
        gkeys, index = _merge_layout(parts, token)
        at = {take[0]: k for k, take in enumerate(takes)}
        les = np.asarray(calls[hq.calls[0]].bucket_les, np.float32)
        layout = disp.hist_layout([at[i] for i in hq.calls], index,
                                  len(gkeys), len(les))
        disp.hist_quantile(
            [_resident(a, disp.device)
             for a in (*layout, np.asarray(hq.q, np.float32), les)],
            len(gkeys))
    return parts, gkeys, token


def _present(fc: FusedCall, comp) -> AggPartial:
    if fc.bucket_les is None:
        return AggPartial(fc.op, fc.gkeys, fc.wends, comp=comp,
                          cache_token=fc.cache_token)
    with span("leaf.hist_finish"):
        return _present_hist(fc, comp)


def _present_hist(fc: FusedCall, comp) -> AggPartial:
    # histogram: comp[..., 0] is the per-(group, bucket)-slot sum, masked
    # where the window has no samples — the hist_sum presenter NaNs those
    # windows via the count column anyway, so the mask is invisible
    G, B = len(fc.gkeys), fc.num_buckets
    buckets = np.asarray(comp[..., 0], np.float64) \
        .reshape(G, B, -1).transpose(0, 2, 1)           # [G, W, B]
    if fc.ragged:
        # ragged bucket rows (round-5 item 5): per-(slot, window) counts
        # come back from the kernel's presence output; scrape holes hit
        # whole scrape rows, so every bucket of a series shares one
        # validity pattern — bucket 0's count IS the series count
        cnt = np.asarray(comp[..., 1], np.float64) \
            .reshape(G, B, -1)[:, 0, :]                  # [G, W]
    else:
        gsize = fc.groups.gsize.reshape(G, B)[:, 0]
        cnt = gsize[:, None] * fc.plan.wvalid[None, :].astype(np.float64)
    hist_comp = np.concatenate([buckets, cnt[..., None]], axis=2)
    return AggPartial("hist_sum", fc.gkeys, fc.wends, comp=hist_comp,
                      bucket_les=fc.bucket_les, cache_token=fc.cache_token)
