"""Metrics + span tracing — the Kamon analogue.

ref: the reference threads Kamon counters/gauges/histograms through every
subsystem (TimeSeriesShardStats TimeSeriesShard.scala:41-134, MemoryStats
BlockManager.scala:91-106, per-query spans exec/ExecPlan.scala:102-131)
and exposes them via reporters — a Prometheus endpoint plus log reporters
(coordinator/.../KamonLogger.scala:16-40, README:812-819).

Here: a process-wide registry of tagged counters/gauges/histograms with
Prometheus text exposition (served at /metrics by the HTTP layer), and a
`span()` context manager that records one node of a request's span tree
(ids, parent, monotonic start and duration) into the trace collector, books
its self time into per-span counters and mirrors itself into the JAX
profiler's trace.  Everything is thread-safe and allocation-light — metric
lookups are dict hits on interned (name, tags) keys.
"""
from __future__ import annotations

import bisect
import itertools
import logging
import random
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

TagTuple = Tuple[Tuple[str, str], ...]


def _tags_key(tags: Dict[str, str]) -> TagTuple:
    return tuple(sorted(tags.items()))


class Counter:
    __slots__ = ("value", "_lock")

    def __init__(self):
        self.value = 0.0
        self._lock = threading.Lock()

    def increment(self, by: float = 1.0) -> None:
        with self._lock:
            self.value += by


class Gauge:
    __slots__ = ("value", "_lock")

    def __init__(self):
        self.value = 0.0
        self._lock = threading.Lock()

    def update(self, v: float) -> None:
        with self._lock:
            self.value = v


# log2-ish bucket boundaries, milliseconds-friendly
_DEFAULT_BOUNDS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 10, 50, 100,
                   500, 1000, 5000, 10000, 60000)

# process-wide exemplar kill switch (config `exemplars_enabled`): when
# off, Histogram.record drops the exemplar argument on the floor so the
# per-record cost is identical to the pre-exemplar code path
EXEMPLARS_ENABLED = True


def set_exemplars_enabled(flag: bool) -> None:
    global EXEMPLARS_ENABLED
    EXEMPLARS_ENABLED = bool(flag)


class Histogram:
    __slots__ = ("bounds", "counts", "sum", "count", "max", "exemplars",
                 "_lock")

    def __init__(self, bounds: Sequence[float] = _DEFAULT_BOUNDS):
        self.bounds = tuple(bounds)
        self.counts = [0] * (len(self.bounds) + 1)
        self.sum = 0.0
        self.count = 0
        # largest value ever recorded: bounds the overflow-bucket
        # percentile estimate (a 752 s p99 and a 5.1 s p99 both land in
        # the +Inf bucket; without the max they'd report identically)
        self.max = 0.0
        # bucket index -> (trace_id, value, unix_ts): the most recent
        # exemplar per bucket (the OpenMetrics bridge from a latency
        # histogram to the exact trace that caused it).  Lazily created —
        # histograms that never see an exemplar pay nothing.
        self.exemplars: Optional[Dict[int, Tuple[str, float, float]]] = None
        self._lock = threading.Lock()

    def record(self, v: float, exemplar: Optional[str] = None) -> None:
        """Record one observation.  `exemplar` is an optional trace id
        attached to the containing bucket (latest wins), emitted by the
        OpenMetrics exposition as `# {trace_id="..."} value ts` so an
        operator can jump from a latency spike straight to the trace."""
        i = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self.counts[i] += 1
            self.sum += v
            self.count += 1
            if v > self.max:
                self.max = v
            if exemplar and EXEMPLARS_ENABLED:
                if self.exemplars is None:
                    self.exemplars = {}
                self.exemplars[i] = (str(exemplar), float(v), time.time())

    # Prometheus-client parity name for the same operation
    observe = record

    def percentile(self, q: float) -> float:
        """Approximate percentile, linearly interpolated within the
        containing bucket (Prometheus histogram_quantile semantics)
        instead of reporting the bucket's upper bound.  The overflow
        (+Inf) bucket interpolates between the last finite bound and the
        maximum value observed — an explicit estimate rather than the
        old behavior of capping at the top bound."""
        with self._lock:
            if self.count == 0:
                return 0.0
            target = q * self.count
            acc = 0
            for i, c in enumerate(self.counts):
                if c == 0:
                    continue
                if acc + c >= target:
                    lo = self.bounds[i - 1] if i > 0 else 0.0
                    hi = self.bounds[i] if i < len(self.bounds) \
                        else max(self.max, self.bounds[-1])
                    frac = (target - acc) / c
                    return lo + frac * (hi - lo)
                acc += c
            return max(self.max, self.bounds[-1])


def _esc_label(v: str) -> str:
    # the exposition-format label escapes: backslash, quote, newline
    # (shared by both exposition grammars — one home, no drift)
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _fmt_tags(tags: TagTuple, extra: str = "") -> str:
    items = [f'{k}="{_esc_label(v)}"' for k, v in tags]
    if extra:
        items.append(extra)
    return "{" + ",".join(items) + "}" if items else ""


class MetricsRegistry:
    """Process-wide named+tagged metrics (ref: Kamon.counter/gauge/histogram)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[Tuple[str, TagTuple], Counter] = {}
        self._gauges: Dict[Tuple[str, TagTuple], Gauge] = {}
        self._hists: Dict[Tuple[str, TagTuple], Histogram] = {}

    def counter(self, name: str, **tags) -> Counter:
        key = (name, _tags_key(tags))
        c = self._counters.get(key)
        if c is None:
            with self._lock:
                c = self._counters.setdefault(key, Counter())
        return c

    def gauge(self, name: str, **tags) -> Gauge:
        key = (name, _tags_key(tags))
        g = self._gauges.get(key)
        if g is None:
            with self._lock:
                g = self._gauges.setdefault(key, Gauge())
        return g

    def histogram(self, name: str, bounds: Optional[Sequence[float]] = None,
                  **tags) -> Histogram:
        """`bounds` applies on FIRST creation of a (name, tags) series
        only (later callers get the existing histogram unchanged) — the
        default log2-ish bounds suit millisecond latencies; seconds-scale
        series (e.g. the ruler's group-eval durations) pass their own."""
        key = (name, _tags_key(tags))
        h = self._hists.get(key)
        if h is None:
            with self._lock:
                h = self._hists.setdefault(
                    key, Histogram(bounds) if bounds else Histogram())
        return h

    def clear(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
        _span_sites.clear()             # cached handles point at the old

    # ----------------------------------------------------- sample snapshot

    def snapshot_samples(self):
        """Every metric as (series_name, tag_tuple, value) with the
        Prometheus exposition naming — counters as `name_total`,
        histograms as cumulative `name_bucket{le=...}` + `name_sum` +
        `name_count`.  The self-scrape loop (utils/selfmon.py) ingests
        exactly this set, so PromQL written against a real /metrics
        scrape works unchanged against the self-scraped series."""
        with self._lock:
            counters = list(self._counters.items())
            gauges = list(self._gauges.items())
            hists = list(self._hists.items())
        out = []
        for (name, tags), c in counters:
            out.append((f"{name}_total", tags, c.value))
        for (name, tags), g in gauges:
            out.append((name, tags, g.value))
        for (name, tags), h in hists:
            with h._lock:                  # torn-read guard, as exposition
                counts = list(h.counts)
                h_sum, h_count = h.sum, h.count
            acc = 0
            for i, b in enumerate(h.bounds):
                acc += counts[i]
                out.append((f"{name}_bucket",
                            tags + (("le", "%g" % b),), acc))
            out.append((f"{name}_bucket", tags + (("le", "+Inf"),),
                        h_count))
            out.append((f"{name}_sum", tags, h_sum))
            out.append((f"{name}_count", tags, h_count))
        return out

    # -------------------------------------------------- prometheus format

    def expose_prometheus(self) -> str:
        """Prometheus text exposition of the framework's own metrics
        (ref: Kamon prometheus reporter, README:812-819)."""
        out: List[str] = []
        fmt_tags = _fmt_tags

        # snapshot under the lock: concurrent first-seen metric creation must
        # not blow up a scrape mid-iteration
        with self._lock:
            counters = list(self._counters.items())
            gauges = list(self._gauges.items())
            hists = list(self._hists.items())
        for (name, tags), c in sorted(counters):
            out.append(f"{name}_total{fmt_tags(tags)} {c.value:g}")
        for (name, tags), g in sorted(gauges):
            out.append(f"{name}{fmt_tags(tags)} {g.value:g}")
        for (name, tags), h in sorted(hists):
            # per-histogram snapshot under ITS lock: counts/sum/count
            # mutate together in record(), and reading them lock-free
            # while formatting could emit a bucket total above _count
            # (sum updated, count not yet) — a torn exposition
            with h._lock:
                counts = list(h.counts)
                h_sum, h_count = h.sum, h.count
            acc = 0
            for i, b in enumerate(h.bounds):
                acc += counts[i]
                le_tag = 'le="%g"' % b
                out.append(f"{name}_bucket{fmt_tags(tags, le_tag)} "
                           f"{acc}")
            inf_tag = 'le="+Inf"'
            out.append(f"{name}_bucket{fmt_tags(tags, inf_tag)} "
                       f"{h_count}")
            out.append(f"{name}_sum{fmt_tags(tags)} {h_sum:g}")
            out.append(f"{name}_count{fmt_tags(tags)} {h_count}")
        return "\n".join(out) + "\n"

    # -------------------------------------------------- openmetrics format

    def expose_openmetrics(self) -> str:
        """OpenMetrics 1.0 text exposition (`/metrics?format=openmetrics`):
        `# TYPE` metadata per family, canonical-float `le` values,
        counter samples under their `_total` name, per-bucket exemplars
        (`# {trace_id="..."} value ts` — the standard bridge from a
        latency histogram to the exact trace that caused it), and the
        mandatory `# EOF` terminator.  The plain Prometheus format
        (expose_prometheus) is untouched — scrapers negotiate via the
        query param, and the legacy output stays byte-identical."""
        out: List[str] = []
        fmt_tags = _fmt_tags

        def om_float(b: float) -> str:
            # canonical float form: OpenMetrics `le` values are floats,
            # never bare ints ("1.0", not "1")
            s = "%g" % b
            return s if ("." in s or "e" in s or "inf" in s) else s + ".0"

        with self._lock:
            counters = list(self._counters.items())
            gauges = list(self._gauges.items())
            hists = list(self._hists.items())

        def grouped(items):
            fams: Dict[str, list] = {}
            for (name, tags), m in sorted(items):
                fams.setdefault(name, []).append((tags, m))
            return fams

        for name, series in grouped(counters).items():
            out.append(f"# TYPE {name} counter")
            for tags, c in series:
                out.append(f"{name}_total{fmt_tags(tags)} {c.value:g}")
        for name, series in grouped(gauges).items():
            out.append(f"# TYPE {name} gauge")
            for tags, g in series:
                out.append(f"{name}{fmt_tags(tags)} {g.value:g}")
        for name, series in grouped(hists).items():
            out.append(f"# TYPE {name} histogram")
            for tags, h in series:
                with h._lock:
                    counts = list(h.counts)
                    h_sum, h_count = h.sum, h.count
                    ex = dict(h.exemplars) if h.exemplars else {}
                acc = 0
                for i, b in enumerate(h.bounds):
                    acc += counts[i]
                    le_tag = 'le="%s"' % om_float(b)
                    line = (f"{name}_bucket{fmt_tags(tags, le_tag)} "
                            f"{acc}")
                    out.append(line + _om_exemplar(ex.get(i)))
                inf_tag = 'le="+Inf"'
                line = (f"{name}_bucket{fmt_tags(tags, inf_tag)} "
                        f"{h_count}")
                out.append(line + _om_exemplar(ex.get(len(h.bounds))))
                out.append(f"{name}_sum{fmt_tags(tags)} {h_sum:g}")
                out.append(f"{name}_count{fmt_tags(tags)} {h_count}")
        out.append("# EOF")
        return "\n".join(out) + "\n"


def _om_exemplar(ex) -> str:
    """One bucket's exemplar suffix, or "" (OpenMetrics exemplar syntax:
    ` # {trace_id="..."} value timestamp`)."""
    if not ex:
        return ""
    tid, v, ts = ex
    tid = str(tid).replace("\\", "").replace('"', "").replace("\n", "")
    return f' # {{trace_id="{tid}"}} {v:g} {ts:.3f}'


registry = MetricsRegistry()


# ----------------------------------------------------- exec resource tally

class _ExecTally(threading.local):
    """Per-thread accumulators attributing device time, host→device
    transfer, and mirror-refresh events to the exec node that triggered
    them (the Kamon-context analogue for QueryStats attribution; PR 3).

    Protocol: ExecPlan.execute_internal snapshots + zeroes the fields on
    entry, folds whatever its own work accumulated into its QueryStats on
    exit, then restores the outer values — so a parent node never
    re-claims what a child already attributed (child contributions arrive
    via QueryStats.merge instead).  `child_wall` carries nested nodes'
    wall seconds up, letting each node compute its EXCLUSIVE cpu time."""

    def __init__(self):
        self.child_wall = 0.0
        self.device_s = 0.0
        self.transfer_s = 0.0
        self.transfer_bytes = 0
        self.mirror_full = 0
        self.mirror_incremental = 0
        # (device, kernel) -> [seconds, count]: the per-chip, per-kernel
        # split of device_s (PR 18 device telemetry) — same snapshot /
        # restore protocol, folded into QueryStats.device_calls
        self.device_calls: Dict[Tuple[str, str], List[float]] = {}

    def snapshot(self):
        s = (self.child_wall, self.device_s, self.transfer_s,
             self.transfer_bytes, self.mirror_full, self.mirror_incremental,
             self.device_calls)
        self.child_wall = 0.0
        self.device_s = 0.0
        self.transfer_s = 0.0
        self.transfer_bytes = 0
        self.mirror_full = 0
        self.mirror_incremental = 0
        self.device_calls = {}
        return s

    def restore(self, snap, total_wall: float) -> None:
        (self.child_wall, self.device_s, self.transfer_s,
         self.transfer_bytes, self.mirror_full,
         self.mirror_incremental, self.device_calls) = snap
        self.child_wall += total_wall


exec_tally = _ExecTally()


def note_device_call(device: str, kernel: str, seconds: float) -> None:
    """Attribute one device kernel dispatch to the current node, split by
    (device, kernel) — device_s accumulates the same seconds, so
    QueryStats.device_seconds and the per-device breakdown reconcile by
    construction."""
    exec_tally.device_s += seconds
    cell = exec_tally.device_calls.get((device, kernel))
    if cell is None:
        exec_tally.device_calls[(device, kernel)] = [seconds, 1]
    else:
        cell[0] += seconds
        cell[1] += 1


def note_transfer(nbytes: int, seconds: float) -> None:
    """Attribute a host→device (or wire) transfer to the current node."""
    exec_tally.transfer_bytes += int(nbytes)
    exec_tally.transfer_s += seconds


def note_mirror_refresh(kind: str) -> None:
    """kind: 'full' | 'incremental' — query-path mirror uploads, so
    QueryStats can say WHICH query paid for a rebuild."""
    if kind == "full":
        exec_tally.mirror_full += 1
    else:
        exec_tally.mirror_incremental += 1


# ------------------------------------------------------------------ spans

_active = threading.local()

# process-wide span kill switch (for measuring the span pipeline's own
# overhead by toggling it off; only tests flip it now: ROADMAP C10).  Stats tallies
# are NOT affected — only counter/histogram/trace work is skipped.
SPANS_ENABLED = True


def set_spans_enabled(flag: bool) -> None:
    global SPANS_ENABLED
    SPANS_ENABLED = bool(flag)

# node identity stamped on every collected span event (set by nodeapp /
# standalone at startup) so a stitched cross-node trace shows placement
NODE_NAME = ""


class TraceCollector:
    """Bounded per-trace span-event buffer — the Zipkin-reporter analogue
    of the reference's Kamon span pipeline (ref: ExecPlan.scala:102-131
    Kamon spans around doExecute; KamonLogger.scala:16-40).  Remote nodes
    ship their events back with the query reply (parallel/transport), so
    `trace(tid)` returns ONE stitched cross-node trace."""

    def __init__(self, max_traces: int = 256, max_events: int = 512):
        import collections as _collections
        self.max_traces = max_traces
        self.max_events = max_events
        self._traces: Dict[str, List[dict]] = {}
        self._order: List[str] = []
        # trace -> origin tag (query | rule_eval | remote_write), set by
        # the doors; /admin/traces?origin= filters on it
        self._origins: Dict[str, str] = {}
        # trace -> final verdict (completed | killed | deadline | error),
        # set by the query frontend at completion; /admin/traces/<id>
        # carries it so "how did this query end" needs no slowlog join
        self._verdicts: Dict[str, str] = {}
        # trace -> (unix ns, monotonic ns) read together, once, when the
        # trace is first seen: the one wall-clock anchor that turns its
        # events' monotonic `start_ns` into unix time for readers
        self._anchors: Dict[str, Tuple[int, int]] = {}
        # ids evicted from the bounded ring: /traces/{id} answers "410
        # gone" (the trace existed, the ring recycled it) instead of a
        # 404 indistinguishable from a typo.  Bounded itself so hostile
        # churn cannot grow it without bound.
        self._evicted = _collections.deque(maxlen=max(4 * max_traces, 64))
        self._evicted_set: set = set()
        self._lock = threading.Lock()
        # push-export hooks (utils/traceexport.TraceExporter): called
        # outside the lock with every recorded event; must not block
        self._sinks: List = []

    def add_sink(self, sink) -> None:
        self._sinks.append(sink)

    def remove_sink(self, sink) -> None:
        if sink in self._sinks:
            self._sinks.remove(sink)

    def record(self, trace_id: str, event: Optional[dict]) -> None:
        """Record one span event (event=None registers the trace id in
        the ring without an event — the doors tag origins before their
        first span exits)."""
        evicted = 0
        with self._lock:
            evs = self._traces.get(trace_id)
            if evs is None:
                evs = self._traces[trace_id] = []
                self._anchors[trace_id] = (time.time_ns(),
                                           time.perf_counter_ns())
                self._order.append(trace_id)
                while len(self._order) > self.max_traces:
                    old = self._order.pop(0)
                    self._traces.pop(old, None)
                    self._origins.pop(old, None)
                    self._verdicts.pop(old, None)
                    self._anchors.pop(old, None)
                    if old in self._evicted_set:
                        # a re-registered-then-re-evicted id: refresh
                        # its position instead of duplicating it (a
                        # duplicate would let the rotation discard the
                        # set entry while a deque copy remains, turning
                        # a promised 410 into a 404).  O(n) on a small
                        # bounded deque, and only on this rare path.
                        try:
                            self._evicted.remove(old)
                        except ValueError:
                            pass
                    elif len(self._evicted) == self._evicted.maxlen:
                        self._evicted_set.discard(self._evicted[0])
                    self._evicted.append(old)
                    self._evicted_set.add(old)
                    evicted += 1
            if event is not None and len(evs) < self.max_events:
                if type(event) is dict and "start_unix_ns" in event:
                    # shipped here by another node (take()): onto this
                    # node's monotonic clock, through the trace's anchor
                    wall, mono = self._anchors[trace_id]
                    event = dict(event)
                    event["start_ns"] = \
                        event.pop("start_unix_ns") - wall + mono
                evs.append(event)
        if evicted:
            registry.counter("trace_evictions").increment(evicted)
        if event is not None:
            for sink in self._sinks:
                sink(trace_id, _as_event(event))
        return evs

    def events_of(self, trace_id: str) -> list:
        """The trace's live event list, registered in the ring if new.  A
        `trace_context` fetches it once; the spans inside append to it
        without the lock (list.append is atomic), bounded by max_events."""
        with self._lock:
            evs = self._traces.get(trace_id)
        return evs if evs is not None else self.record(trace_id, None)

    def note_origin(self, trace_id: str, origin: str) -> None:
        """Tag a trace with its door (query | rule_eval | remote_write).
        The doors tag BEFORE their first span exits, so an unknown id is
        registered in the ring (empty event list) rather than dropped —
        the origins map shares the ring's bound either way."""
        if not trace_id or not origin:
            return
        with self._lock:
            if trace_id in self._traces:
                self._origins[trace_id] = origin
                return
        # register through record()'s eviction bookkeeping, then tag
        self.record(trace_id, None)
        with self._lock:
            if trace_id in self._traces:
                self._origins[trace_id] = origin

    def note_verdict(self, trace_id: str, verdict: str) -> None:
        """Tag a trace with its query's final verdict (completed |
        killed | deadline | error).  Only known ids are tagged — a
        verdict for an evicted trace would re-register it for nothing."""
        if not trace_id or not verdict:
            return
        with self._lock:
            if trace_id in self._traces:
                self._verdicts[trace_id] = verdict

    def verdict(self, trace_id: str) -> str:
        with self._lock:
            return self._verdicts.get(trace_id, "")

    def was_evicted(self, trace_id: str) -> bool:
        with self._lock:
            return trace_id in self._evicted_set \
                and trace_id not in self._traces

    def trace(self, trace_id: str) -> List[dict]:
        """The trace's events in start order."""
        with self._lock:
            evs = list(self._traces.get(trace_id, ()))
        evs = [_as_event(e) for e in evs]
        evs.sort(key=lambda e: e.get("start_ns", 0))
        return evs

    def anchor(self, trace_id: str) -> Optional[Tuple[int, int]]:
        """(unix ns, monotonic ns) of one instant: an event's unix start
        is `start_ns - monotonic + unix`."""
        with self._lock:
            return self._anchors.get(trace_id)

    def take(self, trace_id: str) -> List[dict]:
        """Drain the trace's events (used by the node query server: each
        dispatch reply carries exactly the events recorded since the last
        one, so the coordinator's merge never duplicates).  Another
        node's monotonic clock means nothing here, so the copies carry
        `start_unix_ns` instead of `start_ns`; record() on the receiving
        node puts them on its own clock."""
        with self._lock:
            evs = self._traces.get(trace_id)
            if not evs:
                return []
            wall, mono = self._anchors[trace_id]
            out = []
            for ev in evs:
                ev = _as_event(ev)
                if "start_ns" in ev:
                    ev = dict(ev)
                    ev["start_unix_ns"] = ev.pop("start_ns") - mono + wall
                out.append(ev)
            evs.clear()
            return out

    def trace_ids(self, origin: str = "", limit: int = 0) -> List[str]:
        """Known ids, oldest first.  `origin` filters to one door's
        traces; `limit` keeps the newest N."""
        with self._lock:
            if origin:
                ids = [t for t in self._order
                       if self._origins.get(t) == origin]
            else:
                ids = list(self._order)
        return ids[-limit:] if limit > 0 else ids


collector = TraceCollector()


# ------------------------------------------------------- W3C traceparent

# the W3C Trace Context header: 00-<32 hex trace id>-<16 hex span id>-<flags>
_TRACEPARENT_RE = None


def parse_traceparent(header: Optional[str]) -> Optional[str]:
    """Extract the 32-hex trace id from a `traceparent` request header
    (W3C Trace Context).  Returns None for missing/malformed headers and
    for the all-zero (invalid) trace id — the caller mints its own."""
    global _TRACEPARENT_RE
    if not header:
        return None
    if _TRACEPARENT_RE is None:
        import re as _re
        _TRACEPARENT_RE = _re.compile(
            r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$")
    m = _TRACEPARENT_RE.match(header.strip().lower())
    if not m or m.group(1) == "ff":
        return None
    tid = m.group(2)
    if tid == "0" * 32 or m.group(3) == "0" * 16:
        return None
    return tid


def make_traceparent(trace_id: str) -> str:
    """Format a trace id as an outgoing `traceparent` header (a fresh
    16-hex span id per call; non-32-hex internal ids are hashed into
    shape, matching the trace-export normalization)."""
    import uuid as _uuid
    tid = str(trace_id).replace("-", "").lower()
    if len(tid) != 32 or any(c not in "0123456789abcdef" for c in tid):
        tid = _uuid.uuid5(_uuid.NAMESPACE_OID, str(trace_id)).hex
    return f"00-{tid}-{_uuid.uuid4().hex[:16]}-01"


def mint_trace_id() -> str:
    """A fresh W3C-shaped (32 lower hex) trace id for a request that
    arrived without one."""
    import uuid as _uuid
    return _uuid.uuid4().hex


class trace_context:
    """Bind a trace id to this thread for the duration; spans entered
    inside feed TraceCollector under it.  Re-entrant (restores the outer
    id), so a node executing a dispatched subtree nests cleanly."""

    def __init__(self, trace_id: str):
        self.trace_id = trace_id

    def __enter__(self):
        self._prev = getattr(_active, "trace_id", None)
        self._prev_events = getattr(_active, "events", None)
        _active.trace_id = self.trace_id
        _active.events = collector.events_of(self.trace_id) \
            if self.trace_id else None
        # an event's `span` path joins the names entered since the
        # innermost trace context (as it always has: the contexts used to
        # be entered at the stack's root), whatever spans now lie above
        self._prev_base = getattr(_active, "path_base", 0)
        _active.path_base = len(getattr(_active, "stack", ()))
        return self

    def __exit__(self, exc_type, exc, tb):
        _active.trace_id = self._prev
        _active.events = self._prev_events
        _active.path_base = self._prev_base
        return False


def current_trace_id():
    return getattr(_active, "trace_id", None)


class _SpanSite:
    """What one span name resolves to, once: its three counter families
    (`span_<name>_self_seconds_total`, `span_<name>_seconds_total`,
    `span_<name>_calls_total`, dots written `_`) and its profiler label.
    One family per span and no `span` label, so that a scrape summed per
    family still tells the spans apart.  Only `_book` writes these
    counters, under its one lock.  A span that exits as its thread's
    outermost gets a fourth, `span_<name>_cpu_seconds_total`, at its first
    such exit (`cpu`): a name that is never a root has no such family.

    A site opened with `hist=True` is one of the older spans whose
    `span_<name>_seconds` histogram (tagged, with trace exemplars) is
    documented: it keeps the histogram, whose `_sum` is its seconds, in
    place of the `_seconds_total` counter.  A name is one site: its
    first opening decides."""
    __slots__ = ("label", "self_c", "calls_c", "dur_c", "hists", "cpu_c")

    def __init__(self, name: str, hist: bool):
        flat = name.replace(".", "_")
        self.label = "filodb:" + name
        self.self_c = registry.counter(f"span_{flat}_self_seconds")
        self.calls_c = registry.counter(f"span_{flat}_calls")
        self.cpu_c = None
        if hist:
            self.dur_c = None
            self.hists = (f"span_{name}_seconds", {})   # tags -> Histogram
        else:
            self.dur_c = registry.counter(f"span_{flat}_seconds")
            self.hists = None

    def hist(self, tags: Dict[str, str]) -> Histogram:
        family, by_tags = self.hists
        key = tuple(tags.items())
        h = by_tags.get(key)
        if h is None:
            h = by_tags[key] = registry.histogram(family, **tags)
        return h

    def cpu(self) -> Counter:
        """`span_<name>_cpu_seconds_total`, made at a root's first exit."""
        c = self.cpu_c
        if c is None:
            flat = self.label[len("filodb:"):].replace(".", "_")
            c = self.cpu_c = registry.counter(f"span_{flat}_cpu_seconds")
        return c


_span_sites: Dict[str, _SpanSite] = {}
# 64-bit span ids: a process-wide counter from a random start (next() on
# it is atomic), so ids of two nodes in one stitched trace do not meet
_span_ids = itertools.count(random.getrandbits(63) | 1)
_trace_annotation = None        # jax.profiler.TraceAnnotation, once found
_book_lock = threading.Lock()
_BOOK_AT = 512                  # exited spans a thread holds unbooked


def _find_trace_annotation():
    """jax.profiler.TraceAnnotation if this process has imported JAX,
    else None: a process without JAX has no profiler session to annotate,
    and this module must not be the one that imports it."""
    global _trace_annotation
    if "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _trace_annotation = TraceAnnotation
    return _trace_annotation


def enter_annotation(name: str):
    """Inside a profiler session an entered `TraceAnnotation`
    `filodb:<name>`, for its caller to exit; else None.  For what is timed
    outside the span tree and must still show on its thread's line (the
    collector's hook, utils/heap.py: it may not open a span, which is not
    safe to re-enter from an arbitrary bytecode boundary)."""
    ann = _trace_annotation or _find_trace_annotation()
    if not SPANS_ENABLED or ann is None or not ann.is_enabled():
        return None
    ann = ann("filodb:" + name)
    ann.__enter__()
    return ann


def _book(done: list) -> None:
    """Book a thread's exited spans, in exit order (children before their
    parents): each adds its duration to its parent's children time, then
    `duration - children` to its site's self seconds; the thread's
    outermost span also books the CPU time it read.  Run when the
    thread's outermost span exits, after the work it timed (behind the
    HTTP door: after the reply is written), in one warm loop; an exit
    itself touches no counter and takes no lock."""
    try:
        with _book_lock:
            for sp in done:
                site, dur = sp._site, sp.dur_ns
                parent = sp._parent
                if parent is not None:
                    parent._children_ns += dur
                else:
                    site.cpu().value += sp.cpu_ns * 1e-9
                site.self_c.value += (dur - sp._children_ns) * 1e-9
                site.calls_c.value += 1
                if site.dur_c is not None:
                    site.dur_c.value += dur * 1e-9
                else:
                    # the trace id doubles as the histogram's exemplar
                    # (histogram spike -> /admin/traces/<id> in one hop)
                    site.hist(sp.tags).record(dur * 1e-9, exemplar=sp._tid)
    finally:
        done.clear()


def _event(path: str, name: str, trace_id: str, span_id: int, parent,
           start_ns: int, dur_ns: int, tags: Dict[str, str]) -> dict:
    """One collector event; `parent` is the enclosing span or None."""
    ev = {"span": path, "name": name, "trace_id": trace_id,
          "span_id": "%016x" % span_id,
          "parent_id": None if parent is None else "%016x" % parent._id,
          "start_ns": start_ns, "dur_ns": dur_ns, "dur_s": dur_ns * 1e-9,
          "node": NODE_NAME}
    if tags:
        ev.update(tags)
    return ev


def _as_event(item) -> dict:
    """A trace's list holds finished spans (made into events only when
    someone reads the trace) and ready-made events (shipped by another
    node, or recorded by hand)."""
    return item if type(item) is dict else item.event()


class span:
    """One node of a request's span tree (ref: Kamon.spanBuilder threaded
    through ExecPlan.execute / startODPSpan).

    Entering pushes a frame on the thread's stack; the frame below it is
    the parent.  Exiting stamps the duration and, under a `trace_context`,
    appends the span to the trace.  When the thread's outermost span
    exits, every span that exited under it is booked (`_book`): its
    duration into its parent's children time, and `duration - children`
    as its SELF time (so the self times of a tree sum to its root's
    duration).  Read back, a span is one event: `span` (the names entered
    since the innermost `trace_context`, joined into a path), `name`,
    `trace_id`, `span_id`, `parent_id`, `start_ns` / `dur_ns` on
    `time.perf_counter_ns()` (CLOCK_MONOTONIC), `dur_s`, `node` and the
    tags.  The collector anchors each trace to the wall clock once.
    Inside a `jax.profiler` session every span is also a
    `TraceAnnotation("filodb:<name>")` on its thread's line of the host
    plane, on the clock of the device's operations; the outermost span
    checks the recorder's flag, and the spans under it do as it did.

    A thread's OUTERMOST span also reads the thread's CPU clock
    (`time.thread_time_ns()`, CLOCK_THREAD_CPUTIME_ID: a request's
    `conn.serve`, an accept, a flush pass; two reads each) and books
    `span_<name>_cpu_seconds_total`: a thread that waits for the
    interpreter lock, the device or a socket is off the CPU, and one that
    runs Python holds the lock, so a root's CPU time is the program's
    measure of the lock-HELD time behind its wall time.  It is an upper
    bound where C code computes, or the kernel works, with the lock
    released (large NumPy loops, system calls), hence `cpu`, not `held`.
    The spans under a root do not read the clock: where it is a system
    call of its own (15 us a read and ticks of 10 ms under the sandbox of
    the benchmark's machine, PERF.md section 6, PR 40) a request's 300
    spans would pay a third of its throughput and measure mostly the
    reads.

    `hist=True` keeps the span's documented `span_<name>_seconds`
    histogram (see _SpanSite).  After exit `dur_ns` holds the duration,
    for callers that report it elsewhere (one clock, not a second pair
    around the same call), and `cpu_ns` a root's CPU time (None on a span
    under one)."""

    __slots__ = ("name", "tags", "dur_ns", "cpu_ns", "_site", "_t0", "_c0",
                 "_children_ns", "_id", "_parent", "_path_root", "_ann",
                 "_tid")

    def __init__(self, name: str, hist: bool = False, **tags: str):
        self.name = name
        self.tags = tags
        site = _span_sites.get(name)
        if site is None:
            site = _span_sites[name] = _SpanSite(name, hist)
        self._site = site
        self.dur_ns = 0
        self.cpu_ns = None
        self._t0 = None

    @property
    def dur_s(self) -> float:
        return self.dur_ns * 1e-9

    def __enter__(self):
        if not SPANS_ENABLED:
            # the clock still runs: QueryStats phases are filled from
            # span durations and must not change with the switch
            self._site = None
            self._t0 = time.perf_counter_ns()
            return self
        local = _active.__dict__
        stack = local.get("stack")
        if stack is None:
            stack = local["stack"] = []
            local["done"] = []
        if stack:
            parent = self._parent = stack[-1]
            annotate = parent._ann is not None
        else:
            self._parent = None
            ann = _trace_annotation or _find_trace_annotation()
            annotate = ann is not None and ann.is_enabled()
        self._path_root = len(stack) <= local.get("path_base", 0)
        self._tid = local.get("trace_id")
        self._children_ns = 0
        self._id = next(_span_ids)      # below 2**64 for any process life
        stack.append(self)
        if annotate:
            ann = self._ann = _trace_annotation(self._site.label)
            ann.__enter__()
        else:
            self._ann = None
        if self._parent is None:
            self._c0 = time.thread_time_ns()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t0 = self._t0
        if t0 is None:
            return False
        self.dur_ns = time.perf_counter_ns() - t0
        if self._site is None:
            return False
        if self._parent is None:
            self.cpu_ns = time.thread_time_ns() - self._c0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        local = _active.__dict__
        stack = local["stack"]
        stack.pop()
        tid = self._tid
        if tid:
            evs = local.get("events")
            if evs is not None and len(evs) < collector.max_events:
                evs.append(self)        # made into an event when read
            for sink in collector._sinks:
                sink(tid, self.event())
        done = local["done"]
        done.append(self)
        if not stack or len(done) >= _BOOK_AT:
            _book(done)
        return False

    @property
    def _path(self) -> str:
        return self.name if self._path_root \
            else self._parent._path + "." + self.name

    def event(self) -> dict:
        # a parent outside the trace (the connection's spans above
        # `http.request`) is no parent in it
        parent = self._parent
        if parent is not None and parent._tid != self._tid:
            parent = None
        return _event(self._path, self.name, self._tid, self._id,
                      parent, self._t0, self.dur_ns, self.tags)


class span_part:
    """A timed part of the span open on this thread that stays INSIDE
    that span's self time: for a span that readers take whole by its
    self time (`leaf.kernel_enqueue`), a nested `span` would move the
    time out of it.  A part books `span_<name>_seconds_total` and
    `span_<name>_calls_total` (no self time: the span that holds it has
    it), joins the trace as a child event of that span, and inside a
    profiler session that the span annotates is a
    `TraceAnnotation("filodb-part:<name>")`, a label that readers of the
    `filodb:` spans pass over.  `dur_ns` as on a span."""

    __slots__ = ("name", "dur_ns", "_t0", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.dur_ns = 0

    def __enter__(self):
        self._ann = None
        if SPANS_ENABLED:
            stack = _active.__dict__.get("stack")
            if stack and stack[-1]._ann is not None:
                ann = self._ann = _trace_annotation(
                    "filodb-part:" + self.name)
                ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur_ns = time.perf_counter_ns() - self._t0
        if not SPANS_ENABLED:
            return False
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        flat = self.name.replace(".", "_")
        registry.counter(f"span_{flat}_seconds").increment(
            self.dur_ns * 1e-9)
        registry.counter(f"span_{flat}_calls").increment()
        tid = current_trace_id()
        if tid:
            record_child_event(tid, self.name, self.dur_ns * 1e-9)
        return False


def record_child_event(trace_id: str, name: str, dur_s: float,
                       **tags) -> None:
    """An event that ENDS now and lasted `dur_s`, as a child of the span
    open on this thread: for a duration that was measured by other spans
    and is reported once more under a documented name (the device
    ledger's `kernel_dispatch`, whose `span` field stays that bare name).
    It books no counter and no self time: the spans that measured it
    already did."""
    stack = getattr(_active, "stack", None)
    dur = int(dur_s * 1e9)
    collector.record(trace_id, _event(
        name, name, trace_id, next(_span_ids), stack[-1] if stack else None,
        time.perf_counter_ns() - dur, dur, tags))


# ----------------------------------------------------- scheduler asserts


class FiloSchedulers:
    """Thread-name assertions on hot entry points (ref:
    core/.../memstore/FiloSchedulers.scala:14-20, gated by
    filodb.scheduler.enable-assertions)."""

    enabled = False
    INGEST = "ingest"
    QUERY = "query"
    FLUSH = "flush"

    @staticmethod
    def assert_thread_name(fragment: str) -> None:
        if not FiloSchedulers.enabled:
            return
        name = threading.current_thread().name
        assert fragment in name, \
            f"expected thread name containing {fragment!r}, got {name!r}"


_degrade_log = logging.getLogger("filodb.fused")
_degrade_last: Dict[str, float] = {}


def log_fused_degradation(where: str, exc: BaseException,
                          min_interval_s: float = 60.0) -> None:
    """The fused fast path (query/leafexec.py) degrades
    silently to the general path on any error; without the exception text
    the operator only sees an error counter climb with nothing to
    diagnose.  Rate-limited so a hot query loop can't flood the log."""
    now = time.monotonic()
    if now - _degrade_last.get(where, -1e9) >= min_interval_s:
        _degrade_last[where] = now
        _degrade_log.warning(
            "%s fused path degraded to general path: %s: %s",
            where, type(exc).__name__, exc)


def log_error_once(where: str, exc: BaseException,
                   min_interval_s: float = 300.0,
                   logger_name: str = "filodb") -> None:
    """Log a swallowed optimization-path exception once per (site, error
    class), rate-limited — the general form of log_fused_degradation for
    paths whose failures otherwise vanish into a bare counter (e.g. the
    device mirror's incremental-refresh fallback).  A new error CLASS at
    the same site always logs immediately, so a regression that changes
    failure mode is visible even inside the rate window.

    Every call — logged or rate-suppressed — also increments
    `suppressed_errors_total{site,class}`, so swallowed
    optimization-path errors are visible at /metrics and alertable via
    the self-scrape loop, not only greppable in logs."""
    registry.counter("suppressed_errors",
                     **{"site": where,
                        "class": type(exc).__name__}).increment()
    key = f"{where}:{type(exc).__name__}"
    now = time.monotonic()
    if now - _degrade_last.get(key, -1e9) >= min_interval_s:
        _degrade_last[key] = now
        logging.getLogger(logger_name).warning(
            "%s suppressed (optimization path fell back): %s: %s",
            where, type(exc).__name__, exc)
