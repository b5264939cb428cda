"""Strict Prometheus text-exposition validation of /metrics (tier-1).

A malformed exposition must never ship: Prometheus silently drops bad
scrape bodies, which reads as "the server is fine" while every alert
goes dark.  This parses EVERY line of the live registry's output —
including metrics other tests seeded — against the exposition grammar:
metric-name regex, fully-escaped label values, monotone non-decreasing
bucket counts with ascending `le` bounds, and `_sum`/`_count`
consistency per histogram family.
"""
import math
import re

import numpy as np

from filodb_tpu.utils.metrics import Histogram, registry

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_NAME_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
_VALUE_RE = re.compile(
    r"^(-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?|[-+]?Inf|NaN)$")
# one label pair: name="value" with only \\ \" \n escapes inside
_PAIR_RE = re.compile(
    r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\\n]|\\\\|\\"|\\n)*)"(?:,|$)')


def _parse_line(line):
    """(name, labels_dict, value) or raise AssertionError."""
    m = re.match(r"^([^{ ]+)(\{(.*)\})? (.+)$", line)
    assert m, f"unparsable exposition line: {line!r}"
    name, _, labels_raw, value = m.groups()
    assert _NAME_RE.match(name), f"bad metric name: {name!r}"
    labels = {}
    if labels_raw:
        pos = 0
        while pos < len(labels_raw):
            pm = _PAIR_RE.match(labels_raw, pos)
            assert pm, (f"bad label syntax at {labels_raw[pos:]!r} "
                        f"in: {line!r}")
            assert _LABEL_NAME_RE.match(pm.group(1))
            labels[pm.group(1)] = pm.group(2)
            pos = pm.end()
    assert _VALUE_RE.match(value), f"bad sample value {value!r} in {line!r}"
    return name, labels, float(value.replace("Inf", "inf")
                               .replace("NaN", "nan"))


def _strict_parse(text):
    """Parse a full exposition body; returns {(name, frozen_labels): value}
    and the per-histogram family structures for consistency checks."""
    samples = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, labels, value = _parse_line(line)
        key = (name, tuple(sorted(labels.items())))
        assert key not in samples, f"duplicate sample: {key}"
        samples[key] = value
    return samples


def _histogram_families(samples):
    """{(base, labels-without-le): {"buckets": [(le, v)], "sum", "count"}}"""
    fams = {}
    for (name, labels), value in samples.items():
        if name.endswith("_bucket"):
            base = name[:-len("_bucket")]
            lab = dict(labels)
            le = lab.pop("le")
            fam = fams.setdefault((base, tuple(sorted(lab.items()))), {})
            fam.setdefault("buckets", []).append((le, value))
        elif name.endswith("_sum") and (name[:-4], labels) not in samples:
            # a histogram's _sum (counters end _total, gauges are bare)
            fams.setdefault((name[:-4], labels), {})["sum"] = value
        elif name.endswith("_count"):
            fams.setdefault((name[:-6], labels), {})["count"] = value
    return {k: v for k, v in fams.items() if "buckets" in v}


def test_metrics_exposition_is_strictly_parseable():
    # seed nasty label values: the escaping satellite's regression net
    registry.counter("expo_strict_ops",
                     path='a"b\\c\nd', ok="yes").increment(3)
    registry.gauge("expo_strict_depth", unit="ms").update(-1.5)
    h = registry.histogram("expo_strict_lat", route="/x")
    for v in (0.002, 0.04, 7.0, 1e9):      # incl. overflow bucket
        h.record(v)
    text = registry.expose_prometheus()
    samples = _strict_parse(text)
    # the escaped label round-trips: unescape recovers the original
    esc = [v for (n, labels), v in samples.items()
           if n == "expo_strict_ops_total" and dict(labels).get("ok") == "yes"]
    assert len(esc) == 1
    raw = [dict(labels)["path"] for (n, labels) in samples
           if n == "expo_strict_ops_total"][0]
    assert raw.replace("\\\\", "\x00").replace('\\"', '"') \
        .replace("\\n", "\n").replace("\x00", "\\") == 'a"b\\c\nd'

    fams = _histogram_families(samples)
    assert ("expo_strict_lat", (("route", "/x"),)) in fams
    for (base, labels), fam in fams.items():
        where = f"{base}{dict(labels)}"
        assert "sum" in fam, f"{where}: missing _sum"
        assert "count" in fam, f"{where}: missing _count"
        # le bounds ascending with +Inf last; cumulative counts monotone
        les = [le for le, _ in fam["buckets"]]
        assert les.count("+Inf") == 1 and les[-1] == "+Inf", where
        bounds = [float(le) for le in les[:-1]]
        assert bounds == sorted(bounds), f"{where}: le not ascending"
        counts = [v for _, v in fam["buckets"]]
        assert all(b >= a for a, b in zip(counts, counts[1:])), \
            f"{where}: bucket counts not monotone"
        assert counts[-1] == fam["count"], \
            f"{where}: +Inf bucket != _count"
        assert math.isfinite(fam["sum"]), where


def test_exposition_survives_concurrent_histogram_writes():
    """The expose-vs-record race (satellite 1): a scrape formatting a
    histogram mid-record must never emit a cumulative bucket count above
    its _count.  Hammer one histogram from threads while scraping."""
    import threading

    h = registry.histogram("expo_race_lat")
    stop = threading.Event()

    def writer():
        rng = np.random.default_rng(0)
        while not stop.is_set():
            h.record(float(rng.random() * 10))

    threads = [threading.Thread(target=writer, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(50):
            samples = _strict_parse(registry.expose_prometheus())
            fams = _histogram_families(samples)
            fam = fams.get(("expo_race_lat", ()))
            assert fam is not None
            counts = [v for _, v in fam["buckets"]]
            assert all(b >= a for a, b in zip(counts, counts[1:]))
            assert counts[-1] == fam["count"]
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)


# ------------------------------------------------ openmetrics grammar

# OpenMetrics sample line: name{labels} value [# {exemplar} ev ets]
_OM_SAMPLE_RE = re.compile(
    r"^([^{ ]+)(\{(.*?)\})? (-?[0-9.]+(?:[eE][+-]?[0-9]+)?|[-+]?Inf|NaN)"
    r"( # \{trace_id=\"([^\"\\\n]*)\"\} (-?[0-9.]+(?:[eE][+-]?[0-9]+)?)"
    r" ([0-9.]+))?$")
# canonical float per the OpenMetrics ABNF: le values are floats,
# never bare ints
_OM_FLOAT_RE = re.compile(
    r"^(\+Inf|-?[0-9]+\.[0-9]+([eE][+-]?[0-9]+)?|-?[0-9.]+[eE][+-]?[0-9]+)$")


def _strict_parse_openmetrics(text):
    """Parse a full OpenMetrics body; returns (samples, types,
    exemplars) and asserts the grammar: `# TYPE` metadata precedes each
    family's samples, counters expose only `_total` under their family
    name, le values are canonical floats, exactly one `# EOF`
    terminator, nothing after it."""
    assert text.endswith("# EOF\n"), "missing the mandatory # EOF"
    body = text[:-len("# EOF\n")]
    assert "# EOF" not in body, "interior # EOF"
    samples, types, exemplars = {}, {}, {}
    for line in body.splitlines():
        assert line, "blank lines are not OpenMetrics"
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            fam, _, kind = rest.partition(" ")
            assert _NAME_RE.match(fam), fam
            assert kind in ("counter", "gauge", "histogram"), line
            assert fam not in types, f"duplicate TYPE for {fam}"
            types[fam] = kind
            continue
        assert not line.startswith("#"), f"unknown comment: {line!r}"
        m = _OM_SAMPLE_RE.match(line)
        assert m, f"unparsable openmetrics line: {line!r}"
        name, _, labels_raw, value = m.group(1, 2, 3, 4)
        assert _NAME_RE.match(name), name
        labels = {}
        if labels_raw:
            pos = 0
            while pos < len(labels_raw):
                pm = _PAIR_RE.match(labels_raw, pos)
                assert pm, f"bad label syntax in {line!r}"
                labels[pm.group(1)] = pm.group(2)
                pos = pm.end()
        # metadata/sample-name contract: the sample belongs to a typed
        # family, under the kind's allowed suffixes
        fam = next((f for f in (name, name.rsplit("_", 1)[0])
                    if f in types), None)
        if name.endswith("_bucket"):
            fam = name[:-len("_bucket")]
        assert fam in types, f"sample {name!r} precedes its # TYPE"
        kind = types[fam]
        if kind == "counter":
            assert name == fam + "_total", \
                f"counter family {fam} exposes {name!r}"
        elif kind == "gauge":
            assert name == fam, f"gauge family {fam} exposes {name!r}"
        else:
            assert name in (fam + "_bucket", fam + "_sum",
                            fam + "_count"), \
                f"histogram family {fam} exposes {name!r}"
        if "le" in labels:
            assert _OM_FLOAT_RE.match(labels["le"]), \
                f"le not a canonical float: {labels['le']!r}"
        key = (name, tuple(sorted(labels.items())))
        assert key not in samples, f"duplicate sample: {key}"
        samples[key] = float(value.replace("Inf", "inf")
                             .replace("NaN", "nan"))
        if m.group(5):
            # exemplar only legal on histogram buckets; value/ts parse
            assert name.endswith("_bucket"), line
            exemplars[key] = (m.group(6), float(m.group(7)),
                              float(m.group(8)))
    return samples, types, exemplars


def test_openmetrics_exposition_strictly_parseable_with_exemplars():
    registry.counter("om_strict_ops", path='a"b\\c\nd').increment(2)
    registry.gauge("om_strict_depth").update(-2.5)
    h = registry.histogram("om_strict_lat", route="/om")
    h.record(0.003, exemplar="0123456789abcdef0123456789abcdef")
    h.record(42.0, exemplar="feedfacefeedfacefeedfacefeedface")
    text = registry.expose_openmetrics()
    samples, types, exemplars = _strict_parse_openmetrics(text)
    assert types["om_strict_ops"] == "counter"
    assert types["om_strict_depth"] == "gauge"
    assert types["om_strict_lat"] == "histogram"
    assert ("om_strict_ops_total", (("path", 'a\\"b\\\\c\\nd'),)) \
        in samples
    # the seeded exemplars ride their buckets
    got = {tid for (name, _), (tid, _v, _t) in exemplars.items()
           if name == "om_strict_lat_bucket"}
    assert {"0123456789abcdef0123456789abcdef",
            "feedfacefeedfacefeedfacefeedface"} <= got
    # exemplar values sit within their bucket's bound
    for (name, labels), (_tid, ev, ets) in exemplars.items():
        le = dict(labels).get("le")
        if le and le != "+Inf":
            assert ev <= float(le) + 1e-9, (labels, ev)
        assert ets > 1e9, "exemplar timestamp is unix seconds"
    # histogram family consistency holds in this grammar too
    fams = _histogram_families(samples)
    fam = fams[("om_strict_lat", (("route", "/om"),))]
    counts = [v for _, v in fam["buckets"]]
    assert all(b >= a for a, b in zip(counts, counts[1:]))
    assert counts[-1] == fam["count"] == 2


def test_openmetrics_survives_concurrent_scrapes():
    """The same expose-vs-record hammer as the Prometheus gate, on the
    OpenMetrics grammar — including exemplar writes racing the scrape."""
    import threading

    h = registry.histogram("om_race_lat")
    stop = threading.Event()

    def writer():
        rng = np.random.default_rng(1)
        i = 0
        while not stop.is_set():
            h.record(float(rng.random() * 10),
                     exemplar=f"{i:032x}")
            i += 1

    threads = [threading.Thread(target=writer, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(25):
            samples, _types, _ex = _strict_parse_openmetrics(
                registry.expose_openmetrics())
            fams = _histogram_families(samples)
            fam = fams.get(("om_race_lat", ()))
            assert fam is not None
            counts = [v for _, v in fam["buckets"]]
            assert all(b >= a for a, b in zip(counts, counts[1:]))
            assert counts[-1] == fam["count"]
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)


def test_plain_exposition_unchanged_by_exemplars():
    """Exemplar-carrying histograms must leave the legacy format
    byte-free of metadata/exemplar syntax (the no-regression gate)."""
    h = registry.histogram("om_plain_lat")
    h.record(0.5, exemplar="aa" * 16)
    text = registry.expose_prometheus()
    assert "# " not in text and "# EOF" not in text
    # and still strictly parses under the legacy grammar
    _strict_parse(text)


def test_exemplars_toggle_off_drops_them():
    from filodb_tpu.utils.metrics import set_exemplars_enabled
    h = registry.histogram("om_toggle_lat")
    try:
        set_exemplars_enabled(False)
        h.record(0.1, exemplar="bb" * 16)
        assert not h.exemplars
    finally:
        set_exemplars_enabled(True)
    h.record(0.1, exemplar="cc" * 16)
    assert h.exemplars


def test_percentile_interpolates_and_estimates_overflow():
    h = Histogram(bounds=(1.0, 10.0))
    for _ in range(99):
        h.record(5.0)
    h.record(752.0)                      # the round-5 soak's outlier shape
    # p50 interpolated inside (1, 10], not snapped to 10
    assert 1.0 < h.percentile(0.5) < 10.0
    # p100 reaches toward the observed max instead of capping at 10
    assert h.percentile(1.0) == 752.0
    # two histograms equal except their overflow magnitude now DIFFER
    h2 = Histogram(bounds=(1.0, 10.0))
    for _ in range(99):
        h2.record(5.0)
    h2.record(11.0)
    assert h.percentile(1.0) > h2.percentile(1.0)
