"""A range query's matrix leaves the door as bytes (ISSUE 45): the rows are
rendered once as the response's own JSON text, a row without an infinity in
one formatting call over its block's template; that text rides beside the
envelope (`_rendered`) and `http/server.py` closes the encoded envelope with
it without walking it again.

The reference here is the parent's presenter written as the loop it was:
one `_value_text` and one `[t, text]` a point.  Every case's body, parsed,
must be that object, value strings and all: what the engine renders, what a
route returns and the server encodes, and what a live `FiloServer` sends."""
import json
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.http.routes import _present_matrix
from filodb_tpu.http.server import _encode_json
from filodb_tpu.ingest.generator import gauge_batch
from filodb_tpu.query.engine import QueryEngine
from filodb_tpu.query.rangevector import (QueryResult, RangeVectorKey,
                                          ResultBlock)
from filodb_tpu.standalone import DatasetConfig, FiloServer
from filodb_tpu.utils.metrics import registry

from histrig import bench_json, bench_module

START = 1_600_000_020_000
START_S = START // 1000
GRID = {"start": str(START_S + 600), "end": str(START_S + 4200), "step": "60"}
PATH = "/promql/prometheus/api/v1/query_range"


# ---- the reference: the parent's format, a point at a time

def _value_text(v):
    if v in (np.inf, -np.inf):
        return "+Inf" if v > 0 else "-Inf"
    return "{:.17g}".format(v)


def _parent_rows(result):
    out = []
    for b in result.blocks:
        vals = np.asarray(b.values)
        if vals.ndim != 2:
            continue
        for key, row in zip(b.keys, vals.tolist()):
            points = [[int(t) / 1000.0, _value_text(v)]
                      for t, v in zip(b.wends, row) if v == v]
            if points:
                labels = dict(key.labels_dict)
                name = labels.pop("_metric_", None)
                if name:
                    labels["__name__"] = name
                out.append({"metric": labels, "values": points})
    return out


def _parent_payload(result):
    payload = {"status": "success",
               "data": {"resultType": "matrix",
                        "result": _parent_rows(result)}}
    if result.partial or result.stats.warnings:
        payload["warnings"] = list(result.stats.warnings) or [
            "partial results: one or more shards were unreachable"]
    if result.partial:
        payload["partial"] = True
    return payload


# ---- results built by hand

WENDS = (1_600_000_007 + np.arange(721) * 30) * 1000


def _keys(n, **more):
    return [RangeVectorKey.make({"_ns_": f'App-{i}', "q": 'a"b\\c %s é',
                                 **more}) for i in range(n)]


def _values(rows, windows=721, seed=7):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((rows, windows)) \
        * 10.0 ** rng.integers(-12, 12, (rows, 1))


def _whole():
    vals = _values(3)
    vals[0, :6] = (0.1, -0.0, 5e-324, 1.7976931348623157e308, 1e22, 123456.0)
    return QueryResult([ResultBlock(_keys(3, _metric_="m"), WENDS, vals)])


def _holes():
    vals = _values(3)
    vals[1, np.random.default_rng(1).random(721) < 0.3] = np.nan
    vals[2, 1:] = np.nan
    return QueryResult([ResultBlock(_keys(3), WENDS, vals)])


def _nan_only():
    vals = _values(3)
    vals[1] = np.nan
    return QueryResult([ResultBlock(_keys(3), WENDS, vals)])


def _infinities():
    vals = _values(3)
    vals[1, ::50] = np.inf
    vals[1, 7::90] = -np.inf
    vals[1, 3::40] = np.nan
    return QueryResult([ResultBlock(_keys(3), WENDS, vals)])


def _empty():
    return QueryResult([])


def _histogram():
    hist = ResultBlock(_keys(2), WENDS[:5], np.ones((2, 5, 4)),
                       bucket_les=np.array([1.0, 2.0, 4.0, np.inf]))
    return QueryResult([hist, ResultBlock(_keys(1), WENDS[:5],
                                          _values(1, 5))])


def _no_windows():
    return QueryResult([ResultBlock(_keys(2), WENDS[:0], np.ones((2, 0))),
                        ResultBlock(_keys(1), WENDS[:1], np.ones((1, 1)))])


def _two_blocks():
    return QueryResult([
        ResultBlock(_keys(2), WENDS[:61], _values(2, 61)),
        ResultBlock(_keys(3, zone="b"), WENDS[100:230:2] + 500,
                    _values(3, 65, seed=8).astype(np.float32))])


def _wide():
    return QueryResult([ResultBlock(_keys(20), WENDS, _values(20))])


def _partial():
    res = QueryResult([ResultBlock(_keys(2), WENDS[:61], _values(2, 61))],
                      partial=True)
    res.stats.warnings.append('shard 3 of "prometheus" was unreachable')
    return res


BUILT = {"whole rows": _whole, "a row with holes": _holes,
         "a row of NaN only": _nan_only, "a row with +Inf and -Inf": _infinities,
         "an empty result": _empty, "a histogram block": _histogram,
         "a block of no windows": _no_windows,
         "two blocks, two grids": _two_blocks, "20 rows x 721 windows": _wide,
         "a partial result with warnings": _partial}


def _built(name):
    """(status, body), (status, the parent's object) of a result built by
    hand: the envelope as a route would hand it to the server."""
    res = BUILT[name]()
    payload = QueryEngine.render_prom_matrix(res)
    assert payload["data"] == {"resultType": "matrix"}
    assert type(payload["_rendered"].text) is str
    # an envelope that still carries its rows beside it is not a body yet
    with pytest.raises(TypeError):
        json.dumps(payload)
    blob = _encode_json(payload)
    assert "_rendered" not in payload and b"_rendered" not in blob
    assert QueryEngine.to_prom_matrix(res) == json.loads(blob)
    return (200, blob), (200, _parent_payload(res))


# ---- results served

@pytest.fixture(scope="module")
def server():
    srv = FiloServer([DatasetConfig("prometheus", num_shards=1)],
                     http_host="127.0.0.1", http_port=0)
    srv.memstore.get_shard("prometheus", 0).ingest(
        gauge_batch(10, 720, start_ms=START))
    srv.start()
    yield srv
    srv.shutdown()


def _engine_answer(srv, query):
    g = {k: int(v) for k, v in GRID.items()}
    return srv.engines["prometheus"].query_range(query, g["start"],
                                                 g["step"], g["end"])


def _routed(srv):
    """`traceID` and `stats=all` ride the envelope the route returns; a
    caller in the process (`handle`) reads plain lists, which `json.dumps`
    takes (the disk-kill drill compares two answers so)."""
    q = 'heap_usage{_ns_=~"App-[0-4]"}'
    params = {"query": q, "stats": "all", **GRID}
    status, payload = srv.api.route("GET", PATH, dict(params))
    assert "result" not in payload["data"]
    blob = _encode_json(payload)
    body = json.loads(blob)
    assert body.pop("traceID") and body.pop("stats")["samplesScanned"] > 0
    want = _parent_payload(_engine_answer(srv, q))
    assert len(want["data"]["result"]) == 5
    status2, inproc = srv.api.handle("GET", PATH, dict(params))
    assert status2 == status and "_rendered" not in inproc
    assert type(inproc["data"]["result"]) is list
    assert inproc["data"]["result"] == want["data"]["result"]
    assert list(inproc)[:2] == ["status", "data"] and "stats" in inproc
    assert json.loads(json.dumps(inproc, sort_keys=True))["data"] \
        == want["data"]
    return (status, json.dumps(body).encode()), (200, want)


def _batched(srv):
    """`query_range_batch` answers with a list of envelopes, each with its
    rows parsed (one formatter; the list is walked as it always was)."""
    qs = ['heap_usage{_ns_=~"App-[0-4]"}', "sum(heap_usage)"]
    g = {k: int(v) for k, v in GRID.items()}
    status, payload = srv.api.route(
        "POST", "/promql/prometheus/api/v1/query_range_batch", {},
        json.dumps({"queries": qs, **g}).encode())
    body = json.loads(_encode_json(payload))
    assert body["status"] == "success" and len(body["results"]) == 2
    for got, q in zip(body["results"], qs):
        assert got.pop("traceID")
        assert got == _parent_payload(_engine_answer(srv, q))
    first = body["results"][0]
    return (status, json.dumps(first).encode()), \
        (200, _parent_payload(_engine_answer(srv, qs[0])))


def _failed(srv):
    """An error result is its envelope alone, 400; a throttled one 429 with
    the header its route attached (and no `_headers` in the body)."""
    status, payload = srv.api.route(
        "GET", PATH, {"query": "sum(heap_usage)", "scanLimit": "1", **GRID})
    assert payload["error"].startswith("ValueError: shard 0: query would")
    want = {"status": "error", "errorType": "query_error",
            "error": payload["error"], "traceID": payload["traceID"]}
    url = (f"http://127.0.0.1:{srv.http.port}{PATH}?" + urllib.parse.urlencode(
        {"query": "sum(heap_usage)", "timeout": "0.000001", **GRID}))
    with pytest.raises(urllib.error.HTTPError) as shed:
        urllib.request.urlopen(url, timeout=60)
    assert shed.value.code == 429
    assert int(shed.value.headers["Retry-After"]) >= 1
    body = json.loads(shed.value.read())
    assert body.keys() == {"status", "errorType", "error"}
    assert body["errorType"] == "too_many_requests"
    return (status, _encode_json(payload)), (400, want)


def _live(srv):
    # five rows of +Inf, five finite
    q = 'heap_usage{_ns_=~"App-[0-4]"} / 0 or heap_usage{_ns_=~"App-[5-9]"}'
    url = (f"http://127.0.0.1:{srv.http.port}{PATH}?"
           + urllib.parse.urlencode({"query": q, **GRID}))
    with urllib.request.urlopen(url, timeout=60) as r:
        status, blob = r.status, r.read()
        assert r.headers["Content-Type"] == "application/json"
        assert int(r.headers["Content-Length"]) == len(blob)
    body = json.loads(blob)
    assert body.pop("traceID")
    want = _parent_payload(_engine_answer(srv, q))
    texts = {v for r in want["data"]["result"] for _, v in r["values"]}
    assert "+Inf" in texts and len(texts) > 300
    return (status, json.dumps(body).encode()), (200, want)


SERVED = {"traceID and stats=all on the envelope": _routed,
          "a batch of range queries": _batched,
          "an error result": _failed,
          "a request through a live FiloServer": _live}


@pytest.mark.parametrize("case", list(BUILT) + list(SERVED))
def test_the_body_parses_to_the_parents_answer(case, request):
    if case in BUILT:
        (status, blob), (want_status, want) = _built(case)
    else:
        (status, blob), (want_status, want) = SERVED[case](
            request.getfixturevalue("server"))
    assert status == want_status
    body = json.loads(blob)
    assert body == want
    rows = body.get("data", {}).get("result", [])
    # an equal list is equal strings; said again, the rule a value obeys
    for row in rows:
        for t, text in row["values"]:
            assert type(t) is float and type(text) is str
            assert text in ("+Inf", "-Inf") \
                or text == "{:.17g}".format(float(text))
    if case == "a row of NaN only":
        assert [r["metric"]["_ns_"] for r in rows] == ["App-0", "App-2"]
    if case == "a histogram block":
        assert [len(r["values"]) for r in rows] == [5]
    if case == "a block of no windows":
        assert [r["values"] for r in rows] == [[[1_600_000_007.0, "1"]]]
    if case == "20 rows x 721 windows":
        assert sum(len(r["values"]) for r in rows) == 14_420
        # the rows carry no blank between tokens
        assert blob.count(b'],[') == 14_400 and b"], [" not in blob


# ---- the counters, and the benchmark's reading of them

def _booked():
    return [registry.counter(n).value for n in
            ("http_present_points", "http_present_point_fallbacks")]


def test_the_counters_move_by_a_responses_points_and_its_infinite_rows():
    before = _booked()
    present = {}
    for name in ("whole rows", "a row with holes", "a row with +Inf and -Inf",
                 "a histogram block", "an empty result"):
        res = BUILT[name]()
        rendered = _present_matrix(res)["_rendered"]
        present[name] = [len(r["values"]) for r in _parent_rows(res)]
        assert rendered.points == sum(present[name])
    points, fallbacks = (a - b for a, b in zip(_booked(), before))
    assert points == sum(map(sum, present.values()))
    # only the one row that holds an infinity, and only its present points
    inf_row = present["a row with +Inf and -Inf"][1]
    assert 0 < inf_row < 721 and fallbacks == inf_row
    # an error result renders nothing and books nothing
    before = _booked()
    assert _present_matrix(QueryResult([], error="query_timeout: 1s")) \
        == {"status": "error", "errorType": "timeout",
            "error": "query_timeout: 1s"}
    assert _booked() == before


def test_the_benchmark_reads_the_fallbacks_as_it_reads_the_windows(server):
    """`present_point_fallbacks_per_query` is `fused_windows_per_launch`'s
    reader over another counter: same reader, same `args` keys; over two
    scrapes of a live `/metrics` it reads the infinite rows' points a
    request, and 0.0 on a program without the counter."""
    mine, theirs = (bench_json("layer_metrics", n) for n in
                    ("present_point_fallbacks_per_query",
                     "fused_windows_per_launch"))
    assert mine["reader"] == theirs["reader"] == "counter_delta"
    assert mine["args"].keys() == theirs["args"].keys()
    assert (mine["args"]["phase"], mine["args"]["per"]) \
        == (theirs["args"]["phase"], theirs["args"]["per"])
    assert mine["args"]["counters"] == ["http_present_point_fallbacks_total"]
    bench = bench_json("", "../BENCHMARK")
    entry, = (e for e in bench["per_layer"] if e["name"] == mine["name"])
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert (entry["unit"], entry["layer"], entry["moves"], entry["source"]) \
        == (mine["unit"], mine["layer"], mine["moves"], "program_counter")

    def scrape():
        url = f"http://127.0.0.1:{server.http.port}/metrics"
        with urllib.request.urlopen(url, timeout=60) as r:
            lines = r.read().decode().splitlines()
        return {ln.rpartition(" ")[0]: float(ln.rpartition(" ")[2])
                for ln in lines if ln and ln[0] != "#"}

    def ask(query):
        url = (f"http://127.0.0.1:{server.http.port}{PATH}?"
               + urllib.parse.urlencode({"query": query, **GRID}))
        with urllib.request.urlopen(url, timeout=60) as r:
            return json.loads(r.read())["data"]["result"]

    before = scrape()
    finite = ask("heap_usage * 2")
    infinite = ask("heap_usage * 2 / 0")
    after = scrape()
    assert len(finite) == len(infinite) == 10
    points = sum(len(r["values"]) for r in finite)
    assert after["http_present_points_total"] \
        - before.get("http_present_points_total", 0.0) == 2 * points
    read = bench_module("readers", mine["reader"]).read
    ctx = {"counters": {"window": (before, after)}, "results": [1, 2]}
    assert read(ctx, **mine["args"]) == points / 2
    without = {k: v for k, v in after.items() if "http_present" not in k}
    ctx = {"counters": {"window": (without, without)}, "results": [1, 2]}
    assert read(ctx, **mine["args"]) == 0.0
