"""A small histogram deployment behind the real HTTP door, built from the
benchmark's own files (configuration, generator, loader, reference, traffic,
the client's comparison) at a size the CPU serves in seconds: 128 series x 64
buckets x 240 samples over 4 shards, interpret-mode kernels.  Not a test
file: `test_hist_served.py` and `test_leaf_route.py` share it."""
import contextlib
import json
import os
import sys
import urllib.parse
import urllib.request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmark import run  # noqa: E402

CONFIG, CELL = "histdev-64b-4k", "histdev-64b-4k.quantiles"
SERIES, SAMPLES, SHARDS = 128, 240, 4
# benchmark/<kind>/<name>.py by path, as the harness finds it
bench_module = run.load_module


def bench_json(kind, name):
    return run.load_json(os.path.join(run.BENCH_DIR, kind, name + ".json"))


@contextlib.contextmanager
def environ(**values):
    """Environment variables set for the length of a fixture."""
    old = {k: os.environ.get(k) for k in values}
    os.environ.update(values)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                del os.environ[k]
            else:
                os.environ[k] = v


def small_config(**over):
    return dict(bench_json("configs", CONFIG), series=SERIES,
                samples=SAMPLES, **over)


def small_plan(cfg, seed):
    """The cell's six panels on a grid that 240 samples hold: 20 minutes a
    request, two phases."""
    tp = dict(bench_json("workloads", CELL)["traffic"], span_s=1200,
              phases=2, warmup_opens=1)
    return bench_module("traffic", tp["kind"]).Plan(cfg, tp, seed)


class HistRig:
    """One `FiloServer` on port 0 holding the small deployment, loaded by
    `loaders/hist_grid.py`, with the reference's tables in the client's
    `Tables`.  `FILODB_TPU_FUSED_INTERPRET=1` is the caller's to set."""

    def __init__(self, seed, control=None, shards=SHARDS):
        from filodb_tpu.standalone import DatasetConfig, FiloServer
        self.cfg = small_config(shards=shards)
        self.plan = small_plan(self.cfg, seed)
        self.srv = FiloServer([DatasetConfig(self.cfg["dataset"], shards)],
                              http_host="127.0.0.1", http_port=0)
        self.srv.start()
        self.base = f"http://127.0.0.1:{self.srv.http.port}"
        spans = dict.fromkeys(("keys_and_routing", "generate", "reference",
                               "ingest_columns"), 0.0)
        self.ref, self.per_shard = bench_module("loaders", "hist_grid").load(
            self.srv, self.cfg, self.plan, seed, control, spans,
            bench_module)
        self.tables = bench_module("", "client").Tables({
            "port": self.srv.http.port,
            "wends_s": self.plan.window_ends_s().tolist(), "limits": {},
            "tables": [{"by": self.plan.panels[j]["by"], "groups": groups,
                        "values": self.ref.table(self.plan.panels[j],
                                                 fold).tolist(),
                        "check": "quantile_rel_err"}
                       for j, fold, groups in self.plan.tables()]})

    def get(self, path, params=None):
        url = self.base + path
        if params:
            url += "?" + urllib.parse.urlencode(params)
        with urllib.request.urlopen(url, timeout=300) as r:
            return r.read()

    def ask(self, req):
        """(largest relative error against the reference, None) or (None,
        what differs), by the benchmark client's own comparison."""
        body = json.loads(self.get(req["path"], req["params"]))
        return self.tables.compare(req, body), body

    def open(self, n):
        """The six requests of the window's open n (every open is a
        result-cache miss)."""
        k = len(self.plan.panels)
        return self.plan.requests()[n * k:(n + 1) * k]

    def counters(self):
        out = {}
        for line in self.get("/metrics").decode().splitlines():
            if line and line[0] != "#":
                name, _, val = line.rpartition(" ")
                fam = name.split("{", 1)[0]
                out[fam] = out.get(fam, 0.0) + float(val)
        return out

    def close(self):
        self.srv.shutdown()
