"""A chip's 32-shard share of the 128-shard layout behind the real HTTP door,
built from the benchmark's own files (configuration `ts128-counters-262k-32sh`,
generator, loader `grid_on_mirror` (`grid` after one question to the leaf
router), `reference.py`, traffic `dashboard_open`, the
client's comparison) at a size the CPU serves in seconds: 2,048 series x 240
samples, interpret-mode kernels.  Not a test file: `test_ts128_served.py` and
`test_fused_caches.py` share it."""
from histrig import HistRig, bench_json, bench_module

CONFIG, CELL = "ts128-counters-262k-32sh", "ts128-counters-262k-32sh.open"
SERIES, SAMPLES = 2048, 240


def small_config(config=CONFIG, **over):
    return dict(bench_json("configs", config), series=SERIES,
                samples=SAMPLES, **over)


def small_plan(cfg, seed, cell=CELL, **traffic):
    """The cell's six panels on a grid that 240 samples hold: 20 minutes a
    request, two phases (`traffic`: a subclass's own cut, `Ts128Rig.TRAFFIC`)."""
    tp = dict(bench_json("workloads", cell)["traffic"],
              **(traffic or Ts128Rig.TRAFFIC))
    return bench_module("traffic", tp["kind"]).Plan(cfg, tp, seed)


class Ts128Rig(HistRig):
    """One `FiloServer` on port 0 holding the small deployment, loaded by
    the configuration's loader, with the reference's tables in the client's `Tables`;
    `get`, `ask`, `open`, `counters` and `close` are `HistRig`'s.
    `FILODB_TPU_FUSED_INTERPRET=1` is the caller's to set.  A subclass
    names another counters configuration and cell (`CONFIG`, `CELL`), and
    where it must another size and cut of the traffic (`SIZE`, `TRAFFIC`)."""
    CONFIG, CELL = CONFIG, CELL
    SIZE = {}                   # over small_config's 2,048 x 240
    TRAFFIC = dict(span_s=1200, phases=2, warmup_opens=1)

    def __init__(self, seed, control=None):
        from filodb_tpu.standalone import DatasetConfig, FiloServer
        self.seed = seed
        self.cfg = dict(small_config(self.CONFIG), **self.SIZE)
        self.plan = small_plan(self.cfg, seed, self.CELL, **self.TRAFFIC)
        self.srv = FiloServer(
            [DatasetConfig(self.cfg["dataset"], self.cfg["shards"])],
            http_host="127.0.0.1", http_port=0)
        self.srv.start()
        self.base = f"http://127.0.0.1:{self.srv.http.port}"
        spans = dict.fromkeys(("keys_and_routing", "generate", "reference",
                               "ingest_columns"), 0.0)
        self.ref, self.per_shard = bench_module(
            "loaders", self.cfg.get("loader", "grid")).load(
            self.srv, self.cfg, self.plan, seed, control, spans,
            bench_module)
        self.tables = bench_module("", "client").Tables({
            "port": self.srv.http.port,
            "wends_s": self.plan.window_ends_s().tolist(), "limits": {},
            "tables": [{"by": self.plan.panels[j]["by"], "groups": groups,
                        "values": self.ref.table(self.plan.panels[j],
                                                 fold).tolist(),
                        "check": "rate_rel_err"}
                       for j, fold, groups in self.plan.tables()]})

    @property
    def populated(self):
        return sum(1 for n in self.per_shard if n)

    def forget_results(self):
        """Empty the frontend's result cache, so that a repeated request
        reaches the leaves again."""
        self.srv.api.frontends[self.cfg["dataset"]].cache.clear()

    def samples(self):
        """/metrics, sample by sample: {name{labels}: value}."""
        out = {}
        for line in self.get("/metrics").decode().splitlines():
            if line and line[0] != "#":
                name, _, val = line.rpartition(" ")
                out[name] = float(val)
        return out

