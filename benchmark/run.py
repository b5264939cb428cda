#!/usr/bin/env python3
"""The benchmark's one command: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process holds the chip.  It starts a `FiloServer` in-process the way
`filo-cli serve` does, makes the cell's data from `--seed`, loads it through
the columnar ingest door routed as the gateway routes, works out the f64
reference for every window the traffic can ask for, warms up the cell's
panels, and then lets a child process (`client.py`, no JAX) drive the HTTP
door in a closed loop for `--seconds`, comparing every body with the
reference.  The last line of stdout is the result; `--trace 1` takes a
profiler trace of the window and reports the per-layer metrics instead of the
end-to-end ones.

Which cell, configuration, traffic kind, generator and per-layer readers are
used is all read from `BENCHMARK.json` and the files it names: nothing about
one cell is written in this file.

Without a TPU it exits non-zero and prints no result.  `--rehearse` runs the
same control flow on the CPU at a tiny size with interpret-mode kernels; its
result line says so in `device` and none of its numbers is a measurement.
`--control bf16` rounds the samples to bfloat16 before they are ingested
(the reference keeps the unrounded ones): the lower-precision run that the
comparison must fail.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import urllib.request  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
GEN_CHUNK = 65_536          # series generated and ingested at once
REF_BLOCK = 256             # ... and handed to the reference at once (cache-sized)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """benchmark/<kind>/<name>.py, found by name, under a module name that
    shadows nothing (`trace`, for one, is also a standard module)."""
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name}".replace("__", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--control", choices=("bf16",), default=None)
    p.add_argument("--keep-trace", default=None,
                   help="also save the trace in plain form to this path")
    return p.parse_args(argv)


def to_bf16(vals):
    """f64 -> nearest bfloat16 (ties to even) -> f64."""
    import numpy as np
    bits = vals.astype(np.float32).view(np.uint32)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & np.uint32(0xFFFF0000)
    return bits.view(np.float32).astype(np.float64)


def label_value(spec, i):
    if isinstance(spec, str):
        return spec
    return spec["prefix"] + str(i % spec["mod"] if "mod" in spec else i)


class Http:
    """The program's own counters, read over its HTTP door."""

    def __init__(self, port):
        self.base = f"http://127.0.0.1:{port}"

    def get(self, path):
        with urllib.request.urlopen(self.base + path, timeout=600) as r:
            return r.read()

    def counters(self):
        """Samples of /metrics, summed per family."""
        out = {}
        for line in self.get("/metrics").decode().splitlines():
            if not line or line[0] == "#":
                continue
            name, _, val = line.rpartition(" ")
            fam = name.split("{", 1)[0]
            try:
                out[fam] = out.get(fam, 0.0) + float(val)
            except ValueError:
                pass
        return out

    def job(self, name):
        """The /admin/jobs row of one of the program's background jobs."""
        rows = json.loads(self.get("/admin/jobs"))["data"]["jobs"]
        return next((j for j in rows if j["job"] == name), None)

    def kernels(self):
        """{kernel: [count, seconds]} of /admin/devices, over all devices."""
        out = {}
        devs = json.loads(self.get("/admin/devices"))["data"]["devices"]
        for st in devs.values():
            for name, k in st["kernels"].items():
                c = out.setdefault(name, [0, 0.0])
                c[0] += int(k["count"])
                c[1] += float(k["seconds"])
        return out


def load_data(server, cfg, plan, seed, control, spans):
    """Generate, reference-evaluate and ingest the configuration's series
    through shard.ingest_columns, routed to shards as the gateway routes
    them.  Returns (Reference, series per shard)."""
    import numpy as np
    from filodb_tpu.core.partkey import PartKey
    Reference = load_module("", "reference").Reference
    gen = load_module("generators", cfg["generator"])
    ds, S, T = cfg["dataset"], cfg["series"], cfg["samples"]
    mapper, spread = server.mappers[ds], server.spreads[ds]
    shards = server.memstore.shards_for(ds)
    ts_row = cfg["start_ms"] + np.arange(T, dtype=np.int64) * cfg["scrape_ms"]
    num_base = plan.num_base()
    ref = Reference(ts_row, plan.window_ends_s() * 1000,
                    plan.range_s * 1000, plan.panels, num_base)
    per_shard = np.zeros(len(shards), np.int64)
    vbuf = np.empty((min(GEN_CHUNK, S), T))
    for c, lo in enumerate(range(0, S, GEN_CHUNK)):
        hi = min(lo + GEN_CHUNK, S)
        n = hi - lo
        t0 = time.perf_counter()
        keys = [PartKey.make(cfg["metric"], {
            lab: label_value(spec, i) for lab, spec in cfg["labels"].items()})
            for i in range(lo, hi)]
        shard_of = np.fromiter(
            (mapper.ingestion_shard(pk.shard_key_hash(), pk.partition_hash(),
                                    spread.spread_for(pk.shard_key()))
             for pk in keys), np.int64, n)
        t1 = time.perf_counter()
        vals = gen.chunk(np.random.default_rng([seed, c]), vbuf[:n])
        t2 = time.perf_counter()
        for b in range(0, n, REF_BLOCK):
            ref.add(vals[b:b + REF_BLOCK],
                    np.arange(lo + b, min(lo + b + REF_BLOCK, hi)) % num_base)
        t3 = time.perf_counter()
        stored = to_bf16(vals) if control == "bf16" else vals
        for sh in shards:
            idx = np.flatnonzero(shard_of == sh.shard_num)
            if idx.size:
                got = sh.ingest_columns(
                    cfg["schema"], [keys[i] for i in idx],
                    np.broadcast_to(ts_row, (idx.size, T)),
                    {cfg["column"]: stored[idx]}, offset=c)
                if got != idx.size * T:
                    raise RuntimeError(f"ingested {got} of {idx.size * T}")
                per_shard[sh.shard_num] += idx.size
        t4 = time.perf_counter()
        spans["keys_and_routing"] += t1 - t0
        spans["generate"] += t2 - t1
        spans["reference"] += t3 - t2
        spans["ingest_columns"] += t4 - t3
    return ref, per_shard.tolist()


def wait_for_job(http, name, lead_s):
    """Sleep until `lead_s` before the next pass of the program's background
    job `name` is due, so that every window holds exactly one pass, whole,
    wherever set-up happened to end.  Returns the seconds waited."""
    t0 = time.perf_counter()
    while True:
        job = http.job(name)
        if job is None or job["lastEndUnixSeconds"] <= 0:
            break                       # no such job in this deployment
        due = job["lastEndUnixSeconds"] + job["intervalSeconds"] - lead_s
        if job["running"]:
            time.sleep(0.2)             # a pass is under way: let it end
        elif time.time() >= due:
            break
        else:
            time.sleep(min(due - time.time(), 1.0))
    return time.perf_counter() - t0


class Child:
    """client.py: started once, told what to do over its stdin."""

    def __init__(self):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(BENCH_DIR, "client.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)

    def ask(self, **msg):
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError("the client process died")
        return json.loads(line)

    def close(self):
        try:
            if self.proc.poll() is None:
                self.proc.stdin.write('{"cmd": "exit"}\n')
                self.proc.stdin.close()
                self.proc.wait(timeout=30)
        except (OSError, subprocess.TimeoutExpired):
            self.proc.kill()
            self.proc.wait()


def percentile(sorted_vals, q):
    """Nearest-rank percentile of a sorted list."""
    return sorted_vals[max(math.ceil(q * len(sorted_vals)) - 1, 0)]


def end_to_end(window, setup_s):
    good = sorted((r["done"] - r["send"]) * 1000.0
                  for r in window["results"] if r["ok"])
    out = {"setup_s": setup_s}
    if good:
        out["query_p50_ms"] = statistics.median(good)
        out["query_p95_ms"] = percentile(good, 0.95)
        out["queries_per_s"] = len(good) / (window["t_end"] - window["t0"])
    return out


def checks_of(wl, results):
    """[(name, value, limit, ok)]: each number compared, beside its limit."""
    out = []
    for chk in wl["checks"]:
        errs = [r["err"] for r in results
                if r["err"] is not None and r["check"] == chk["name"]]
        worst = max(errs, default=float("nan"))
        out.append((chk["name"], worst, chk["limit"],
                    bool(errs) and worst <= chk["limit"]))
    broken = sum(1 for r in results if r["err"] is None)
    out.append(("requests_unanswered_or_misshapen", broken, 0, broken == 0))
    return out


def per_layer(bench, cell, ctx):
    """The cell's per-layer metrics, each by the reader its file names."""
    metrics = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        spec = load_json(os.path.join(BENCH_DIR, "layer_metrics",
                                      m["name"] + ".json"))
        value = load_module("readers", spec["reader"]).read(
            ctx, **spec.get("args", {}))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def breakdown_of(tracelib, planes, results, trace_t0):
    """The programs that took most device time, and the longest idle gaps
    labelled by what the client side can see."""
    span = tracelib.span_ns(planes)
    if span is None:
        return None
    # the trace counts nanoseconds from about when start_trace was called;
    # the client's clock is this process's (CLOCK_MONOTONIC)
    flights = [((r["send"] - trace_t0) * 1e9, (r["done"] - trace_t0) * 1e9)
               for r in results]

    def label(s, e):
        mid = (s + e) / 2
        return "request-in-flight" if any(a <= mid <= b for a, b in flights) \
            else "no-request"
    return {"device_ops": tracelib.top_programs(planes),
            "idle_gaps": tracelib.idle_gaps(planes, span[0], span[1], label)}


def main(argv=None):
    args = parse_args(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
    wl = load_json(os.path.join(BENCH_DIR, "workloads", cell["name"] + ".json"))
    tp = wl["traffic"]
    if args.rehearse:
        # the only way to run without a chip; set before jax is imported
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["FILODB_TPU_FUSED_INTERPRET"] = "1"
        cfg["series"] = cfg["rehearse_series"]

    import jax
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}
    peaks = load_json(os.path.join(BENCH_DIR, "peaks.json"))["by_device_kind"]
    if args.rehearse:
        device["rehearsal"] = "CPU, interpret-mode kernels, " \
            f"{cfg['series']} series: control flow only, no measurement"
    elif device["platform"] != "tpu" or device["count"] < cell["chips"] \
            or device["kind"] not in peaks:
        log(f"the cell needs {cell['chips']} TPU chip(s) of a kind that "
            f"peaks.json knows; found {device}")
        return 1

    sys.path.insert(0, ROOT)
    from filodb_tpu.config import apply_jax_runtime
    from filodb_tpu.standalone import DatasetConfig, FiloServer

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(time.perf_counter())
        if name == COMPILE_EVENT else None)

    traffic = load_module("traffic", tp["kind"])
    plan = traffic.Plan(cfg, tp, args.seed)
    spans = dict.fromkeys(("keys_and_routing", "generate", "reference",
                           "ingest_columns", "tables", "warm_up",
                           "align_wait"), 0.0)
    server = FiloServer([DatasetConfig(cfg["dataset"], cfg["shards"])],
                        http_host="127.0.0.1", http_port=0)
    apply_jax_runtime(server.config)
    server.start()
    child = None
    tracing = False
    try:
        http = Http(server.http.port)
        c_start = http.counters()
        ref, per_shard = load_data(server, cfg, plan, args.seed, args.control,
                                   spans)
        log(f"loaded {cfg['series']} x {cfg['samples']}: per shard "
            f"{per_shard}, spans {spans}")

        t0 = time.perf_counter()
        check_of_fn = {fn: c["name"] for c in wl["checks"] for fn in c["fns"]}
        panels = []
        for p in plan.panels:
            fold, groups = plan.fold(p)
            panels.append({"by": p["by"], "groups": groups,
                           "values": ref.table(p, fold).tolist(),
                           "check": check_of_fn[p["fn"]]})
        child = Child()
        child.ask(cmd="tables", port=server.http.port,
                  wends_s=plan.window_ends_s().tolist(), panels=panels,
                  limits={c["name"]: c["limit"] for c in wl["checks"]})
        spans["tables"] = time.perf_counter() - t0

        # warm-up: the first open alone (it builds the mirror and compiles),
        # the others as the window will run them
        t0 = time.perf_counter()
        warm = plan.warmup()
        n1 = len(plan.panels)
        warm_res = child.ask(cmd="run", requests=warm[:n1], in_flight=1,
                             seconds=None)["results"]
        warm_res += child.ask(cmd="run", requests=warm[n1:],
                              in_flight=tp["in_flight"],
                              seconds=None)["results"]
        spans["warm_up"] = time.perf_counter() - t0
        for r in warm_res:
            if not r["ok"]:
                log(f"warm-up request {r['id']} failed: {r['why']}")

        if "align_to_job" in tp:
            spans["align_wait"] = wait_for_job(
                http, tp["align_to_job"]["job"], tp["align_to_job"]["lead_s"])
        c_before, k_before = http.counters(), http.kernels()
        trace_dir = os.path.join(BENCH_DIR, ".trace", args.workload)
        if args.trace:
            import shutil
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            trace_t0 = time.perf_counter()
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
        # set-up is the program's and the load's; the reference's own time
        # and the idle wait for the background job are the harness's
        setup_s = time.perf_counter() - T_START - spans["reference"] \
            - spans["tables"] - spans["align_wait"]
        epoch0 = time.time()
        window = child.ask(cmd="run", requests=plan.requests(),
                           in_flight=tp["in_flight"], seconds=args.seconds)
        if tracing:
            trace_t1 = time.perf_counter()
            jax.profiler.stop_trace()
            tracing = False
        c_after, k_after = http.counters(), http.kernels()
        jobs_after = {name: http.job(name) for name in
                      ([tp["align_to_job"]["job"]] if "align_to_job" in tp
                       else [])}
        epochs = (epoch0, time.time())
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())
    finally:
        if tracing:
            jax.profiler.stop_trace()
        if child is not None:
            child.close()
        # shutdown()'s final flush encodes every sample into the in-memory
        # column store, which dies with this process: minutes at this size
        # that no request waits for.  The doors and threads are stopped.
        for sched in server.flush_schedulers.values():
            sched.stop(final_flush=False)
        server.flush_schedulers.clear()
        server.shutdown()
    log(f"server stopped {time.perf_counter() - T_START:.1f} s after start")

    results = window["results"]
    for r in results + warm_res:
        r["check"] = panels[r["panel"]]["check"]
    device["memory_peak_bytes"] = int(peak)
    checks = checks_of(wl, results + warm_res)
    failed = [r for r in results if not r["ok"]]
    for r in failed[:5]:
        log(f"request {r['id']} failed: {r['why']}")
    buckets = {}
    for r in results:
        buckets.setdefault(int((r["send"] - window["t0"]) // 2), []).append(
            (r["done"] - r["send"]) * 1e3)
    log("median ms of the requests sent in each 2 s of the window: "
        + " ".join(f"{statistics.median(v):.0f}"
                   for _, v in sorted(buckets.items())))
    if window["sent"] >= window["listed"]:
        log("the window ran out of opens that nothing has cached")
    for name, value, limit, ok in checks:
        print(f"check {name}: {value!r} limit {limit!r} "
              f"{'ok' if ok else 'NOT OK'}")
    correct = bool(results) and not failed and all(c[3] for c in checks)

    metrics, breakdown = {}, None
    if not args.trace:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        for name, value in end_to_end(window, setup_s).items():
            metrics[name] = {"value": value, "unit": units[name]}
    else:
        tracelib = load_module("", "trace")
        path = tracelib.find_xplane(trace_dir)
        planes = tracelib.load_xplane(path) if path else []
        if args.keep_trace:
            os.makedirs(os.path.dirname(args.keep_trace), exist_ok=True)
            tracelib.save(planes, args.keep_trace)
        device["busy_s"] = tracelib.busy_seconds(planes)
        device["window_s"] = trace_t1 - trace_t0
        ctx = {"results": results, "cfg": cfg, "plan": plan,
               "per_shard": per_shard, "spans": spans,
               "counters": {"setup": (c_start, c_before),
                            "window": (c_before, c_after)},
               "kernels": (k_before, k_after), "trace": planes,
               "jobs": jobs_after, "window_epochs": epochs,
               "trace_window_s": device["window_s"],
               "compiles_in_window": sum(
                   1 for t in compiles if window["t0"] <= t <= window["t_end"]),
               "peak": peaks.get(device["kind"]), "tracelib": tracelib,
               "costs": load_module("", "costs")}
        metrics = per_layer(bench, cell, ctx)
        breakdown = breakdown_of(tracelib, planes, results, trace_t0)
        log("traced run, end to end (not reported): "
            f"{end_to_end(window, setup_s)}")

    log(f"spans {spans}; compiles {len(compiles)}; sent {window['sent']}; "
        f"result after {time.perf_counter() - T_START:.1f} s")
    line = {"correct": correct, "attempted": len(results),
            "failed": len(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
