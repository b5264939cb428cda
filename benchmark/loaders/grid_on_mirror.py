"""Loader `grid_on_mirror`: `grid` itself (the same generate -> reference ->
ingest, line for line: it calls `grid.load`), after one question to the
program, as `hist_grid` asks one.

A deployment of many small shards has populated leaves under
`query.host_route_max_samples`: the smallest leaf of this configuration at
its own size (`smallest_leaf_series_at_size`: a rehearsal cuts `series`, not
the question) scans 1.27 M samples over the traffic's span, under the cap of
2 M.  Before it generates anything the loader asks the program where it
would send that leaf when its rows sit in the mirror of an attached chip
(`query/leafexec.leaf_route(est_samples, values_per_sample, cap,
mirrored=True)` -> "host" | "device").  A program that sends it to the host,
or whose router cannot be told that a leaf is mirrored and so routes it by
size alone, answers four of this deployment's leaves on the 1.9 s host route
and is not running this deployment on the chip: the run ends here, in
seconds, instead of serving six-second requests that the guards
`offroute_leaves` and `offmirror_leaves` (must read 0) would disown anyway.

After the load, at the configuration's own size, the smallest populated shard
must hold the series the file states: the routing is the gateway's and the
same in every run, so a difference means the file is out of date.
"""


def leaf_samples(cfg, plan):
    """Samples the smallest populated shard's leaf scans: its rows over the
    span and the first window's range."""
    per_series = (plan.span_s + plan.range_s) * 1000 // cfg["scrape_ms"] + 1
    return cfg["smallest_leaf_series_at_size"] * per_series


def require_device_route(cfg, plan):
    from filodb_tpu.config import settings
    from filodb_tpu.query.leafexec import leaf_route
    cap = settings().query.host_route_max_samples
    est = leaf_samples(cfg, plan)
    try:
        route = leaf_route(est, 1, cap, mirrored=True)
    except TypeError:
        # a router that knows nothing of the mirror decides by size alone
        route = leaf_route(est, 1, cap)
    if route != "device":
        raise RuntimeError(
            f"leaf_route({est}, 1, {cap}) says {route!r} for a leaf whose "
            "rows sit in the device mirror: this deployment's small shards "
            "must be answered from the mirror, not by the host route")


def load(server, cfg, plan, seed, control, spans, find):
    """`grid.load`, after the question above.  Returns (Reference, series
    per shard)."""
    require_device_route(cfg, plan)
    ref, per_shard = find("loaders", "grid").load(
        server, cfg, plan, seed, control, spans, find)
    smallest = min(n for n in per_shard if n)
    if cfg["series"] != cfg["rehearse_series"] \
            and smallest != cfg["smallest_leaf_series_at_size"]:
        raise RuntimeError(
            f"the smallest populated shard holds {smallest} series, the "
            f"configuration states {cfg['smallest_leaf_series_at_size']}")
    return ref, per_shard
