"""Host milliseconds spent INSIDE the listed spans of the program and not in
their children, over the window, per answered request or per call.

The program books each span's self time (duration less its children's) into
`span_<name>_self_seconds_total` and its exits into `span_<name>_calls_total`
(utils/metrics.span; dots in `<name>` written `_`), so over all the spans of
a request the self times sum to the root's duration.  `spans` lists names; an
entry that ends in `.` is a prefix (`leaf.` takes every leaf span and leaves
the background `flush.*` and `mirror.*` out), and `exclude` takes entries of
the same two forms back out.  `per: "call"` divides by the calls of the first
listed name that is not a prefix.  `duration_of: <name>` takes, in place of
any list, that one span's whole duration (`span_<name>_seconds_total`, which
the program books beside the self time).  With `share_of_latency: "outside"`
the answer is 100 x (1 - that time a request / the client's mean latency):
the share of a request that the span never saw.  A program without these
spans (an older commit) gives None.
"""
SELF, CALLS, DUR = "_self_seconds_total", "_calls_total", "_seconds_total"


def flat(name):
    return "span_" + name.replace(".", "_")


def chosen(families, spans, exclude=()):
    """The `span_*_self_seconds_total` families of the listed spans."""
    def hit(fam, entries):
        return any(fam.startswith(flat(e)) if e.endswith(".")
                   else fam == flat(e) + SELF for e in entries)
    return sorted(f for f in families if f.endswith(SELF)
                  and hit(f, spans) and not hit(f, exclude))


def read(ctx, spans=(), per="request", exclude=(), share_of_latency=None,
         duration_of=None):
    before, after = ctx["counters"]["window"]
    if duration_of:
        fams = [f for f in (flat(duration_of) + DUR,) if f in after]
    else:
        fams = chosen(after, spans, exclude)
    if not fams:
        return None
    secs = sum(after[f] - before.get(f, 0.0) for f in fams)
    if per == "call":
        first = flat(next(s for s in spans if not s.endswith("."))) + CALLS
        n = after.get(first, 0.0) - before.get(first, 0.0)
    else:
        n = len(ctx["results"])
    if not n:
        return None
    ms = 1000.0 * secs / n
    if share_of_latency == "outside":
        res = ctx["results"]
        mean = sum((r["done"] - r["send"]) * 1000.0 for r in res) / len(res)
        return 100.0 * (1.0 - ms / mean)
    return ms
