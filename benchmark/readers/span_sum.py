"""Milliseconds a request, over the window, of a sum of the program's span
and counter families; or the share (%) of the client's mean latency that the
sum does NOT cover.

`self_of` lists spans taken by their self time (`span_<name>_self_seconds_
total`), `whole_of` spans taken by their whole duration (`span_<name>_
seconds_total`), `less` spans whose duration is taken off again, `counters`
families of seconds by their own names.  With `share_of_latency: "unseen"`
the answer is 100 x (1 - the sum a request / the client's mean latency).

A connection's life, one stage after the other on the client's clock: the
hand-over (`conn_handover_seconds_total`: accept()'s return to the handler
thread's first act; a counter, because it spans two threads), then
`conn.serve` on the handler thread, of which the end (`conn.close`: after
the answer) is no part of what the client waits for.  What that sum leaves
of the client's latency is the kernel's queue before accept() returns, the
socket, and the client process's own connect, read and parse.  A program
without one of the families (an older commit) gives None.
"""
SELF, DUR = "_self_seconds_total", "_seconds_total"


def flat(name):
    return "span_" + name.replace(".", "_")


def read(ctx, self_of=(), whole_of=(), less=(), counters=(),
         share_of_latency=None):
    before, after = ctx["counters"]["window"]
    res = ctx["results"]
    terms = [(flat(s) + SELF, 1.0) for s in self_of] \
        + [(flat(s) + DUR, 1.0) for s in whole_of] \
        + [(flat(s) + DUR, -1.0) for s in less] \
        + [(c, 1.0) for c in counters]
    if not res or any(f not in after for f, _ in terms):
        return None
    secs = sum(sign * (after[f] - before.get(f, 0.0)) for f, sign in terms)
    ms = 1000.0 * secs / len(res)
    if share_of_latency == "unseen":
        mean = sum((r["done"] - r["send"]) * 1000.0 for r in res) / len(res)
        return 100.0 * (1.0 - ms / mean)
    return ms
