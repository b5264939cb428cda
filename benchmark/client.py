"""The load generator and the comparison, in a process of its own that never
imports JAX (the parent holds the chip).

Commands arrive as JSON lines on stdin, one reply line each on stdout:

    {"cmd": "tables", "port", "wends_s", "panels": [{"by", "groups",
        "values", "check"}], "limits": {check: limit}}   -> {"ok": true}
    {"cmd": "run", "requests", "in_flight", "seconds"}  -> results
    {"cmd": "exit"}

`run` is a closed loop: `in_flight` threads each send the next request of the
list the moment their last one is answered, no think time.  With `seconds` no
request is started after that long; those in flight are finished and counted,
and the window ends when the last of them is answered.  A request's clock
runs from before the socket is opened to the parsed body; its body is
compared with the reference table after the clock has stopped.
"""
import json
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

TIMEOUT_S = 1100


class Tables:
    def __init__(self, msg):
        self.base = f"http://127.0.0.1:{msg['port']}"
        self.col = {t: i for i, t in enumerate(msg["wends_s"])}
        self.panels = msg["panels"]
        self.limits = msg["limits"]
        for p in self.panels:
            p["rows"] = {tuple(g): row
                         for g, row in zip(p["groups"], p["values"])}

    def compare(self, req, body):
        """(largest relative error, None) or (None, what differs)."""
        if body.get("status") != "success":
            return None, f"status {body.get('status')}: {body.get('error')}"
        p = self.panels[req["panel"]]
        q = req["params"]
        want_t = [t for t in range(q["start"], q["end"] + 1, q["step"])]
        got = {tuple(r["metric"].get(lab, "") for lab in p["by"]): r
               for r in body["data"]["result"]}
        if set(got) != set(p["rows"]):
            return None, f"groups differ: {sorted(got)[:3]}"
        worst = 0.0
        for key, row in p["rows"].items():
            want = {t: row[self.col[t]] for t in want_t
                    if row[self.col[t]] == row[self.col[t]]}
            vals = got[key].get("values") or []
            if [int(float(t)) for t, _ in vals] != list(want):
                return None, f"timestamps differ in {key}"
            for t, v in vals:
                w = want[int(float(t))]
                err = abs(float(v) - w) / max(abs(w), 1e-300)
                if not err <= worst:        # also catches a NaN answer
                    worst = err
                    if err != err:
                        return None, f"not a number in {key} at {t}"
        return worst, None


def one(tables, req):
    out = {"id": req["id"], "panel": req["panel"], "ok": False,
           "err": None, "cache": None, "why": None}
    url = tables.base + req["path"] + "?" + urllib.parse.urlencode(
        req["params"])
    out["send"] = time.perf_counter()
    try:
        with urllib.request.urlopen(url, timeout=TIMEOUT_S) as r:
            body = json.loads(r.read())
        out["done"] = time.perf_counter()
    except (urllib.error.URLError, OSError, ValueError) as e:
        out["done"] = time.perf_counter()
        code = getattr(e, "code", None)
        out["why"] = f"http {code}" if code else f"{type(e).__name__}: {e}"
        return out
    err, why = tables.compare(req, body)
    stats = (body.get("data") or {}).get("stats") or body.get("stats") or {}
    out["cache"] = (stats.get("cache") or {}).get("result")
    out["err"], out["why"] = err, why
    if why is None:
        limit = tables.limits[tables.panels[req["panel"]]["check"]]
        out["ok"] = err <= limit
        if not out["ok"]:
            out["why"] = f"relative error {err} over {limit}"
    return out


def run(tables, msg):
    reqs, results = msg["requests"], []
    lock = threading.Lock()
    state = {"next": 0}
    t0 = time.perf_counter()
    deadline = None if msg.get("seconds") is None else t0 + msg["seconds"]

    def worker():
        while True:
            with lock:
                i = state["next"]
                if i >= len(reqs) or (deadline is not None
                                      and time.perf_counter() >= deadline):
                    return
                state["next"] = i + 1
            res = one(tables, reqs[i])
            with lock:
                results.append(res)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(msg["in_flight"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_end = max([r["done"] for r in results], default=t0)
    return {"t0": t0, "t_end": t_end, "results": results,
            "sent": state["next"], "listed": len(reqs)}


def main():
    tables = None
    for line in sys.stdin:
        msg = json.loads(line)
        if msg["cmd"] == "exit":
            break
        if msg["cmd"] == "tables":
            tables = Tables(msg)
            reply = {"ok": True}
        else:
            reply = run(tables, msg)
        sys.stdout.write(json.dumps(reply) + "\n")
        sys.stdout.flush()


if __name__ == "__main__":
    main()
