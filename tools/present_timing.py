"""Host time of a range query's body by the kind of its rows: whole, 30% of
the windows absent, an infinity every 50th window; 10 rows at 61 and at 721
windows.  Times the tree it is run from, so a parent and a change are two
runs (`PYTHONPATH=<tree> python3 tools/present_timing.py`): a tree whose
server has `_encode_json` renders the rows as text, an older one formats
dicts and walks them with `json.dumps`.  Prints one JSON line; host clock of
one thread, no device."""
import json
import os
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from filodb_tpu.query.engine import QueryEngine  # noqa: E402
from filodb_tpu.query.rangevector import (QueryResult,  # noqa: E402
                                          RangeVectorKey, ResultBlock)

try:
    from filodb_tpu.http.server import _encode_json

    def body(res):
        return _encode_json(QueryEngine.render_prom_matrix(res))
except ImportError:
    def body(res):
        return json.dumps(QueryEngine.to_prom_matrix(res)).encode()


def main(rows=10, repeats=200):
    rng = np.random.default_rng(5)
    keys = [RangeVectorKey.make({"_ns_": f"App-{i}"}) for i in range(rows)]
    out = {}
    for windows in (61, 721):
        wends = (1_600_000_007 + np.arange(windows) * 30) * 1000
        for kind in ("whole", "holes30", "inf"):
            vals = rng.standard_normal((rows, windows)) * 1e3
            if kind == "holes30":
                vals[rng.random((rows, windows)) < 0.3] = np.nan
            if kind == "inf":
                vals[:, ::50] = np.inf
            res = QueryResult([ResultBlock(keys, wends, vals)])
            body(res)
            t0 = time.perf_counter()
            for _ in range(repeats):
                blob = body(res)
            out[f"{kind}_{windows}_ms"] = round(
                (time.perf_counter() - t0) / repeats * 1e3, 4)
            out[f"{kind}_{windows}_bytes"] = len(blob)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
