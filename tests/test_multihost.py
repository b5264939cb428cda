"""Multi-host runtime helpers (single-process equivalence; real multi-host
needs a pod — the contract is that one process degrades exactly to the
local mesh path, ref: SURVEY §2.9 comm backend)."""
import numpy as np
import pytest

import jax

from filodb_tpu.parallel import multihost
from filodb_tpu.parallel.mesh import device_put_packed, pack_shards


def test_initialize_single_process_is_noop():
    multihost.initialize(num_processes=1)     # must not raise or connect


def test_global_mesh_shapes():
    mesh = multihost.global_mesh(n_shard=4, n_time=2)
    assert mesh.shape == {"shard": 4, "time": 2}
    with pytest.raises(ValueError):
        multihost.global_mesh(n_shard=64, n_time=64)


def test_multihost_put_matches_local_put():
    """Under one process device_put_packed_multihost must produce arrays
    identical to the local path — same shardings, same values."""
    rng = np.random.default_rng(0)
    blocks = []
    for d in range(4):
        ts = np.arange(12, dtype=np.int32)[None, :].repeat(3, 0)
        vals = rng.normal(size=(3, 12))
        labels = [{"_ns_": f"App-{i % 2}", "inst": f"d{d}-{i}"}
                  for i in range(3)]
        blocks.append((ts, vals, labels))
    packed = pack_shards(blocks, by=("_ns_",), base_ms=0)
    mesh = multihost.global_mesh(n_shard=4, n_time=2)
    a = device_put_packed(packed, mesh)
    b = multihost.device_put_packed_multihost(packed, mesh)
    for name in ("ts_off", "values", "group_ids"):
        x, y = getattr(a, name), getattr(b, name)
        assert x.sharding == y.sharding, name
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_multihost_mesh_runs_spmd_agg():
    """The global-mesh arrays drive the same SPMD program end to end."""
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    from filodb_tpu.ingest.generator import counter_batch
    from filodb_tpu.core.index import Equals
    from filodb_tpu.ops.timewindow import make_window_ends
    START = 1_600_000_000_000
    ms = TimeSeriesMemStore()
    ms.setup("prometheus", 0)
    ms.setup("prometheus", 1)
    b = counter_batch(8, 120, start_ms=START)
    ms.ingest("prometheus", 0, b, offset=1)
    mesh = multihost.global_mesh(n_shard=2, n_time=2)
    from test_mesh import mesh_agg, shard_blocks
    end = START + 119 * 10_000
    blocks = shard_blocks(ms, [Equals("_metric_", "request_total")], START,
                          end, counter=True)
    p = multihost.device_put_packed_multihost(
        pack_shards(blocks, by=("_ns_",), base_ms=START, precorrected=True),
        mesh)
    wends = make_window_ends(START + 400_000, end, 60_000)
    out = mesh_agg(mesh, p, wends, range_ms=300_000, fn_name="rate",
                   agg_op="sum")
    assert np.isfinite(out).any()
    assert len(p.group_labels) >= 1
