"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so multi-shard / multi-host sharding
logic is exercised without TPU hardware (the reference's analogue is the
multi-JVM test harness, ref: standalone/src/multi-jvm).  Environment variables
must be set before jax is imported anywhere.
"""
import os

# Force CPU: unit tests must never occupy a chip (one process holds it at a
# time, and the driver runs several workers) and need 8 virtual devices.
# jax.config as well as the env var: something may have imported jax first.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

# Float64 on CPU for exact-semantics conformance tests against the reference's
# double-precision math; the TPU runtime path uses float32 (see filodb_tpu.config).
jax.config.update("jax_enable_x64", True)

import numpy as np
import pytest


@pytest.fixture(scope="session")
def mesh8():
    from jax.sharding import Mesh
    devs = np.array(jax.devices("cpu")[:8]).reshape(8)
    return Mesh(devs, ("shard",))


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running tests (run by "
        "default; deselect with -m 'not slow')")
    config.addinivalue_line(
        "markers", "chaos: fault-injection / node-kill chaos tests "
        "(subprocess clusters, SIGKILL, wall-clock waits). Implies slow, "
        "so tier-1's -m 'not slow' excludes them; run explicitly with "
        "-m chaos or via `python -m bench.drills chaos`.")
    config.addinivalue_line(
        "markers", "multichip: multi-device equivalence tests (per-device "
        "fused dispatch, sharded DeviceMirror, partial merges). Auto-skip "
        "below 2 local devices so tier-1 stays green on 1-device boxes; "
        "this harness forces 8 virtual CPU devices, so they normally run.")
    config.addinivalue_line(
        "markers", "replication: chaos-style replication tests (multi-"
        "store clusters under live ingest+query traffic, handoff drills, "
        "wall-clock waits). Implies slow, so tier-1's -m 'not slow' "
        "excludes them; run explicitly with -m replication or via "
        "`python -m bench.drills replication`.")


def pytest_collection_modifyitems(config, items):
    # chaos implies slow: the tier-1 gate (-m 'not slow') must never pay
    # for subprocess spawn + SIGKILL + restart cycles
    few_devices = jax.local_device_count() < 2
    skip_multichip = pytest.mark.skip(
        reason="multichip tests need >= 2 local devices "
               "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    for item in items:
        if "chaos" in item.keywords and "slow" not in item.keywords:
            item.add_marker(pytest.mark.slow)
        if "replication" in item.keywords and "slow" not in item.keywords:
            item.add_marker(pytest.mark.slow)
        if few_devices and "multichip" in item.keywords:
            item.add_marker(skip_multichip)
