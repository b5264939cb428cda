"""chip_smoke.py's contract, as far as a machine without a chip can hold it,
and the one compile-cache rule (config.apply_jax_runtime)."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*argv, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "FILODB_TPU_FUSED_INTERPRET",
                         "FILODB_TPU_FORCE_SHARDED_MIRROR")}
    full.update(env)
    p = subprocess.run([sys.executable, SMOKE, *argv], cwd=REPO, env=full,
                       capture_output=True, text=True, timeout=600)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, lines


def test_rehearsal_runs_end_to_end_and_names_the_cpu():
    p, lines = _run_smoke("--rehearse", "--series", "1024")
    assert p.returncode == 0, p.stderr[-2000:]
    last = json.loads(lines[-1])
    assert last == {"ok": True, "device": {"platform": "cpu",
                                           "kind": last["device"]["kind"],
                                           "count": 1}}
    phases = [json.loads(ln) for ln in lines[:-1]]
    queries = {ph["name"]: ph for ph in phases if ph["phase"] == "query"}
    # every query was checked, the fused kernel served the uniform grid,
    # and the acknowledged write was read back
    for name in ("rate", "increase", "sum_over_time(gauge)", "rate-instant"):
        assert queries[name]["fused_kernel"], name
        assert queries[name]["route_warm"]["leaf_fused_kernel_total"] >= 1
    assert queries["rate-after-write"]["acknowledged_write_visible"]
    assert queries["read-back-selector"]["checked_vs_written_values"]
    assert all(ph["rehearsal"] for ph in phases if ph["phase"] == "start")


def test_without_a_chip_it_fails_and_prints_no_ok():
    p, lines = _run_smoke(JAX_PLATFORMS="cpu")
    assert p.returncode != 0
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert len(lines) == 1          # no phase ran


_CACHE_PROBE = """
import json, os, sys
import jax
from filodb_tpu.config import FilodbSettings, apply_jax_runtime
cfg = FilodbSettings()
if len(sys.argv) > 1:
    cfg.jax_compile_cache_dir = sys.argv[1]
try:
    got = apply_jax_runtime(cfg)
except OSError as e:
    got = "raised " + type(e).__name__
print(json.dumps([got, jax.config.jax_compilation_cache_dir]))
"""


def _cache_probe(cwd, *argv, **env):
    """apply_jax_runtime in a fresh interpreter (it configures jax for the
    whole process): -> [returned path, what jax was configured with]."""
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(env, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", _CACHE_PROBE, *argv],
                       cwd=cwd, env=full, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_one_compile_cache_rule(tmp_path):
    default = os.path.join(REPO, ".filodb_jax_cache")
    # the variable set: jax reads it itself, nothing is configured in code
    # (jax's own default for the option IS the variable) and nothing made
    env_dir = str(tmp_path / "e")
    assert _cache_probe(tmp_path, JAX_COMPILATION_CACHE_DIR=env_dir) == \
        [env_dir, env_dir]
    assert not os.path.exists(env_dir)
    # not set: one absolute path inside the checkout, whatever the cwd
    assert _cache_probe(tmp_path) == [default, default]
    assert _cache_probe(REPO) == [default, default]
    # a directory that cannot be created raises; empty disables
    blocker = tmp_path / "file"
    blocker.write_text("")
    assert _cache_probe(REPO, str(blocker / "sub"))[0].startswith("raised")
    assert _cache_probe(REPO, "") == [None, None]


def test_a_warmup_shape_the_compiler_refuses_fails_startup(monkeypatch):
    """Boot warm-up used to swallow compile failures in a daemon thread;
    a node deployed with a warm-up list must not serve without it."""
    import pytest

    from filodb_tpu.config import FilodbSettings
    from filodb_tpu.ops import pallas_fused
    from filodb_tpu.standalone import FiloServer
    from filodb_tpu.utils.metrics import registry

    def refuse(*shape):
        raise RuntimeError(f"compiler refused {shape}")
    monkeypatch.setattr(pallas_fused, "warmup_compile", refuse)
    cfg = FilodbSettings()
    cfg.warmup_shapes = "512x720x61x10"
    srv = FiloServer(config=cfg)
    errors = registry.counter("warmup_compile_errors")
    before = errors.value
    try:
        with pytest.raises(RuntimeError, match="compiler refused"):
            srv.start()
        assert errors.value == before + 1
    finally:
        srv.shutdown()
