"""Process-level setup: the chip smoke test's refusal to run without a TPU,
the compile-cache location, and the CPU host-device emulation switch."""
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_cpu():
    """Without a TPU the smoke script exits nonzero within seconds, prints
    no result line and builds no model."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")], env=env,
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert time.time() - t0 < 60
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr
    for model_work in ("serving", "arch=", "compile cache"):
        assert model_work not in proc.stdout


def test_compile_cache_dir(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins; without it the cache sits at one
    fixed directory inside the checkout on every call."""
    import jax
    from repro.launch.runtime import REPO_CACHE_DIR, enable_compile_cache
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
        assert enable_compile_cache() == "/cache/from/env"
        assert jax.config.jax_compilation_cache_dir == "/cache/from/env"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = enable_compile_cache()
        assert first == enable_compile_cache() == REPO_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == REPO_CACHE_DIR
        assert os.path.dirname(REPO_CACHE_DIR) == ROOT
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_emulate_host_devices_selects_cpu(monkeypatch):
    from repro.launch.runtime import emulate_host_devices
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=x")
    emulate_host_devices(4)
    assert os.environ["JAX_PLATFORMS"] == "cpu"
    assert os.environ["XLA_FLAGS"] == (
        "--xla_dump_to=x --xla_force_host_platform_device_count=4")
