"""Process-level JAX setup shared by the launchers and ``chip_smoke.py``.

Importing this module does not import JAX, so ``emulate_host_devices`` can
run before the first JAX import, which is when XLA reads its flags.
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/.jax_cache: a fixed path inside the checkout (git-ignored).  The
# path is part of the persistent cache's key, so it never carries a temp
# name, a pid or a time.
REPO_CACHE_DIR = str(Path(os.path.abspath(__file__)).parents[3] / ".jax_cache")


def emulate_host_devices(n: int):
    """CPU emulation of an ``n``-device mesh: select the CPU platform and
    split it into ``n`` host devices.  Must run before JAX is imported."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n}").strip()


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile
    and return its directory: ``$JAX_COMPILATION_CACHE_DIR`` when set, else
    ``REPO_CACHE_DIR``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
