"""Process-level JAX settings shared by the entry points."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path: a directory named after a temporary, a pid or the time
# would start empty on every run and never hit
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first
    compile and return its directory: ``$JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else ``.jax_cache`` at the root of
    the checkout."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def refuse_on_tpu(tool: str) -> None:
    """CPU tools that fan work out to child processes cannot run on a
    TPU host: the parent already holds the chip, so each child would
    fail or hang on the device lock.  Refuse before spawning."""
    if jax.default_backend() == "tpu":
        raise SystemExit(
            f"{tool} spawns child processes that need JAX and is a CPU "
            f"tool: run it with JAX_PLATFORMS=cpu, not on the TPU "
            f"(chip_smoke.py is the on-chip check)")
