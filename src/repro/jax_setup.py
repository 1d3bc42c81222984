"""Process-level JAX set-up: which backend a CPU study uses, how many host
devices it sees, and where compiled programs are cached.

Nothing here imports JAX at module level: ``force_host_devices`` must run
before JAX loads, and ``use_compile_cache`` is called from an entry
point's ``main``, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

# <repo>/.jax_cache: a fixed path, because the path is part of the cache key
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def force_host_devices(n: int) -> None:
    """Pin this process to the CPU backend with ``n`` host devices, for the
    compile studies that lower a many-chip mesh without the chips.

    Sets ``JAX_PLATFORMS=cpu`` (so the study never takes an accelerator
    another process may hold) and appends
    ``--xla_force_host_platform_device_count=n`` to XLA_FLAGS, preserving
    whatever other flags are already set.  An existing device-count flag
    wins (the caller opted out).  Must run before JAX loads."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (flags + " " if flags else "") + \
        f"--xla_force_host_platform_device_count={n}"


def use_compile_cache() -> Optional[Path]:
    """Turn on JAX's persistent compilation cache for an entry point.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is
    set here (returns None).  Otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`, which is returned."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return COMPILE_CACHE_DIR
