"""Backend decisions in one place: which platform runs the work, whether
Pallas kernels go through the interpreter, and where compiled programs are
cached.

Every device-dependent default in the repo (Pallas `interpret=`, the
batched fitness's `use_pallas`) reads `platform()`, so a TPU run never
quietly takes a CPU branch: interpret mode is chosen only when the work
really lands on the CPU.

    >>> isinstance(pallas_interpret(), bool)
    True
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, checkout-relative: the path is part of the persistent cache's key,
# so a directory derived from a temp name, a pid or the time never hits
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def platform() -> str:
    """Platform of the device that new arrays and jitted calls land on: the
    active `jax.default_device` override if any, else the default backend."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def pallas_interpret() -> bool:
    """Default `interpret=` for Pallas calls: the interpreter only on CPU."""
    return platform() == "cpu"


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is already JAX's choice and is
    left alone; otherwise the cache lives in `<checkout>/.jax_cache`. Either
    way every program is cached, however small or quick to compile. Called
    by entry points only, never at import time."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
