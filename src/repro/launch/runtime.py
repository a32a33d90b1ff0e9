"""Process set-up shared by the entry points: the persistent compilation
cache and a line naming the devices JAX found.

Call these from a ``main()``, never at import time: tests import the
launchers and must keep JAX's defaults.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` is left alone: JAX already reads
    it. Otherwise the cache lives at a fixed directory inside the
    checkout (git-ignored), so every run from this checkout finds the
    programs earlier runs compiled.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def describe_devices() -> str:
    """Platform, kind and count of the devices JAX uses, so a run that
    silently fell back to the CPU says so in its output."""
    devices = jax.devices()
    return (f"platform={devices[0].platform} "
            f"kind={devices[0].device_kind!r} count={len(devices)}")
