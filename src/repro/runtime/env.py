"""Process-level JAX setup shared by every entry point that compiles.

The train and serve CLIs, the device proxy (``proxy_entry``) and the
proxy-host daemon call :func:`enable_compile_cache` before their first
compile, so each process of a run — a resumed trainer, a respawned proxy —
finds what an earlier one compiled instead of compiling the full-width
step again from cold.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax._src import xla_bridge

# <checkout>/.jax_cache: a fixed path (git-ignored), because the cache
# directory is part of what a later process must find again
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone. Otherwise the cache lives in :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def device_report() -> dict | None:
    """{"platform", "kind", "count"} of the devices this process's JAX
    drives, or None while no backend has started (nothing in this process
    has touched a device)."""
    if not xla_bridge.backends_are_initialized():
        return None
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def keep_off_device() -> None:
    """Pin this process's JAX to the host CPU.

    For an application whose device state lives in a proxy process: the
    proxy needs the accelerator, and one chip serves one process. Spawned
    proxies read ``JAX_PLATFORMS`` from the environment, which this leaves
    unchanged.
    """
    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "a JAX backend is already running in this process, so it may "
            "hold the accelerator its device proxy needs"
        )
    jax.config.update("jax_platforms", "cpu")
