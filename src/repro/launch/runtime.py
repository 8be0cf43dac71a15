"""Process-level set-up shared by the entry points (``chip_smoke.py``, the
benchmarks, ``scripts/dev_smoke.py``, the examples).

Nothing here runs at import: an entry point calls these from its own
start-up, so importing a library module never touches JAX's global
configuration.

  * ``enable_compile_cache`` — JAX's persistent compilation cache.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left exactly as it is; otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache`` (listed in ``.gitignore``).  The path is
    part of every entry's key, so it is never made from a temporary
    name, a pid or the time.
  * ``cpu_child_env`` — the environment of a child process that runs on
    forced host CPU devices.  A chip belongs to one process: a parent
    that holds a TPU backend would leave a chip-seeking child failing or
    hanging, so the helper refuses unless this process runs on the CPU,
    and pins the child to the CPU explicitly.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEVICE_FLAG = "--xla_force_host_platform_device_count"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def cpu_child_env(devices: int) -> dict:
    """``os.environ`` for a child pinned to ``devices`` forced host CPU
    devices (``JAX_PLATFORMS=cpu``, any stale device-count flag replaced).
    Raises when this process holds a non-CPU backend."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"refusing to start a JAX child process: this process holds the "
            f"{backend!r} backend, and forced-host-device children are CPU "
            "runs — run the parent with JAX_PLATFORMS=cpu")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    keep = [f for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith(DEVICE_FLAG)]
    env["XLA_FLAGS"] = " ".join(keep + [f"{DEVICE_FLAG}={int(devices)}"])
    return env
