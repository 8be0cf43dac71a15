"""Shared forced-host-device subprocess harness for multi-device tests.

The XLA host device count is fixed when jax initializes, so any test that
wants ``len(jax.devices()) > 1`` on a CPU box must run its body in a fresh
subprocess with ``--xla_force_host_platform_device_count`` in XLA_FLAGS.
Three test files grew their own copy of that boilerplate (env assembly,
PYTHONPATH splice, returncode/marker asserts); this module is the single
copy they now share.
"""
from __future__ import annotations

import os
import subprocess
import sys

from repro.launch.runtime import cpu_child_env


def forced_device_env(devices: int) -> dict:
    """A subprocess env with ``devices`` forced host CPU devices: repo
    ``src`` on PYTHONPATH, any stale device-count flag/override stripped."""
    env = cpu_child_env(devices)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), "..", "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    for k in ("REPRO_MESH_DEVICES", "REPRO_FORCE_DEVICES"):
        env.pop(k, None)
    return env


def run_forced_devices(script: str, devices: int = 4, marker: str = "OK",
                       timeout: int = 420) -> str:
    """Run ``script`` under ``devices`` forced host devices; assert clean
    exit and that ``marker`` was printed (the script's own success line —
    asserting on it catches scripts that die before their checks run).
    Returns stdout for extra assertions."""
    proc = subprocess.run([sys.executable, "-c", script],
                          env=forced_device_env(devices),
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert marker in proc.stdout, proc.stdout[-2000:]
    return proc.stdout
