"""Process-level set-up of the entry points: the persistent compile cache,
the CPU-only children, the chip smoke test's refusal off the chip, and a
warning-free import of the engines on the installed JAX."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import jax

from repro.launch import runtime

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_env_is_left_alone(monkeypatch, tmp_path,
                                         restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path))
    assert runtime.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch,
                                                       restore_cache_dir):
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    want = str(ROOT / ".jax_cache")
    assert runtime.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert runtime.enable_compile_cache() == want      # same path every call


def test_cpu_child_env_pins_children_to_cpu(monkeypatch):
    jax.default_backend()      # initialise before XLA_FLAGS is changed
    other = "--xla_backend_optimization_level=1"
    monkeypatch.setenv("XLA_FLAGS", f"{runtime.DEVICE_FLAG}=8 {other}")
    env = runtime.cpu_child_env(4)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"].split() == [other, f"{runtime.DEVICE_FLAG}=4"]


def test_cpu_child_env_refuses_under_a_chip(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="refusing"):
        runtime.cpu_child_env(4)


def _run(args, cwd, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_fails_without_a_tpu():
    proc = _run(["chip_smoke.py"], cwd=ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "[device] FAIL" in proc.stdout


def test_chip_smoke_fails_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    proc = _run(["chip_smoke.py"], cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_engine_import_has_no_deprecation_warning():
    """``jax.experimental.shard_map`` warns as deprecated on the installed
    JAX; the engines use ``jax.shard_map``."""
    proc = _run(["-W", "error::DeprecationWarning", "-c",
                 "import repro.core, repro.launch.alloc_serve, "
                 "repro.models.moe_ep"],
                cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
