"""Ahead-of-time compiles for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX, and compiles for a chip that is
described rather than attached.  These tests compile the main path's
Pallas kernel — the SIC suffix scan — and the large-N engine around it,
and assert that the kernel is in the program as a Mosaic custom call
(``tpu_custom_call``): what the interpreter-mode tests cannot show is
whether Mosaic accepts the tiling, the dot and the scratch layout.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file.  The persistent compile
cache is off around these compiles (an entry written for a described
chip cannot be read back without one).
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import stackelberg as sb
from repro.kernels import ops
from repro.kernels.sic_suffix import sic_suffix_pallas


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("shape", [
    (64, 1024),     # K=64 rows × N=1024: the large-N throughput cell
    (1, 1024),      # one row, padded up to the 8-row block
    (8, 128),       # one service bucket: B=8 requests × nb=128 lanes
])
def test_suffix_kernel_compiles_for_v5e(shape, one_chip):
    kernel = jax.jit(lambda w: sic_suffix_pallas(w, block=128,
                                                 interpret=False))
    compiled = kernel.lower(_sds(shape, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_blocked_pallas_engine_compiles_for_v5e(one_chip, monkeypatch):
    """The jitted batched engine with ``sic_mode="blocked_pallas"`` at
    N=1024, K=64: the kernel sits inside the vmapped Jacobi while-loop.
    The host here is a CPU, so the platform test of ``kernels.ops`` is
    steered to the TPU branch for this compile only."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    k, n = 64, 1024
    phys = jax.tree_util.tree_map(lambda _: _sds((), one_chip),
                                  sb.GameConfig().physics())
    compiled = sb._batched_equilibrium_jit.lower(
        phys, _sds((k, n), one_chip), _sds((k, n), one_chip),
        _sds((k, n), one_chip), _sds((), one_chip), _sds((), one_chip),
        max_iter=20, inner="projected", sic_mode="blocked_pallas",
        shards=1).compile()
    assert "tpu_custom_call" in compiled.as_text()
