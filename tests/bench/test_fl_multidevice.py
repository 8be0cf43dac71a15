"""The FL training cell on four forced host devices at the small size
(C = 2 points x S = 4 seeds): the program lays the grid over the (1, 4)
(cfg, draw) mesh, its answers equal the same calls pinned to one device,
the window's grid passes the comparison, and the compiled text that
``fl_alloc_device_share`` reads holds the ``sic_power`` scope."""
from _multidevice import run_forced_devices

from bench import run

_SCRIPT = r"""
import os, sys
from pathlib import Path
root = Path(@ROOT@)
sys.path[:0] = [str(root), str(root / "tests" / "bench")]
import numpy as np
import jax
from bench import compare, program_trace, run
from fl_small import CELL, small
from repro.sharding import game_mesh

assert len(jax.devices()) == 4, jax.devices()
config, traffic = small(seeds=4)
assert game_mesh.grid_layout(2, 4) == (1, 4)
spec = run.resolve(CELL)
driver = run.load_module(spec["driver"], "fl_driver_mesh")
cell = driver.Cell(config, traffic, 2 ** 33 + 11, 0.2)
final, got = cell._call(0)
assert driver._devices(final) == 4
os.environ["REPRO_MESH_DEVICES"] = "1"
final1, one = cell._call(0)
del os.environ["REPRO_MESH_DEVICES"]
assert driver._devices(final1) == 1
for f in driver.FIELDS:
    np.testing.assert_allclose(got[f], one[f], rtol=1e-6, err_msg=f)
for k, v in final.params.items():
    np.testing.assert_allclose(np.asarray(v), np.asarray(final1.params[k]),
                               rtol=1e-5, atol=1e-7, err_msg=k)
window = cell.run(0.2)
assert window["counters"]["mesh_devices"] == 4
cell.collect()
ok, rows = compare.judge(cell.check(), spec["limits"])
assert ok, rows
scopes = program_trace.scope_map(driver.compiled_text(config, traffic))
assert any("sic_power" in path for path in scopes.values())
print("FL_MESH_OK")
"""


def test_fl_grid_on_four_devices_equals_one():
    run_forced_devices(_SCRIPT.replace("@ROOT@", repr(str(run.ROOT))),
                       marker="FL_MESH_OK", timeout=900)
