"""BENCHMARK.json and the files it names: every cell, configuration and
per-layer metric is found by name, and a new one needs only new files."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import run

ROOT = run.ROOT
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert all((ROOT / p).is_dir() for p in SPEC["paths"])
    assert (ROOT / SPEC["command"][1]).is_file()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    spec = run.resolve(cell)
    names = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"], "every cell reports a per-layer metric"
    assert spec["driver"].is_file()
    assert all(p.is_file() for p in spec["readers"].values())
    assert spec["limits"], "every cell states its comparison limits"


@pytest.mark.parametrize("cell", CELLS)
def test_split_metric_shares_its_stem_reader(cell):
    """``device_idle.solve`` and ``device_idle.serve`` move different
    end-to-end metrics and share one reader, ``device_idle.py``."""
    readers = run.resolve(cell)["readers"]
    idle = [p.name for n, p in readers.items() if n.startswith("device_idle.")]
    assert idle == ["device_idle.py"]


def test_names_and_units():
    items = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] \
        + SPEC["per_layer"]
    for item in items:
        assert NAME.match(item["name"]), item["name"]
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    moves = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in moves for m in SPEC["per_layer"])
    for c in SPEC["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["reduced"] == c["reduced"]


def test_new_cell_needs_only_new_files(tmp_path):
    """A cell, a traffic mix and a per-layer metric added as new files and
    new entries of BENCHMARK.json, in a copy of the benchmark, are picked
    up by name: no file of the harness changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({"name": "paper_mc_n5_k256", "config": "paper_n5",
                              "traffic": "mc_k256", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "calls_per_window", "unit": "calls",
                              "better": "higher", "source": "program_counter",
                              "layer": "leader iteration",
                              "moves": "solves_per_s",
                              "workloads": ["paper_mc_n5_k256"]})
    for m in spec["end_to_end"]:
        if m["name"] == "solves_per_s":
            m["workloads"].append("paper_mc_n5_k256")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    traffic = json.loads((ROOT / "bench/traffic/mc_k1024.json").read_text())
    (tmp_path / "bench/traffic/mc_k256.json").write_text(
        json.dumps(dict(traffic, draws_per_call=256)))
    shutil.copy(ROOT / "bench/checks/paper_mc_n5.json",
                tmp_path / "bench/checks/paper_mc_n5_k256.json")
    (tmp_path / "bench/metrics/calls_per_window.py").write_text(
        "def read(run):\n    return run.counters.get('calls')\n")
    got = run.resolve("paper_mc_n5_k256", root=tmp_path)
    assert got["traffic"]["draws_per_call"] == 256
    assert "calls_per_window" in got["readers"]
    assert {m["name"] for m in got["end_to_end"]} == {"setup_s", "solves_per_s"}
    with pytest.raises(run.SpecError):
        run.resolve("no_such_cell", root=tmp_path)


def _bench(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper_mc_n5",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = _bench(ROOT)
    assert out.returncode != 0
    assert "{" not in out.stdout and "metrics" not in out.stdout


def test_benchmark_files_alone_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    own files, there is no program to measure: non-zero, no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _bench(tmp_path)
    assert out.returncode != 0
    assert "{" not in out.stdout
