"""The harness finds every piece of a cell by name, and BENCHMARK.json
keeps to the shape the harness and its readers rely on."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import pytest  # noqa: E402

from chipbench import harness  # noqa: E402
from chipbench.tests import tinycells  # noqa: E402

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    c = harness.load_cell(cell, 2**31 + 99)
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    assert c.workload["config"] == entry["config"]
    assert c.workload["traffic"] == entry["traffic"]
    assert c.chips == entry["chips"]
    assert c.workload["why"] == entry["why"]
    assert harness.load_module("drivers", c.workload["driver"])
    assert set(c.workload["limits"])


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_config_file_is_the_named_one(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    with open(os.path.join(harness.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == config
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert cfg["model"]["precision"] == "float32"


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_reader_exists(metric):
    reader = harness.load_module("metrics", metric)
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert reader.UNIT == entry["unit"]
    assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}


def test_names_and_bounds():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}
    assert 1 <= BENCH["run_seconds"] <= 51


def test_a_full_check_of_24_cells_fits_its_time():
    # 2 + 14 runs a cell, each run_seconds + 60 s, 2 x 90 s of compiling a
    # cell and 1200 s spare: later PRs add cells under this run_seconds
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    e2e, layers = harness.metric_lists(cell)
    assert "setup_s" in e2e and len(e2e) >= 2
    assert layers
    assert {m["moves"] for m in layers.values()} <= set(e2e)


def test_a_workload_known_only_to_the_test_loads(tmp_path):
    tinycells.write_cell(str(tmp_path), {"x": 0.0})
    cell = tinycells.load(str(tmp_path), "tiny.replay")
    assert cell.config["nodes"] == 6
    assert harness.metric_lists("tiny.replay") is None
    with pytest.raises(FileNotFoundError):
        harness.load_cell("tiny.replay", 0)


def test_seeds_descend_from_the_cell_seed():
    a = harness.Cell("c", {}, {}, 2**33 + 1, 1)
    b = harness.Cell("c", {}, {}, 1, 1)
    assert a.seeds(2, 0, count=3) != b.seeds(2, 0, count=3)
    assert a.seeds(2, 0, count=3) == a.seeds(2, 0, count=3)
    assert all(0 <= s < 2**31 for s in a.seeds(1, count=8))


def test_readers_find_nothing_without_their_source():
    cell = harness.Cell("c", {"driver": "replay"}, {}, 0, 1)
    empty = harness.Run(cell, [], None)
    for m in BENCH["per_layer"]:
        assert harness.load_module("metrics", m["name"]).read(empty) is None


def test_no_accelerator_no_result():
    """On the CPU the command exits non-zero and prints nothing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run_cell.py"),
         "--workload", CELLS[0], "--seed", str(2**31 + 7), "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no accelerator" in proc.stderr
