"""BENCHMARK.json and the files it names: every cell resolves from data,
every metric has its reader, and a cell is added by files alone."""

import json
import os
import re
import shutil

import pytest

from benchmark import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def spec():
    return cells.benchmark_spec()


def test_every_cell_resolves_to_its_files(spec):
    for w in spec["workloads"]:
        cell = cells.find_cell(w["name"], spec=spec)
        assert cell.chips in (1, 4)
        assert cell.config["chips"] == cell.chips
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer


def test_every_metric_has_its_reader(spec):
    for m in (*spec["end_to_end"], *spec["per_layer"]):
        assert NAME.match(m["name"])
        assert os.path.isfile(os.path.join(cells.HERE, "metrics",
                                           m["name"] + ".py")), m["name"]


def test_the_contract_keys_and_limits(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = [w["name"] for w in spec["workloads"]]
    for m in spec["per_layer"]:
        assert set(m["workloads"]) <= set(names)
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(names) // 2)


def test_a_cell_is_added_by_files_alone(tmp_path, spec):
    """A new configuration and traffic mix, and an entry naming them, make
    a cell the harness finds with no code changed."""
    root = tmp_path / "checkout"
    shutil.copytree(cells.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((root / "benchmark/configs/elo_1v1.json").read_text())
    cfg.update(name="elo_1v1_wide", waiting=dict(cfg["waiting"], sd=450.0))
    (root / "benchmark/configs/elo_1v1_wide.json").write_text(
        json.dumps(cfg))
    mix = json.loads((root / "benchmark/traffic/search.json").read_text())
    (root / "benchmark/traffic/slow.json").write_text(
        json.dumps(dict(mix, rate=100.0)))
    new = json.loads(json.dumps(spec))
    new["configs"].append(dict(spec["configs"][0], name="elo_1v1_wide",
                               file="benchmark/configs/elo_1v1_wide.json"))
    new["workloads"].append({"name": "elo_1v1_wide.slow",
                             "config": "elo_1v1_wide", "traffic": "slow",
                             "chips": 1, "why": "a test cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    cell = cells.find_cell("elo_1v1_wide.slow", root=str(root))
    assert cell.traffic["rate"] == 100.0
    assert cell.config["waiting"]["sd"] == 450.0
    assert cell.end_to_end == ("setup_s",)
    with pytest.raises(KeyError):
        cells.find_cell("no_such.cell", root=str(root))
