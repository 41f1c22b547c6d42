"""BENCHMARK.json and the files it names: every cell resolves from data,
every metric has its reader, and a cell, its queue kind included, is added
by files alone."""

import hashlib
import json
import os
import re
import shutil

import numpy as np
import pytest

from benchmark import cells, reference
from benchmark.reference import RunData
from benchmark.run import result_line
from benchmark.serve import RunRecord

#: The test's queue kind, copied into a checkout as a file of its own.
KIND_FILE = os.path.join(os.path.dirname(__file__), "kind_team_probe.py")

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


def _harness_digest(root) -> dict[str, str]:
    return {os.path.relpath(os.path.join(d, f), root):
            hashlib.sha256(open(os.path.join(d, f), "rb").read()).hexdigest()
            for d, _, files in os.walk(root) if "__pycache__" not in d
            for f in files if f.endswith(".py")}


def test_a_cell_is_added_by_files_alone(tmp_path, spec):
    """A new configuration, traffic mix and queue kind, and entries naming
    them, make cells the harness finds with no code changed: the kind's
    own file decides ``correct`` for the configuration that names it."""
    root = tmp_path / "checkout"
    shutil.copytree(cells.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    harness = _harness_digest(root / "benchmark")
    cfg = json.loads((root / "benchmark/configs/elo_1v1.json").read_text())
    cfg.update(name="elo_1v1_wide", waiting=dict(cfg["waiting"], sd=450.0))
    (root / "benchmark/configs/elo_1v1_wide.json").write_text(
        json.dumps(cfg))
    team = json.loads(json.dumps(cfg))
    team.update(name="team_test", kind="team_test",
                pinned={"region": "eu", "game_mode": "ranked"})
    team["service"]["queues"][0]["team_size"] = 5
    (root / "benchmark/configs/team_test.json").write_text(json.dumps(team))
    (root / "benchmark/kinds").mkdir()
    shutil.copy(KIND_FILE, root / "benchmark/kinds/team_test.py")
    mix = json.loads((root / "benchmark/traffic/search.json").read_text())
    (root / "benchmark/traffic/slow.json").write_text(
        json.dumps(dict(mix, rate=100.0)))
    new = json.loads(json.dumps(spec))
    for name in ("elo_1v1_wide", "team_test"):
        new["configs"].append(dict(spec["configs"][0], name=name,
                                   file=f"benchmark/configs/{name}.json"))
        new["workloads"].append({"name": f"{name}.slow", "config": name,
                                 "traffic": "slow", "chips": 1,
                                 "why": "a test cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    cell = cells.find_cell("elo_1v1_wide.slow", root=str(root))
    assert cell.traffic["rate"] == 100.0
    assert cell.config["waiting"]["sd"] == 450.0
    assert cell.end_to_end == ("setup_s",)
    assert cell.kind is reference
    with pytest.raises(KeyError):
        cells.find_cell("no_such.cell", root=str(root))

    cell = cells.find_cell("team_test.slow", root=str(root))
    kind = cell.kind
    assert kind.__file__ == str(root / "benchmark/kinds/team_test.py")
    fields = kind.fields(np.random.default_rng(0), 3, cell.config)
    assert fields["region"].tolist() == ["eu"] * 3
    ids = [f"w{i}" for i in range(10)]
    data = RunData(100.0, ids, np.full(10, 1500.0), [], np.zeros(0),
                   np.zeros(0), [], 10, 10, team_size=5,
                   pool_fields=kind.fields(None, 10, cell.config))
    run = RunRecord(setup_s=1.0, seconds=1.0, attempted=0, due=np.zeros(0),
                    match_ms=np.zeros(0), matches_in_window=0, failed=0,
                    data=data)
    line = result_line(cell, run, kind.compare(data), False, {},
                       cells.metric_units(new))
    assert line["correct"] is True
    assert list(line["checks"]) == list(kind.LIMITS)
    assert "mixed_group" in line["checks"]
    after = _harness_digest(root / "benchmark")
    assert after.pop("kinds/team_test.py")
    assert after == harness
