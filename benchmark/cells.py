"""Find a cell, its configuration and its traffic mix by name.

Everything a cell needs is data: ``BENCHMARK.json`` names the cell's
configuration and traffic, and each lives in a file of its own under
``benchmark/configs/`` and ``benchmark/traffic/``. Adding a cell is adding
those files and an entry; nothing here changes.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any

#: The benchmark's own directory and the checkout it sits in.
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json``, resolved to its data."""

    name: str
    chips: int
    config_name: str
    config: dict[str, Any]
    traffic_name: str
    traffic: dict[str, Any]
    #: End-to-end metric names this cell reports (``setup_s`` included).
    end_to_end: tuple[str, ...]
    #: Per-layer metric names this cell reports in a traced run.
    per_layer: tuple[str, ...]


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark_spec(root: str = ROOT) -> dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict[str, Any], cell: str) -> bool:
    """A metric without ``workloads`` applies to every cell."""
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: str = ROOT,
              spec: dict[str, Any] | None = None) -> Cell:
    """The cell called ``name``, with its configuration and traffic loaded
    from their files. Raises ``KeyError`` for an unknown cell."""
    spec = spec if spec is not None else benchmark_spec(root)
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(work)})")
    w = work[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(root, os.path.basename(HERE), "traffic",
                                     f"{w['traffic']}.json"))
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"], config=config,
        traffic_name=w["traffic"], traffic=traffic,
        end_to_end=tuple(m["name"] for m in spec["end_to_end"]
                         if _applies(m, name)),
        per_layer=tuple(m["name"] for m in spec["per_layer"]
                        if _applies(m, name)),
    )


def metric_units(spec: dict[str, Any]) -> dict[str, str]:
    return {m["name"]: m["unit"]
            for m in (*spec["end_to_end"], *spec["per_layer"])}
