"""Find a cell, its configuration, its traffic mix and its queue kind by
name.

``BENCHMARK.json`` names the cell's configuration and traffic, and each
lives in a file of its own under ``benchmark/configs/`` and
``benchmark/traffic/``. A configuration may name its queue's kind
(``"kind": "<name>"``): a module of its own, ``benchmark/kinds/<name>.py``,
that gives the requests' extra body fields and the plain reference that
decides ``correct`` (see ``load_kind``). A configuration without a kind is
a 1v1 rating-distance queue, judged by ``benchmark/reference.py``. Adding
a cell is adding those files and an entry; nothing here changes.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os
import re
from dataclasses import dataclass
from types import ModuleType
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
    #: The checkout the cell was found in, where its kind's file lies.
    root: str = ROOT

    @property
    def kind(self) -> ModuleType:
        """The module of the configuration's queue kind."""
        return load_kind(self.config.get("kind"), self.root)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


#: What every kind module defines. ``fields(rng, n, config)``: the extra
#: body fields of ``n`` requests, ``{body key: column}``, drawn from
#: ``rng`` (``{}`` for none). ``LIMITS``: the numbers compared and their
#: limits. ``compare(data, control=False)``: the numbers of one run's
#: ``reference.RunData``. ``failures(numbers)``, ``report(numbers)``: as
#: in ``benchmark/reference.py``.
KIND_API = ("fields", "LIMITS", "compare", "failures", "report")
_KIND_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@functools.cache
def load_kind(name: str | None, root: str = ROOT) -> ModuleType:
    """The queue kind ``name``: the module ``benchmark/kinds/<name>.py``
    under ``root``, loaded once; ``benchmark.reference`` (1v1) for None."""
    if name is None:
        from benchmark import reference

        return reference
    if not _KIND_NAME.match(name):
        raise ValueError(f"bad kind name {name!r}")
    path = os.path.join(root, os.path.basename(HERE), "kinds", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_kind_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [a for a in KIND_API if not hasattr(mod, a)]
    if missing:
        raise AttributeError(f"kind {name!r} ({path}) lacks {missing}")
    return mod


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
        root=root,
    )


def metric_units(spec: dict[str, Any]) -> dict[str, str]:
    return {m["name"]: m["unit"]
            for m in (*spec["end_to_end"], *spec["per_layer"])}
