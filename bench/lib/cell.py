"""Finds a cell's pieces by name: ``BENCHMARK.json`` names the pair
(configuration, traffic); the configuration, its collection, the traffic mix,
the correctness limits, each metric's reader and, where a configuration's
task types need semantics the plain reference lacks, its own reference
module sit in files of their own under ``bench/``, so a later cell,
configuration or metric is added by adding files and entries, never by
editing one that is there."""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    collection: List[Dict[str, Any]]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` as ``BENCHMARK.json`` names it."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    (entry,) = [c for c in spec["configs"] if c["name"] == w["config"]]
    if os.path.normpath(entry["file"]) != os.path.join("bench", "configs", f"{w['config']}.json"):
        raise ValueError(f"configuration {w['config']} is not at bench/configs/")
    return make_cell(name, w["config"], w["traffic"], int(w["chips"]), spec)


def make_cell(name: str, config_name: str, traffic: str, chips: int,
              spec: Dict[str, Any]) -> Cell:
    """A cell from its files: ``configs/<config_name>``, its collection,
    ``traffic/<traffic>`` and ``limits/<name>``, with the metrics ``spec``
    (``BENCHMARK.json``) gives it."""
    config = _json("configs", f"{config_name}.json")
    return Cell(
        name=name,
        chips=chips,
        config=config,
        collection=_json("collections", f"{config['collection']}.json")["dataflows"],
        traffic=_json("traffic", f"{traffic}.json"),
        limits=_json("limits", f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )


def _module(name: str, path: str) -> ModuleType:
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(metric: str) -> Callable[[Any], Any]:
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    return _module(f"bench_metric_{metric}", os.path.join(BENCH, "metrics", f"{metric}.py")).read


def reference_module(config: Dict[str, Any]) -> Optional[ModuleType]:
    """The configuration's own reference semantics: the module its
    ``"reference"`` key names (a path under ``bench/``, from the checkout's
    root), or None where it has none."""
    path = config.get("reference")
    if path is None:
        return None
    if not os.path.normpath(path).startswith("bench" + os.sep):
        raise ValueError(f"reference {path!r} is not under bench/")
    return _module(f"bench_reference_{config['name']}", os.path.join(ROOT, path))
