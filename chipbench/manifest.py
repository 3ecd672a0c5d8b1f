"""Finds a cell's files from the names in ``BENCHMARK.json``.

There is no registry: a cell names a configuration and a traffic mix, and
those names are file names under ``chipbench/configs`` and
``chipbench/traffic``; a metric's name is a file name under
``chipbench/layers``. A later PR adds files and manifest entries and edits
nothing that is here.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(CHECKOUT, "BENCHMARK.json")


class ManifestError(ValueError):
    """The manifest or one of the files it names does not hold together."""


def _load_json(path: str) -> Any:
    if not os.path.isfile(path):
        raise ManifestError(f"{path} does not exist")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it resolves to."""

    name: str
    chips: int
    why: str
    config_name: str
    config: Dict[str, Any]          # chipbench/configs/<config>.json
    traffic_name: str
    traffic: Dict[str, Any]         # chipbench/traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]   # manifest entries this cell reports
    per_layer: List[Dict[str, Any]]


def load_manifest(path: str = MANIFEST) -> Dict[str, Any]:
    return _load_json(path)


def _reported_in(metric: Dict[str, Any], cell_name: str) -> bool:
    cells = metric.get("workloads")
    return cells is None or cell_name in cells


def resolve_cell(name: str, manifest: Optional[Dict[str, Any]] = None,
                 bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    manifest = load_manifest() if manifest is None else manifest
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if len(entries) != 1:
        raise ManifestError(
            f"workload {name!r} is named {len(entries)} times in the "
            f"manifest; known: {[w['name'] for w in manifest['workloads']]}")
    entry = entries[0]
    configs = {c["name"]: c for c in manifest["configs"]}
    if entry["config"] not in configs:
        raise ManifestError(f"workload {name!r} names configuration "
                            f"{entry['config']!r}, which the manifest lacks")
    config_file = os.path.join(
        os.path.dirname(bench_dir), configs[entry["config"]]["file"])
    traffic_file = os.path.join(bench_dir, "traffic",
                                entry["traffic"] + ".json")
    end_to_end = [m for m in manifest["end_to_end"]
                  if _reported_in(m, name)]
    moved = {m["name"] for m in end_to_end}
    per_layer = [m for m in manifest["per_layer"]
                 if _reported_in(m, name) and m["moves"] in moved]
    return Cell(name=name, chips=int(entry["chips"]), why=entry["why"],
                config_name=entry["config"], config=_load_json(config_file),
                traffic_name=entry["traffic"],
                traffic=_load_json(traffic_file),
                end_to_end=end_to_end, per_layer=per_layer)


def layer_reader(metric: str, bench_dir: str = BENCH_DIR
                 ) -> Callable[[Dict[str, Any]], Optional[float]]:
    """The reader of per-layer metric ``metric``:
    ``chipbench/layers/<metric>.json`` names a module and a function, and
    may give it keyword arguments (the trace or span names it reads)."""
    spec = _load_json(os.path.join(bench_dir, "layers", metric + ".json"))
    module = importlib.import_module(spec["module"])
    function = getattr(module, spec["function"])
    kwargs = spec.get("args", {})
    return lambda facts: function(facts, **kwargs)


def load_object(path: str) -> Any:
    """``"package.module:name"`` -> the object."""
    module, _, attr = path.partition(":")
    return getattr(importlib.import_module(module), attr)
