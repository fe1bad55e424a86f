"""From a workload name in ``BENCHMARK.json`` to the files that define it.

The harness is driven by data: a cell is an entry of ``workloads``; its
configuration is ``configs[].file`` (whose ``app`` names its
application under ``benchmark/apps/``), its traffic mix is
``benchmark/mixes/<traffic>.json``, its own parameters (the offered rate
found by a sweep) are the optional ``benchmark/cells/<name>.json``, and
each per-layer metric is ``benchmark/layers/<metric>.json`` naming a
reader under ``benchmark/readers/`` and its parameters.  A later PR adds
a cell, a mix or a layer metric as new files plus entries; nothing here
is edited.  A name that resolves to no file is an error, never a
default.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class ManifestError(Exception):
    """The manifest names something that is not there."""


def _load_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise ManifestError(f"{what}: no file at {path}")
    with open(path, encoding="utf-8") as f:
        try:
            return json.load(f)
        except json.JSONDecodeError as e:
            raise ManifestError(f"{what}: {path} is not JSON: {e}") from e


@dataclasses.dataclass
class LayerMetric:
    name: str
    unit: str
    source: str
    layer: str
    moves: str
    reader: str          # file name under benchmark/readers/
    params: dict

    def read(self, obs):
        """The reader's value for this run, or None when it found
        nothing to read (the harness then leaves the metric out)."""
        path = os.path.join(HERE, "readers", self.reader)
        if not os.path.isfile(path):
            raise ManifestError(
                f"per-layer metric {self.name}: no reader at {path}")
        spec = importlib.util.spec_from_file_location(
            "benchmark_reader_" + self.reader.replace(".", "_"), path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read(obs, self.params)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict         # the configuration file, as it is run
    config_file: str
    traffic_name: str
    traffic: dict
    params: dict         # benchmark/cells/<name>.json, or {}
    end_to_end: list[dict]
    per_layer: list[LayerMetric]


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(root: str, manifest_path: str, workload: str) -> Cell:
    """The cell ``workload`` of the manifest at ``manifest_path``
    (relative to ``root``), with every file it names loaded."""
    manifest = _load_json(os.path.join(root, manifest_path), "manifest")
    cells = {w["name"]: w for w in manifest.get("workloads", [])}
    if workload not in cells:
        raise ManifestError(
            f"workload {workload!r} is not in {manifest_path} "
            f"(it has {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest.get("configs", [])}
    if w["config"] not in configs:
        raise ManifestError(
            f"workload {workload}: configuration {w['config']!r} is not "
            f"in {manifest_path}")
    config_file = configs[w["config"]]["file"]
    config = _load_json(os.path.join(root, config_file),
                        f"configuration {w['config']}")
    traffic = _load_json(
        os.path.join(HERE, "mixes", w["traffic"] + ".json"),
        f"traffic mix {w['traffic']}")
    cell_file = os.path.join(HERE, "cells", workload + ".json")
    params = _load_json(cell_file, f"cell {workload}") \
        if os.path.isfile(cell_file) else {}
    end_to_end = [m for m in manifest.get("end_to_end", [])
                  if _applies(m, workload)]
    reported = {m["name"] for m in end_to_end}
    per_layer = []
    for m in manifest.get("per_layer", []):
        if not _applies(m, workload):
            continue
        if m["moves"] not in reported:
            raise ManifestError(
                f"per-layer metric {m['name']} moves {m['moves']}, which "
                f"cell {workload} does not report")
        spec = _load_json(
            os.path.join(HERE, "layers", m["name"] + ".json"),
            f"per-layer metric {m['name']}")
        per_layer.append(LayerMetric(
            name=m["name"], unit=m["unit"], source=m["source"],
            layer=m["layer"], moves=m["moves"], reader=spec["reader"],
            params=spec.get("params", {})))
    return Cell(name=workload, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                config_file=config_file, traffic_name=w["traffic"],
                traffic=traffic, params=params, end_to_end=end_to_end,
                per_layer=per_layer)
