"""Resolve a cell of BENCHMARK.json to its data files, by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under one of the manifest's
``paths``:

    <path>/configs/<config>.json   (the manifest's ``file`` names it)
    <path>/traffic/<traffic>.json
    <path>/layers/<metric>.json    a declaration for a built-in reducer
    <path>/layers/<metric>.py      or a reader: ``read(obs) -> float|None``

A later PR adds a cell, a configuration, a traffic mix or a metric as
new files plus new manifest entries; nothing here names any of them.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ManifestError(Exception):
    pass


@dataclass
class Metric:
    name: str
    unit: str
    source: str
    layer: str | None = None
    moves: str | None = None
    decl: dict | None = None        # layers/<name>.json
    reader: object | None = None    # layers/<name>.py: read(obs)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def load(path=None, root=ROOT):
    path = path or os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        return json.load(f)


def _read_json(path):
    with open(path) as f:
        return json.load(f)


def _find(root, paths, *parts):
    """The file under one of the manifest's ``paths``; a manifest kept
    elsewhere (tests, rehearsals) falls back on this package's own."""
    here = os.path.dirname(os.path.abspath(__file__))
    for base in [os.path.join(root, p) for p in paths] + [here]:
        cand = os.path.join(base, *parts)
        if os.path.exists(cand):
            return cand
    return None


def _in_cell(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def _layer_metric(root, paths, m):
    out = Metric(m["name"], m["unit"], m["source"], m.get("layer"),
                 m.get("moves"))
    decl = _find(root, paths, "layers", m["name"] + ".json")
    if decl is not None:
        out.decl = _read_json(decl)
        return out
    code = _find(root, paths, "layers", m["name"] + ".py")
    if code is None:
        raise ManifestError(
            f"per-layer metric {m['name']!r}: no layers/{m['name']}.json "
            f"or .py under {paths}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_layer_" + m["name"].replace(".", "_").replace("-", "_"),
        code)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out.reader = mod.read
    return out


def resolve(manifest, workload, root=ROOT):
    """The ``Cell`` named ``workload``: its configuration and traffic
    files read, its metrics listed, each per-layer metric with its
    declaration or reader."""
    paths = manifest["paths"]
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise ManifestError(f"no workload {workload!r}; the manifest has "
                            f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    if w["config"] not in configs:
        raise ManifestError(f"workload {workload!r} names configuration "
                            f"{w['config']!r}, which the manifest lacks")
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic_path = _find(root, paths, "traffic", w["traffic"] + ".json")
    if traffic_path is None:
        raise ManifestError(f"traffic mix {w['traffic']!r}: no "
                            f"traffic/{w['traffic']}.json under {paths}")
    cell = Cell(workload, int(w["chips"]), config, _read_json(traffic_path))
    cell.end_to_end = [Metric(m["name"], m["unit"], m["source"])
                       for m in manifest["end_to_end"]
                       if _in_cell(m, workload)]
    cell.per_layer = [_layer_metric(root, paths, m)
                      for m in manifest["per_layer"]
                      if _in_cell(m, workload)]
    return cell
