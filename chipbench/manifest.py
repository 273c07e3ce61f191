"""Resolve a cell of BENCHMARK.json to its data files, by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own under one of the manifest's
``paths``:

    <path>/configs/<config>.json   (the manifest's ``file`` names it)
    <path>/traffic/<traffic>.json
    <path>/layers/<metric>.json    a declaration for a built-in reducer
    <path>/layers/<metric>.py      or a reader: ``read(obs) -> float|None``
    <path>/archs/<arch>.py         the architecture a configuration's
                                   ``"arch"`` names (README.md, "The
                                   architecture interface")

A later PR adds a cell, a configuration, a traffic mix, a metric or an
architecture as new files plus new manifest entries; nothing here names
any of them.
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


#: What ``archs/<arch>.py`` exposes for each entry point (a
#: configuration's ``kind``); chipbench/README.md says what each is.
ARCH_INTERFACE = {
    "serve": ("decode_symbol", "data_shapes", "make_params",
              "reference_logits", "LOGIT_TOL", "costs"),
    "fit": ("symbol", "pool", "PARSER_FLAGS", "UPDATED_PARAM",
            "reference_loss", "LOSS_TOL", "costs"),
}

#: What an architecture MAY state beside it (README.md has the table);
#: one that states neither is read as 1 and None.
ARCH_OPTIONAL = {"serve": ("decode_step_len", "mask_token")}


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    arch_file: str          # archs/<config["arch"]>.py, found not loaded
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
    out.reader = _load_file("layer", code).read
    return out


def _load_file(kind, path):
    stem = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_" + stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_arch(cell):
    """The cell's architecture module. It imports the program, so a
    runner loads it where it imports the program - after the
    configuration's ``env`` is in place, inside ``setup_s`` - and
    ``resolve`` only finds it."""
    mod = _load_file("arch", cell.arch_file)
    kind = cell.config["kind"]
    missing = [n for n in ARCH_INTERFACE[kind] if not hasattr(mod, n)]
    if missing:
        raise ManifestError(
            f"{cell.arch_file} lacks {missing} of the {kind!r} "
            f"interface {list(ARCH_INTERFACE[kind])}")
    return mod


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
    config_file = configs[w["config"]]["file"]
    config = _read_json(os.path.join(root, config_file))
    if not config.get("arch"):
        raise ManifestError(
            f"configuration {w['config']!r} ({config_file}) lacks the key "
            f"\"arch\": the name of its architecture, archs/<arch>.py")
    arch_file = _find(root, paths, "archs", config["arch"] + ".py")
    if arch_file is None:
        raise ManifestError(f"architecture {config['arch']!r}: no "
                            f"archs/{config['arch']}.py under {paths}")
    traffic_path = _find(root, paths, "traffic", w["traffic"] + ".json")
    if traffic_path is None:
        raise ManifestError(f"traffic mix {w['traffic']!r}: no "
                            f"traffic/{w['traffic']}.json under {paths}")
    cell = Cell(workload, int(w["chips"]), config, _read_json(traffic_path),
                arch_file)
    cell.end_to_end = [Metric(m["name"], m["unit"], m["source"])
                       for m in manifest["end_to_end"]
                       if _in_cell(m, workload)]
    cell.per_layer = [_layer_metric(root, paths, m)
                      for m in manifest["per_layer"]
                      if _in_cell(m, workload)]
    return cell
