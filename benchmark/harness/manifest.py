"""BENCHMARK.json -> what one run needs, every piece found by name.

A cell names a configuration and a traffic mix; the traffic file names
a driver and (for serving) a generator; a metric names its reader; what
belongs to one cell alone — one configuration under one mix, such as
the rate found for it on the chip — sits in `cells/<cell>.json`. Each is
one file under `benchmark/`, loaded from its path, so a later PR adds a
cell by adding files and manifest entries and edits nothing here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST_PATH = os.path.join(ROOT, "BENCHMARK.json")
NAME_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")

# kind of piece -> directory under benchmark/ that holds `<name>.py`
MODULE_DIRS = {
    "builder": "builders",
    "driver": "drivers",
    "generator": "generators",
    "reference": "reference",
    "per_layer": "layer_metrics",
    "end_to_end": "e2e_metrics",
}


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    params: dict       # `cells/<name>.json`; {} for a cell that has none
    end_to_end: tuple  # metric entries of the manifest that apply here
    per_layer: tuple


def load_manifest(path: str = MANIFEST_PATH) -> dict:
    with open(path) as f:
        return json.load(f)


def load_json(root: str, relpath: str) -> dict:
    with open(os.path.join(root, relpath)) as f:
        return json.load(f)


def module_path(kind: str, name: str, root: str = ROOT) -> str:
    if not NAME_RE.match(name):
        raise ValueError(f"{kind} name {name!r} is not a plain name")
    return os.path.join(root, "benchmark", MODULE_DIRS[kind], name + ".py")


def load_module(kind: str, name: str, root: str = ROOT):
    """The module `benchmark/<dir of kind>/<name>.py`, loaded from its
    path (so any plain name works, not only Python identifiers)."""
    path = module_path(kind, name, root)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve_cell(manifest: dict, name: str, root: str = ROOT) -> Cell:
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if name not in by_name:
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json "
            f"(have: {', '.join(sorted(by_name))})"
        )
    w = by_name[name]
    cfg_entry = next(
        c for c in manifest["configs"] if c["name"] == w["config"]
    )
    for plain in (name, w["traffic"]):
        if not NAME_RE.match(plain):
            raise ValueError(f"{plain!r} is not a plain name")
    own = os.path.join("benchmark", "cells", name + ".json")
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=w["config"],
        config=load_json(root, cfg_entry["file"]),
        traffic_name=w["traffic"],
        traffic=load_json(
            root, os.path.join("benchmark", "traffic", w["traffic"] + ".json")
        ),
        params=(
            load_json(root, own)
            if os.path.isfile(os.path.join(root, own)) else {}
        ),
        end_to_end=tuple(
            m for m in manifest["end_to_end"] if applies(m, name)
        ),
        per_layer=tuple(
            m for m in manifest["per_layer"] if applies(m, name)
        ),
    )
