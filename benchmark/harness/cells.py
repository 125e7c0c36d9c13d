"""Finding a cell's files by the names in the manifest.

The manifest is ``BENCHMARK.json`` at the root of the checkout, or (for
the CPU rehearsals only) a file of the same shape that also says
``"rehearsal": true``. A cell names its configuration and its traffic
mix; the configuration's file is the one the manifest lists, the mix is
``benchmark/traffic/<traffic>.json``, the loop of its kind is
``benchmark/kinds/<kind>.py`` and a per-layer metric's reader is
``benchmark/layers/<metric>.py``. Adding any of them is adding a file.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_manifest(path: str = "") -> dict:
    return load_json(path or os.path.join(ROOT, "BENCHMARK.json"))


def find_cell(manifest: dict, name: str) -> dict:
    """The cell, its configuration (file loaded) and its traffic mix."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; the manifest has "
                       f"{sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in manifest["configs"]
                 if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     cell["traffic"] + ".json"))
    return {"cell": cell, "config": config, "traffic": traffic}


def metrics_of(manifest: dict, group: str, cell_name: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that apply to a cell
    (an entry with no ``workloads`` key applies to every cell)."""
    return [m for m in manifest[group]
            if cell_name in m.get("workloads", [cell_name])]


def load_module(folder: str, name: str):
    """``benchmark/<folder>/<name>.py`` as a module; names may hold dots
    and dashes, so it is loaded by path."""
    path = os.path.join(BENCH, folder, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"{folder}/{name}.py is missing: every "
                                f"{folder[:-1]} is a file of its own")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_{name}".replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
