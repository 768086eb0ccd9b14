"""Finding a cell's files by name.

A cell ``<config>.<mix>`` of ``BENCHMARK.json`` is assembled from:

* ``configs/<config>.json`` -- the model as it is run (sizes, dtype,
  ``max_batch``, ``max_seq_len``, ``mesh_shape``, source, cuts);
* ``mixes/<mix>.json`` -- traffic parameters; its ``kind`` names the
  generator ``mixes/<kind>.py``;
* ``cells/<cell>.json`` -- what is fixed per cell (offered rate, the
  correctness limit and sample);
* ``metrics/<metric>.py`` -- one reader per per-layer metric.

Each is looked up first under ``<root>/bench`` and then beside this file,
so a test can add a throwaway file in a temporary root.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional, Sequence

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    mix_name: str
    mix: Dict[str, Any]
    params: Dict[str, Any]          # cells/<cell>.json
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    search: Sequence[str]


def search_dirs(root: str) -> List[str]:
    dirs = [os.path.join(root, "bench"), HERE]
    out = []
    for d in dirs:
        d = os.path.abspath(d)
        if d not in out:
            out.append(d)
    return out


def find_file(search: Sequence[str], sub: str, name: str, ext: str) -> str:
    tried = []
    for d in search:
        path = os.path.join(d, sub, name + ext)
        tried.append(path)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {sub}/{name}{ext}; looked at {tried}")


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(search: Sequence[str], sub: str, name: str):
    """Import ``<sub>/<name>.py`` by path (names may hold dots)."""
    path = find_file(search, sub, name, ".py")
    mod_name = "bench_" + sub + "_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(metric: Dict[str, Any], cell: str,
            reported: Optional[Sequence[str]] = None) -> bool:
    """A metric with ``workloads`` holds in the cells it lists; a per-layer
    metric without it holds wherever the metric it moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if reported is not None and "moves" in metric:
        return metric["moves"] in reported
    return True


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"cells: {sorted(by_name)}")
    wl = by_name[name]
    search = search_dirs(root)
    config = load_json(find_file(search, "configs", wl["config"], ".json"))
    mix = load_json(find_file(search, "mixes", wl["traffic"], ".json"))
    params = load_json(find_file(search, "cells", name, ".json"))
    e2e = [m for m in bench["end_to_end"] if applies(m, name)]
    reported = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if applies(m, name, reported)]
    return Cell(name=name, chips=int(wl["chips"]), config_name=wl["config"],
                config=config, mix_name=wl["traffic"], mix=mix,
                params=params, end_to_end=e2e, per_layer=per_layer,
                search=search)
