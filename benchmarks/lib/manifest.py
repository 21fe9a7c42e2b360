"""Finding the benchmark's files by name.

Everything that belongs to one configuration, one cell, one traffic
mix, one driver or one per-layer metric is a file of its own:

    configs/<config>.json          sizes, source, what was changed
    workloads/<cell>.json          config, traffic, driver, chips, system
                                   settings, the metrics the cell reports
    traffic/<traffic>.json         parameters the one generator reads
    drivers/<driver>.py            run(cell, args) -> result
    layer_metrics/<metric>.json    layer, unit, moves, reader, its arguments
    readers/<reader>.py            read(obs, **arguments) -> number or None

``run.py`` holds no table of names; a later PR adds files and entries
of ``BENCHMARK.json`` and edits nothing that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def checkout_root(bench_dir: str = BENCH_DIR) -> str:
    return os.path.dirname(bench_dir)


def load_json(kind: str, name: str, bench_dir: str) -> Dict[str, Any]:
    if not NAME_RE.match(name):
        raise ValueError(f"{kind} name {name!r} has characters a name "
                         "may not have")
    path = os.path.join(bench_dir, kind, name + ".json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    with open(path) as f:
        return json.load(f)


def _load_module(kind: str, name: str, bench_dir: str):
    if not NAME_RE.match(name):
        raise ValueError(f"{kind} name {name!r} is not a name")
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def list_names(kind: str, bench_dir: str = BENCH_DIR) -> List[str]:
    d = os.path.join(bench_dir, kind)
    return sorted(
        os.path.splitext(f)[0] for f in os.listdir(d)
        if f.endswith((".json", ".py")) and not f.startswith("_")
    )


def load_cell(name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """A cell with its configuration, traffic and per-layer metric
    files read in: all the data one run needs."""
    cell = dict(load_json("workloads", name, bench_dir))
    cell["name"] = name
    cell["config_file"] = load_json("configs", cell["config"], bench_dir)
    cell["traffic_file"] = load_json("traffic", cell["traffic"], bench_dir)
    cell["layer_metric_files"] = {
        m: load_json("layer_metrics", m, bench_dir)
        for m in cell["per_layer"]
    }
    return cell


def load_driver(name: str, bench_dir: str = BENCH_DIR):
    return _load_module("drivers", name, bench_dir)


def load_reader(name: str, bench_dir: str = BENCH_DIR):
    return _load_module("readers", name, bench_dir)


def read_layer_metrics(cell: Dict[str, Any], obs: Dict[str, Any],
                       bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """Every per-layer metric of the cell through its own reader.  A
    reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out: Dict[str, Any] = {}
    for name, spec in cell["layer_metric_files"].items():
        reader = load_reader(spec["reader"], bench_dir)
        value = reader.read(obs, **spec.get("args", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out
