"""Finding things by name.

``BENCHMARK.json`` is the one registry: a cell names its configuration
and its traffic mix, a configuration names its file, a metric is a
module called after it.  Nothing here holds a list of what exists; a
later PR adds a file and an entry, and edits no file that is there.

    benchmarks/configs/<config>.json        sizes, as run (+ "rehearse")
    benchmarks/traffic/<traffic>.json       driver + the mix's parameters
    benchmarks/drivers/<driver>.py          run(ctx) -> record
    benchmarks/end_to_end/<metric>.py       read(record) -> number | None
    benchmarks/layer_metrics/<metric>.py    read(record) -> number | None
"""

from __future__ import annotations

import copy
import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    known = [c["name"] for c in bench["workloads"]]
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have {known}")


def _with_rehearsal(data: dict, rehearse: bool) -> dict:
    """``rehearse`` is a block of overrides for the CPU walk-through:
    top-level keys replace, nested groups merge one level deep."""
    data = copy.deepcopy(data)
    block = data.pop("rehearse", {})
    if rehearse:
        for key, value in block.items():
            if isinstance(value, dict) and isinstance(data.get(key), dict):
                data[key].update(value)
            else:
                data[key] = value
    return data


def load_config(bench: dict, name: str, rehearse: bool) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == name:
            data = load_json(os.path.join(ROOT, entry["file"]))
            return _with_rehearsal(data, rehearse)
    raise SystemExit(f"no config {name!r} in BENCHMARK.json")


def load_traffic(name: str, rehearse: bool) -> dict:
    path = os.path.join(BENCH_DIR, "traffic", f"{name}.json")
    return _with_rehearsal(load_json(path), rehearse)


def load_driver(name: str):
    return importlib.import_module(f"benchmarks.drivers.{name}")


def load_cell(name: str, rehearse: bool):
    """``(bench, cell, config, traffic)`` for the cell called ``name``."""
    bench = load_benchmark()
    cell = find_cell(bench, name)
    return (bench, cell, load_config(bench, cell["config"], rehearse),
            load_traffic(cell["traffic"], rehearse))


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: all
    that list it under ``workloads``, and all that have no such key."""
    return [
        m for m in bench[kind]
        if "workloads" not in m or cell in m["workloads"]
    ]


def _load_by_path(path: str):
    """Import one file by its path: a metric's name may hold a ``.`` or a
    ``-``, which a module name cannot."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_" + "".join(c if c.isalnum() else "_" for c in path), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(kind: str, name: str):
    """The metric's own reader module, ``<folder>/<name>.py``."""
    folder = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}[kind]
    return _load_by_path(os.path.join(BENCH_DIR, folder, f"{name}.py"))
