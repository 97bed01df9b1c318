"""Find a cell's parts by name: its entry in ``BENCHMARK.json``, its
configuration file, its traffic file and its per-layer metric readers.

Nothing here names a cell, a configuration, a traffic mix or a metric:
a later change adds one as new files plus an entry.
"""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class SpecError(Exception):
    pass


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(items, name: str, what: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    names = ", ".join(i["name"] for i in items)
    raise SpecError(f"no {what} named {name!r} (have: {names})")


def cell(bench: dict, name: str) -> dict:
    return _by_name(bench["workloads"], name, "workload")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    entry = _by_name(bench["configs"], name, "config")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def traffic(name: str) -> dict:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    if not os.path.exists(path):
        raise SpecError(f"no traffic file {path}")
    with open(path) as f:
        return json.load(f)


def _module(path: str, modname: str):
    if not os.path.exists(path):
        raise SpecError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(cfg: dict):
    """The configuration's plain reference, beside its file."""
    name = cfg["reference"]
    return _module(os.path.join(HERE, "configs", f"{name}.py"),
                   f"benchmark_reference_{name}")


def metric_reader(name: str):
    """A per-layer metric's reader: ``metrics/<name>.py`` with a
    ``read(ctx)`` that returns a number, or None when it finds nothing
    to read."""
    return _module(os.path.join(HERE, "metrics", f"{name}.py"),
                   "benchmark_metric_" + name.replace(".", "_"))


def end_to_end_for(bench: dict, cell_name: str) -> list:
    return [m for m in bench["end_to_end"]
            if "workloads" not in m or cell_name in m["workloads"]]


def per_layer_for(bench: dict, cell_name: str) -> list:
    """The per-layer metrics that list the cell under ``workloads``;
    every per-layer metric names its cells."""
    for m in bench["per_layer"]:
        if "workloads" not in m:
            raise SpecError(f"per-layer metric {m['name']!r} lists no "
                            f"workloads")
    return [m for m in bench["per_layer"] if cell_name in m["workloads"]]


def load(name: str, root: str = ROOT) -> tuple:
    """A cell by name: (benchmark, cell entry, configuration, traffic)."""
    bench = load_benchmark(root)
    entry = cell(bench, name)
    return bench, entry, config(bench, entry["config"], root), \
        traffic(entry["traffic"])
