"""Finds the benchmark's pieces by the names `BENCHMARK.json` gives them.

* a cell: an entry of `workloads`;
* a configuration: the `file` of its entry in `configs`;
* a traffic mix: `bench/traffic/<name>.json`;
* a metric: `bench/metrics/<name>.py`, whose `read(run)` returns the
  metric's value, or None where the run holds nothing to read;
* a kind of query: `bench/kinds/<kind>.py`, named by the configuration's
  `workload.kind`, which builds a query's inputs (see `generate`).

A new cell, configuration, mix or metric is a new file and a new entry,
never an edit of this module.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
from typing import Callable, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bm: dict, name: str) -> dict:
    return _named(bm["workloads"], name, "workload")


def config(bm: dict, name: str, root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, _named(bm["configs"], name,
                                                "config")["file"]))


def traffic(name: str) -> dict:
    return _load_json(os.path.join(BENCH_DIR, "traffic", f"{name}.json"))


def _module(folder: str, name: str):
    path = os.path.join(BENCH_DIR, folder, f"{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_" + name.replace(".", "_"), path)
    if mod_spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no {folder} file for {name!r} at {path}")
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(name: str) -> Callable:
    """The `read` function of `metrics/<name>.py`."""
    return _module("metrics", name).read


@functools.lru_cache(maxsize=None)
def kind(name: str):
    """The module `kinds/<name>.py`."""
    return _module("kinds", name)


def metrics(bm: dict, cell: str, traced: bool) -> List[dict]:
    """The cell's end-to-end metrics, or with `traced` its per-layer
    ones: each metric whose `workloads` names the cell or that has no
    `workloads` key."""
    group = bm["per_layer"] if traced else bm["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]
