"""The benchmark's one traffic generator.

A configuration (`configs/<name>.json`) fixes the deployment: the
applications with their stages, pools and batch sizes, the public
cloud's prices, the latency model and the deadline grid. A traffic mix
(`traffic/<name>.json`) fixes how that deployment is queried: how many
applications one query covers, which orders, how many seeded pool
sizings. `build` turns the two and a seed into queries.

Every query draws its own inputs from `(seed, stream, query index)`, so
no two queries of a run share an input (the system's cache of prepared
sweeps never serves one) and the same seed gives the same queries.
Stream 0 is the measured window, stream 1 the warm-up.

How a query's inputs are drawn is the configuration's `workload.kind`:
the module `kinds/<kind>.py` (found by `spec.kind`) gives

* `tasks(config, mix, rng, app_names)`: the query's tasks (app, pred,
  act, release, c_max_grid, orders, replicas) and its page size;
* `warm(config, mix, rng, app_names)`: such (tasks, page size) pairs
  that together compile every shape family the window's queries of
  that application set can compile to, and no other;
* `small(config)`: the configuration at a size a CPU test runs.

A new kind of query is a new file there.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import spec

INPUT_KEYS = ("P_private", "P_public", "upload", "download")


@dataclasses.dataclass
class Scenario:
    """One scenario of a query, in the order the system returns them."""

    task: int      # index of the application in the query
    index: int     # scenario index within that application's grid
    order: str
    c_max: float
    replicas: Tuple[int, ...]


@dataclasses.dataclass
class Query:
    tasks: List[dict]          # app, pred, act, release, c_max_grid,
                               # orders, replicas (None = the DAG's own)
    chunk_jobs: Optional[int]
    scenarios: List[Scenario]
    work: Dict[str, int]       # stages, invocations, scenarios


def seed_words(seed: int) -> List[int]:
    """A whole number of any sign and size as SeedSequence entropy."""
    return [int(seed) & (2 ** 64 - 1), 1 if int(seed) < 0 else 0]


def app_replicas(app: dict) -> np.ndarray:
    return np.array([int(s["replicas"]) for s in app["stages"]])


def _grid(task: dict, app: dict) -> List[Tuple[str, float, Tuple[int, ...]]]:
    """The task's scenarios in the system's order: orders, then
    deadlines, then pool sizings."""
    pools = (task["replicas"] if task["replicas"] is not None
             else [app_replicas(app)])
    return [(o, float(c), tuple(int(x) for x in r))
            for o in task["orders"] for c in task["c_max_grid"]
            for r in pools]


def query(config: dict, tasks: List[dict],
          chunk: Optional[int]) -> Query:
    """The query of these tasks, paged at `chunk` jobs where not None."""
    apps = config["apps"]
    scenarios = []
    stages = invocations = 0
    for ti, t in enumerate(tasks):
        app = apps[t["app"]]
        J, M = t["pred"]["P_private"].shape
        grid = _grid(t, app)
        scenarios += [Scenario(ti, s, o, c, r)
                      for s, (o, c, r) in enumerate(grid)]
        stages += len(grid) * J * M
        invocations += len(grid) * J
    return Query(tasks, chunk, scenarios,
                 dict(stages=stages, invocations=invocations,
                      scenarios=len(scenarios)))


def _app_sets(config: dict, mix: dict) -> List[List[str]]:
    names = sorted(config["apps"])
    per = mix.get("apps_per_query", "all")
    if per == "all":
        return [names]
    return [names[i:i + int(per)] for i in range(0, len(names), int(per))]


def _kind(config: dict):
    return spec.kind(config["workload"]["kind"])


def build(config: dict, mix: dict, seed: int, n: int,
          stream: int = 0) -> List[Query]:
    """`n` queries; query i covers the application set i mod the sets."""
    sets = _app_sets(config, mix)
    kind = _kind(config)
    return [query(config, *kind.tasks(
                config, mix,
                np.random.default_rng(seed_words(seed) + [stream, i]),
                sets[i % len(sets)]))
            for i in range(n)]


def warmup(config: dict, mix: dict, seed: int) -> List[Query]:
    """The queries that compile every shape family the window's queries
    compile to, and no other: the kind's `warm` of each application set,
    drawn from stream 1 of the seed."""
    kind = _kind(config)
    return [query(config, tasks, chunk)
            for i, names in enumerate(_app_sets(config, mix))
            for tasks, chunk in kind.warm(
                config, mix,
                np.random.default_rng(seed_words(seed) + [1, i]), names)]


def small(config: dict) -> dict:
    """A copy of the configuration at a size a CPU test runs."""
    return _kind(config).small(json.loads(json.dumps(config)))


def rounded(query: Query) -> Query:
    """The query with every float input rounded to float32 and back."""
    def rnd(x):
        return None if x is None else np.asarray(x).astype(
            np.float32).astype(np.float64)

    tasks = [dict(t, pred={k: rnd(v) for k, v in t["pred"].items()},
                  act={k: rnd(v) for k, v in t["act"].items()},
                  release=rnd(t["release"]))
             for t in query.tasks]
    return dataclasses.replace(query, tasks=tasks)
