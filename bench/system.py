"""The system under test, as the benchmark drives it.

This is the only module of the benchmark that imports the program. It
builds the configuration's application DAGs and cost model with the
program's own types, runs a query through `sweep_scenarios` (the entry
point behind `SkedulixScheduler.schedule_sweep`, and behind
`schedule(..., chunk_jobs=)` for a paged stream), and reads the program's
own counters after each query.

With spans on, it also wraps the program's phases in profiler
annotations (`bench:<phase>`), so that the trace can say what the host
was doing while the device sat idle. The wrapping is installed only for
a traced run and removed afterwards.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Dict, List

import numpy as np

from bench.generate import Query

#: program phases wrapped in a traced run: span name -> (module, owner,
#: attribute). A phase the program no longer has is skipped.
PHASES = {
    "prep": ("repro.core.vectorsim", None, "_prep_sweep"),
    "plan": ("repro.core.vectorsim", "_Task", "init_plan"),
    "dispatch": ("repro.core.vectorsim", None, "_dispatch"),
    "finalize": ("repro.core.vectorsim", None, "_finalize"),
    "pack": ("repro.core.vectorsim", "_Task", "pack"),
}

RESULT_FIELDS = ("makespan", "cost_usd", "public_mask", "start", "end",
                 "completion", "n_offloaded_stages", "n_init_offloaded_jobs",
                 "per_stage_offloads", "provider", "replica", "segment",
                 "attempts", "failed", "abandoned", "queue_wait", "cold")


class System:
    def __init__(self, config: dict):
        from repro.core import vectorsim
        from repro.core.cost import CostModel
        from repro.core.dag import AppDAG, Stage

        self._vs = vectorsim
        self.dags = {
            name: AppDAG(name, tuple(
                Stage(s["name"], replicas=int(s["replicas"]),
                      mem_mb=float(s["mem_mb"])) for s in app["stages"]),
                tuple(tuple(e) for e in app["edges"]))
            for name, app in config["apps"].items()}
        pc = config["public_cloud"]
        self.cost_model = CostModel(quantum_ms=float(pc["quantum_ms"]),
                                    usd_per_gb_ms=float(pc["usd_per_gb_ms"]),
                                    min_quantums=float(pc["min_quantums"]))
        self.flags = config["scheduler"]

    def run(self, query: Query) -> List[object]:
        """The query's results, one per application, on the host."""
        tasks = []
        for t in query.tasks:
            task = dict(dag=self.dags[t["app"]], pred=t["pred"],
                        act=t["act"], c_max_grid=t["c_max_grid"],
                        orders=t["orders"])
            if t["release"] is not None:
                task["arrivals"] = t["release"]
            if t["replicas"] is not None:
                task["replicas"] = t["replicas"]
            tasks.append(task)
        return self._vs.sweep_scenarios(
            tasks, cost_model=self.cost_model,
            include_transfers=bool(self.flags["include_transfers"]),
            init_phase=bool(self.flags["init_phase"]),
            adaptive=bool(self.flags["adaptive"]),
            t0=float(self.flags["t0"]),
            init_window=self.flags.get("init_window_s"),
            chunk_jobs=query.chunk_jobs)

    def stats(self) -> Dict[str, dict]:
        """The program's counters of the query that just ran."""
        return dict(run=dict(self._vs._LAST_RUN_STATS),
                    page=dict(self._vs._LAST_PAGE_STATS))

    @staticmethod
    def labels(result, s: int) -> tuple:
        """(order, c_max, replicas) the result gives scenario `s`."""
        return (str(result.orders[s]), float(result.c_max[s]),
                tuple(int(x) for x in np.asarray(result.replicas[s])))

    @staticmethod
    def fields(result, s: int) -> Dict[str, np.ndarray]:
        """Scenario `s` of one application's result, field by field."""
        return {f: np.asarray(getattr(result, f))[s] for f in RESULT_FIELDS}

    @contextlib.contextmanager
    def spans(self, after_dispatch=None):
        """Annotate the program's phases in the profiler's trace, and
        call `after_dispatch` after each engine call returns."""
        import importlib

        import jax

        undo = []
        for span, (mod, owner, attr) in PHASES.items():
            target = importlib.import_module(mod)
            if owner is not None:
                target = getattr(target, owner, None)
            fn = getattr(target, attr, None) if target is not None else None
            if fn is None:
                continue

            after = after_dispatch if span == "dispatch" else None

            def wrapped(*a, _fn=fn, _name=f"bench:{span}", _after=after,
                        **kw):
                with jax.profiler.TraceAnnotation(_name):
                    out = _fn(*a, **kw)
                if _after is not None:
                    _after()
                return out

            functools.update_wrapper(wrapped, fn)
            setattr(target, attr, wrapped)
            undo.append((target, attr, fn))
        try:
            yield
        finally:
            for target, attr, fn in reversed(undo):
                setattr(target, attr, fn)
