"""What the metric readers under `metrics/` share.

A reader gets the run (`harness.Run`) and returns a number, or None
where the run holds nothing for it to read.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

#: substring of the XLA module name of the engine's compiled program
#: (`jax.jit(jax.vmap(run_one))` in `vectorsim._engine_fn`)
ENGINE_MODULE = "run_one"


def total(run, key: str) -> float:
    """Sum of one work count over the window's queries."""
    return float(sum(r["work"][key] for r in run.records))


def rate(run, key: str) -> Optional[float]:
    """A work count over all the window's seconds."""
    return total(run, key) / run.window_s if run.window_s > 0 else None


def stat_mean(run, group: str, key: str) -> Optional[float]:
    """Mean per query of one of the program's own counters or spans."""
    vals = [r["stats"][group][key] for r in run.records
            if key in r["stats"].get(group, {})]
    return float(np.mean(vals)) if vals else None


def ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else 1e3 * x


def engine_device_ms(run) -> Optional[float]:
    """Device time of the engine's program per engine call (a fused
    group of a sweep, or a page of a paged run), over the traced calls."""
    if (run.trace is None or run.trace["dropped"]
            or not run.trace.get("engine_calls")):
        return None
    t = sum(v for k, v in run.trace["modules"].items()
            if ENGINE_MODULE in k)
    return 1e3 * t / run.trace["engine_calls"] if t > 0 else None


def device_idle_pct(run) -> Optional[float]:
    if (run.trace is None or run.trace["dropped"]
            or not run.trace["n_devices"]):
        return None
    return run.trace["idle_pct"]
