"""The comparison that decides `correct`.

Each checked scenario's result is compared with the plain reference
(`reference.simulate`) run on the same inputs, field by field:

* `decision_mismatches`: elements of the decision fields (placement,
  provider, replica, price segment, attempts, counters, abandonment,
  cold starts) that differ, plus float elements that are NaN on one
  side only, plus fields of the wrong shape (all their elements) and
  scenarios whose labels (order, deadline, pool) are not the ones
  asked for. The configuration's guarantee is that the engine's
  decisions are the reference's decisions, so its limit is 0.
* `float_gap`: the widest gap of a float field (makespan, cost, start,
  end, completion, queue wait), as |a - b| / (1 + |b|) with `b` the
  reference. Within 1 s this is an absolute gap, beyond it a relative
  one; a gap that is not finite reads 1. The system computes in
  float64; on a TPU float64 is emulated with float32 pairs, so the
  floats are not bit-identical there.

The copy follows the field lists of the system's own DES-vs-engine
check and adds the gap as one number with a limit of its own.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.reference import EXACT_FIELDS, FLOAT_FIELDS


def compare(got: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]
            ) -> Dict[str, float]:
    """{decision_mismatches, float_gap} of one scenario."""
    bad, gap = 0, 0.0
    for f in EXACT_FIELDS + FLOAT_FIELDS:
        a, b = np.asarray(got[f]), np.asarray(ref[f])
        if a.shape != b.shape:
            bad += max(a.size, b.size, 1)
            continue
        if f in FLOAT_FIELDS:
            a, b = a.astype(np.float64), b.astype(np.float64)
            na, nb = np.isnan(a), np.isnan(b)
            bad += int((na != nb).sum())
            both = ~(na | nb)
            if both.any():
                x, y = a[both], b[both]
                with np.errstate(invalid="ignore"):
                    d = np.where(x == y, 0.0,
                                 np.abs(x - y) / (1.0 + np.abs(y)))
                d = np.nan_to_num(d, nan=1.0, posinf=1.0)
                gap = max(gap, float(d.max()))
        else:
            bad += int((a != b).sum())
    return dict(decision_mismatches=bad, float_gap=gap)


def combine(parts: List[Dict[str, float]]) -> Dict[str, float]:
    """The worst of each number over the checked scenarios."""
    return dict(
        decision_mismatches=int(sum(p["decision_mismatches"] for p in parts)),
        float_gap=max([p["float_gap"] for p in parts] or [0.0]))


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True when every number is within its limit."""
    return all(numbers[k] <= limits[k] for k in limits)
