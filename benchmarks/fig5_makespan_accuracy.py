"""Fig. 5: achieved makespan vs requested C_max.

Paper result: absolute error < 3.5% (matrix), < 1.5% (video) — driven by
performance-model accuracy.
"""
from __future__ import annotations

import numpy as np

from repro.core import simulate_all_private

from .common import app_setup, print_rows, row, timed


def run(full: bool = False, n_points: int = 4):
    rows = []
    for app in ("matrix", "video"):
        spec, sched, pred, act, tr, te = app_setup(app, full)
        priv = simulate_all_private(spec.dag, pred, act)
        for order in ("spt", "hcf"):
            errs = []
            t_all = 0.0
            for f in np.linspace(0.5, 0.9, n_points):
                c_max = float(priv.makespan * f)
                rep, t = timed(sched.schedule_batch, c_max=c_max,
                               pred=pred, act=act, order=order)
                t_all += t
                errs.append(abs(rep.result.makespan - c_max) / c_max * 100)
            J = pred["P_private"].shape[0]
            rows.append(row(
                f"fig5/{app}/{order}", t_all / n_points / J * 1e6,
                f"mean_abs_err%={np.mean(errs):.2f};max={np.max(errs):.2f}"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys
    print_rows(run(full="--full" in sys.argv))
