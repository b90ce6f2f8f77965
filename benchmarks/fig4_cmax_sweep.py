"""Fig. 4: offloaded-function %% and total cost vs C_max, SPT vs HCF,
for all three applications.

Paper result: offloads decrease with deadline; HCF offloads more and (for
compute-heavy apps) costs 14-18% more than SPT; image app reverses.

``--engine vector`` (default) evaluates each app's whole (order x C_max)
grid as one batched call on the jit engine (``SkedulixScheduler.
schedule_sweep``); ``--engine des`` replays the grid serially through the
event-heap reference — identical numbers, the seed's code path.
"""
from __future__ import annotations

import numpy as np

from repro.core import simulate_all_private

from .common import app_setup, print_rows, row, timed


def run(full: bool = False, n_points: int = 5, engine: str = "vector"):
    rows = []
    for app in ("matrix", "video", "image"):
        spec, sched, pred, act, tr, te = app_setup(app, full)
        priv = simulate_all_private(spec.dag, pred, act)
        fracs = np.linspace(0.45, 0.95, n_points)
        c_grid = tuple(float(priv.makespan * f) for f in fracs)
        J = pred["P_private"].shape[0]
        if engine == "vector":  # keep one-time jit compile out of the timing
            sched.schedule_sweep(c_grid, pred=pred, act=act,
                                 orders=("spt",), engine=engine)
        for order in ("spt", "hcf"):
            rep, t = timed(sched.schedule_sweep, c_grid, pred=pred, act=act,
                           orders=(order,), engine=engine)
            costs = list(rep.cost_usd)
            offs = [100.0 * f for f in rep.offload_fraction]
            rows.append(row(
                f"fig4/{app}/{order}", t / n_points / J * 1e6,
                "off%=" + "|".join(f"{o:.0f}" for o in offs)
                + ";cost=" + "|".join(f"{c:.5f}" for c in costs)))
        # SPT-vs-HCF cost ratio averaged over the sweep (paper: 14-18%)
        rows.append(row(f"fig4/{app}/hcf_over_spt", 0.0,
                        _ratio(rows[-2], rows[-1])))
    return rows


def _ratio(spt_row, hcf_row) -> str:
    def costs(r):
        part = [p for p in r["derived"].split(";") if p.startswith("cost=")][0]
        return np.array([float(x) for x in part[5:].split("|")])
    s, h = costs(spt_row), costs(hcf_row)
    mask = s > 1e-12
    if not mask.any():
        return "ratio=nan"
    return f"ratio={float(np.mean(h[mask] / s[mask])):.3f}"


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys
    eng = "des" if "--engine=des" in sys.argv or "des" in sys.argv else "vector"
    print_rows(run(full="--full" in sys.argv, engine=eng))
