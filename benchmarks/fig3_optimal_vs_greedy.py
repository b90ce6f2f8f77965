"""Fig. 3: optimal (MILP) vs SPT/HCF greedy vs all-public, 30 jobs.

Paper result: greedy within 28-34% of optimal cost; both meet C_max;
all-public is faster but far more expensive.
"""
from __future__ import annotations

import numpy as np

from repro.core import simulate_all_public, solve_milp

from .common import app_setup, print_rows, row, timed


def run(full: bool = False, milp_time_s: float = 60.0, n_jobs: int = 30):
    rows = []
    for app in ("matrix", "video"):
        spec, sched, pred, act, tr, te = app_setup(app, full)
        J = min(n_jobs, pred["P_private"].shape[0])
        if app == "video" and not full:
            J = min(J, 12)           # MILP size guard for the quick pass
        p = {k: v[:J] for k, v in pred.items()}
        a = {k: v[:J] for k, v in act.items()}
        pub = simulate_all_public(spec.dag, p, a)
        priv_time = p["P_private"].sum() / spec.dag.replicas.sum()
        # keep C_max above the all-public floor (otherwise the MILP is
        # trivially infeasible at reduced scale)
        c_max = float(max(priv_time * 0.75, pub.makespan * 1.3))

        m, t_m = timed(solve_milp, spec.dag, a["P_private"], a["P_public"],
                       c_max, a["upload"], a["download"],
                       time_limit_s=milp_time_s)
        for order in ("spt", "hcf"):
            rep, t_g = timed(sched.schedule_batch, c_max=c_max, pred=p,
                             act=a, order=order)
            r = rep.result
            ratio = (r.cost_usd / m.cost_usd) if (m.feasible and
                                                  m.cost_usd > 0) else np.nan
            rows.append(row(
                f"fig3/{app}/{order}", t_g / J * 1e6,
                f"cost=${r.cost_usd:.6f};makespan={r.makespan:.2f};"
                f"cmax={c_max:.2f};vs_opt={ratio:.2f}x"))
        opt_cost = m.cost_usd if m.feasible else float("nan")
        rows.append(row(f"fig3/{app}/optimal", t_m / J * 1e6,
                        f"cost=${opt_cost:.6f};gap={m.mip_gap:.3f}"))
        rows.append(row(f"fig3/{app}/all_public", 0.0,
                        f"cost=${pub.cost_usd:.6f};makespan={pub.makespan:.2f}"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys
    print_rows(run(full="--full" in sys.argv))
