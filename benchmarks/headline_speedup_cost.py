"""Headline claim: hybrid achieves up to 1.92x speedup over all-private at
40.5%% of all-public cost (matrix, C_max=400s); 1.65x / 39.5%% (video).
"""
from __future__ import annotations

from repro.core import simulate_all_private, simulate_all_public

from .common import app_setup, print_rows, row, timed

# paper's operating points: C_max as a fraction of the all-private makespan
# (400s/740s for matrix, 250s/407s for video)
_FRACS = {"matrix": 400.0 / 740.0, "video": 250.0 / 407.0}


def run(full: bool = False):
    rows = []
    for app in ("matrix", "video"):
        spec, sched, pred, act, tr, te = app_setup(app, full)
        priv = simulate_all_private(spec.dag, pred, act)
        pub = simulate_all_public(spec.dag, pred, act)
        c_max = float(priv.makespan * _FRACS[app])
        rep, t = timed(sched.schedule_batch, c_max=c_max, pred=pred,
                       act=act, order="spt")
        r = rep.result
        speedup = priv.makespan / r.makespan
        cost_pct = 100.0 * r.cost_usd / pub.cost_usd
        J = pred["P_private"].shape[0]
        rows.append(row(
            f"headline/{app}", t / J * 1e6,
            f"speedup={speedup:.2f}x;cost_pct_of_public={cost_pct:.1f}%;"
            f"met={int(r.met_deadline)};paper=1.92x@40.5%"
            if app == "matrix" else
            f"speedup={speedup:.2f}x;cost_pct_of_public={cost_pct:.1f}%;"
            f"met={int(r.met_deadline)};paper=1.65x@39.5%"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys
    print_rows(run(full="--full" in sys.argv))
