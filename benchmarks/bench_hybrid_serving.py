"""Beyond-paper: the Skedulix scheduler driving LLM request batches over a
reserved pod + elastic overflow (serving/hybrid.py), for three archs.

Each arch also runs an SLA *sweep* — both priority orders across a grid of
deadlines — through ``HybridServingScheduler.schedule_sweep``; with
``--engine vector`` (default) the whole grid is one batched jit-engine
call, with ``--engine des`` it replays serially through the event-heap
reference. A second sweep runs over a 3-pool elastic *portfolio*
(``elastic_portfolio``): overflow lands on the cheapest feasible pool per
request stage, exercising the multi-provider engine path end-to-end.
"""
from __future__ import annotations

import numpy as np

from repro.configs import get_config
from repro.serving import HybridServingScheduler
from repro.serving.hybrid import elastic_portfolio

from .common import print_rows, row, timed


def run(full: bool = False, engine: str = "vector"):
    rows = []
    J = 128 if full else 48
    n_grid = 4
    for arch in ("llama3-8b", "recurrentgemma-9b", "arctic-480b"):
        h = HybridServingScheduler(get_config(arch))
        h.fit_perf_models(n_train=256 if full else 128)
        rng = np.random.default_rng(7)
        plen = rng.integers(128, 4096, J)
        ntok = rng.integers(32, 512, J)
        pub, priv = h.baselines(plen, ntok)
        c_max = priv.makespan * 0.5
        rep, t = timed(h.schedule, plen, ntok, c_max=c_max, order="spt")
        r = rep.result
        rows.append(row(
            f"serve/{arch}", t / J * 1e6,
            f"speedup={priv.makespan / r.makespan:.2f}x;"
            f"cost_pct_of_public={100 * r.cost_usd / pub.cost_usd:.1f}%;"
            f"met={int(r.makespan <= c_max * 1.1)};"
            f"offloaded={r.n_offloaded_stages}"))
        # SLA sweep: both orders x a deadline grid, one batched call
        grid = tuple(float(priv.makespan * f)
                     for f in np.linspace(0.4, 0.85, n_grid))
        if engine == "vector":  # keep one-time jit compile out of the timing
            h.schedule_sweep(plen, ntok, grid, orders=("spt", "hcf"),
                             engine=engine)
        sweep, ts = timed(h.schedule_sweep, plen, ntok, grid,
                          orders=("spt", "hcf"), engine=engine)
        met = int(np.sum(sweep.makespan <= np.asarray(sweep.c_max) * 1.1))
        rows.append(row(
            f"serve/{arch}/sweep[{engine}]",
            ts / sweep.num_scenarios / J * 1e6,
            f"scenarios={sweep.num_scenarios};met={met};"
            f"cost_spread={sweep.cost_usd.min():.4f}"
            f"..{sweep.cost_usd.max():.4f}"))
        # same SLA sweep over a 3-pool elastic portfolio: overflow goes to
        # the cheapest feasible pool per stage (multi-provider engine path)
        hp = HybridServingScheduler(get_config(arch),
                                    portfolio=elastic_portfolio(3))
        hp.perf_model = h.perf_model  # reuse the fitted ridge models
        if engine == "vector":
            hp.schedule_sweep(plen, ntok, grid, orders=("spt", "hcf"),
                              engine=engine)
        psweep, tp = timed(hp.schedule_sweep, plen, ntok, grid,
                           orders=("spt", "hcf"), engine=engine)
        pools = np.unique(psweep.provider[psweep.provider >= 0]).size
        rows.append(row(
            f"serve/{arch}/sweep[{engine},3pool]",
            tp / psweep.num_scenarios / J * 1e6,
            f"scenarios={psweep.num_scenarios};pools_used={pools};"
            f"cost_spread={psweep.cost_usd.min():.4f}"
            f"..{psweep.cost_usd.max():.4f}"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys
    eng = "des" if "--engine=des" in sys.argv or "des" in sys.argv else "vector"
    print_rows(run(full="--full" in sys.argv, engine=eng))
