"""Spot markets and diurnal tariffs in the placement argmin.

Reproduces the Fig.-4-style deadline sweep under *time-dependent*
provider pricing: the same request batch is scheduled against a flat
3-provider portfolio, a spot-market random walk, and phase-shifted
diurnal tariffs — one batched vector-engine call via the
``price_traces=`` scenario axis — and then the serving layer's
``spot_frontier`` sweeps spot-market scenarios x SLA deadlines for the
prefill/decode pod.

Run from the repo root:
    PYTHONPATH=src python examples/spot_pricing.py
"""
import numpy as np

from repro.configs import get_config
from repro.core import (APPS, SkedulixScheduler, demo_portfolio,
                        diurnal_portfolio, spot_portfolio)
from repro.serving.hybrid import (HybridServingScheduler, elastic_portfolio,
                                  spot_elastic_traces)


def batch_pricing_sweep():
    dag = APPS["video"]
    rng = np.random.default_rng(0)
    J, M = 64, dag.num_stages
    P_priv = rng.lognormal(0.0, 0.5, (J, M)) * 2.0
    pred = dict(P_private=P_priv,
                P_public=P_priv * rng.uniform(0.8, 1.6, (J, M)),
                upload=rng.uniform(0.05, 0.3, (J, M)),
                download=rng.uniform(0.05, 0.3, (J, M)))
    act = {k: v * rng.lognormal(0, 0.05, v.shape) for k, v in pred.items()}
    base = float(P_priv.sum()) / float(dag.replicas.sum())
    grid = tuple(base * f for f in (0.3, 0.5, 0.8))
    horizon = float(max(grid))

    sched = SkedulixScheduler(dag, portfolio=demo_portfolio(3))
    markets = [None,                                    # flat (PR-2) pricing
               spot_portfolio(3, 6, horizon_s=horizon),
               diurnal_portfolio(3, period_s=horizon / 2)]
    names = ["flat", "spot", "diurnal"]
    res = sched.schedule_sweep(grid, pred=pred, act=act, orders=("spt",),
                               price_traces=markets)
    print("video app, 3 providers, deadline sweep x pricing sweep:")
    print(f"{'market':>8} {'C_max':>7} {'cost $':>9} {'offl':>5} "
          f"{'segments used':>14}")
    for s in range(res.num_scenarios):
        segs = np.unique(res.segment[s][res.segment[s] >= 0])
        print(f"{names[int(res.trace_idx[s])]:>8} {res.c_max[s]:7.2f} "
              f"{res.cost_usd[s]:9.5f} {int(res.n_offloaded_stages[s]):>5} "
              f"{str(segs.tolist()):>14}")


def serving_spot_frontier():
    h = HybridServingScheduler(get_config("llama3-8b"),
                               portfolio=elastic_portfolio(3))
    rng = np.random.default_rng(1)
    J = 96
    plen = rng.integers(512, 4096, J)
    ntok = rng.integers(64, 512, J)
    tot = h.lat.latencies(plen, ntok, None)["P_private"].sum() / 8.0
    grid = spot_elastic_traces(3, num_segments=6,
                               horizon_s=float(tot) * 0.6) + [None]
    f = h.spot_frontier(plen, ntok, grid,
                        c_max_grid=tuple(float(tot * x)
                                         for x in (0.15, 0.3, 0.6)))
    print("\nserving pod, spot elastic markets x SLA deadlines "
          "(frontier, cheapest first):")
    print(f.table())
    print("total overflow spend per market:",
          np.round(f.per_trace_cost(), 5).tolist())


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    batch_pricing_sweep()
    serving_spot_frontier()
