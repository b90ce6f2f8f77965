"""Fault injection, retries and reliability frontiers.

Schedules the Fig.-4-style deadline sweep under injected chaos —
seeded invocation failures, a provider outage window, mid-stage kills —
with a retry policy (exponential backoff, re-placement with failed
providers masked, private fallback, per-job abandonment), as one
batched vector-engine call via the ``faults=`` scenario axis. Then the
serving layer's ``reliability_frontier`` sweeps fault configs x SLA
deadlines for the prefill/decode pod, and ``serve_online`` rides out a
full provider outage by degrading gracefully instead of crashing.

Run from the repo root:
    PYTHONPATH=src python examples/reliability_frontier.py
"""
import numpy as np

from repro.configs import get_config
from repro.core import (APPS, FaultModel, RetryPolicy, SkedulixScheduler,
                        demo_portfolio)
from repro.serving.hybrid import HybridServingScheduler, elastic_portfolio


def batch_chaos_sweep():
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
    grid = tuple(base * f for f in (0.3, 0.5))
    horizon = float(max(grid))

    chaos = FaultModel.from_rate(
        0.35, J, M, max_attempts=3, seed=7,
        outages=((0, 0.1 * horizon, 0.4 * horizon),), kill_frac=0.6)
    retry = RetryPolicy(max_attempts=3, backoff_s=0.3, jitter_frac=0.3)

    sched = SkedulixScheduler(dag, portfolio=demo_portfolio(3))
    res = sched.schedule_sweep(grid, pred=pred, act=act, orders=("spt",),
                               faults=[None, 0.15, chaos], retry=retry)
    names = ["fault-free", "rate 0.15", "chaos+outage"]
    print("video app, 3 providers, deadline sweep x fault sweep:")
    print(f"{'faults':>12} {'C_max':>7} {'cost $':>9} {'offl':>5} "
          f"{'attempts':>8} {'failed':>6} {'abandoned':>9}")
    for s in range(res.num_scenarios):
        print(f"{names[int(res.fault_idx[s])]:>12} {res.c_max[s]:7.2f} "
              f"{res.cost_usd[s]:9.5f} {int(res.n_offloaded_stages[s]):>5} "
              f"{int(res.attempts[s].sum()):>8} "
              f"{int(res.failed[s].sum()):>6} "
              f"{int(res.abandoned[s].sum()):>9}")


def serving_reliability_frontier():
    h = HybridServingScheduler(get_config("llama3-8b"),
                               portfolio=elastic_portfolio(3))
    rng = np.random.default_rng(1)
    J = 96
    plen = rng.integers(512, 4096, J)
    ntok = rng.integers(64, 512, J)
    tot = h.lat.latencies(plen, ntok, None)["P_private"].sum() / 8.0
    chaos = FaultModel.from_rate(0.3, J, 3, max_attempts=3, seed=3,
                                 outages=((0, 0.0, float(tot) * 0.2),))
    f = h.reliability_frontier(
        plen, ntok, fault_grid=[None, 0.1, chaos],
        c_max_grid=tuple(float(tot * x) for x in (0.15, 0.3, 0.6)),
        retry=RetryPolicy(max_attempts=3, backoff_s=0.2))
    print("\nserving pod, fault configs x SLA deadlines "
          "(frontier, cheapest first):")
    print(f.table())


def online_full_outage():
    h = HybridServingScheduler(get_config("llama3-8b"),
                               portfolio=elastic_portfolio(3))
    rng = np.random.default_rng(2)
    J = 48
    plen = rng.integers(256, 2048, J)
    ntok = rng.integers(32, 256, J)
    # every elastic provider dark for the whole stream: degraded mode
    fm = FaultModel.from_rate(0.2, J, 3, max_attempts=3, seed=5,
                              outages=tuple((p, 0.0, 1e9)
                                            for p in range(3)))
    rep = h.serve_online(plen, ntok, "poisson:4.0", sla_s=3.0,
                         replan_every_s=1.0, faults=fm,
                         retry=RetryPolicy(max_attempts=3))
    s = rep.summary()
    print("\nonline stream through a full elastic outage "
          "(graceful degradation):")
    for k in ("sla_attainment", "sla_attainment_served", "abandoned_frac",
              "offload_frac", "cost_usd"):
        print(f"  {k:>22}: {s[k]:.4f}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    batch_chaos_sweep()
    serving_reliability_frontier()
    online_full_outage()
