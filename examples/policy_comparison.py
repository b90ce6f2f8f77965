"""Policy comparison demo: Fig. 4 as a pluggable-policy shoot-out.

The paper's Fig. 4 compares the hybrid greedy against private-only and
public-only baselines. With the policy harness the same question runs
as ONE batched sweep over any number of policies — here the paper's
Alg. 1 (``SkedulixGreedy``), both trivial brackets, a seeded random
placement, and two literature baselines: NOAH's shared-queue spillover
(Stein 2018) and the cost-analysis placement of De Palma et al. 2023.
Every policy sees the identical bursty MMPP request stream, crossed
with a fault-free / faulty scenario axis, and the report ranks them by
elastic spend, SLA attainment (against true arrivals), and makespan.

    PYTHONPATH=src python examples/policy_comparison.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.configs.registry import get_config
from repro.core.arrivals import MMPPArrivals
from repro.serving import (CostAnalysisPlacement, HybridServingScheduler,
                           NoahSharedQueue, PrivateOnly, PublicOnly,
                           RandomFeasible, SkedulixGreedy,
                           elastic_portfolio)


def main():
    print("== Skedulix policy harness: llama3-8b pod + elastic overflow ==")
    cfg = get_config("llama3-8b")
    sched = HybridServingScheduler(cfg, portfolio=elastic_portfolio(3))

    rng = np.random.default_rng(0)
    J = 96
    prompt_len = rng.integers(128, 4096, J)
    new_tokens = rng.integers(32, 384, J)
    # bursty traffic: a calm phase (~2 req/s) and a burst phase (~24 req/s)
    arrivals = MMPPArrivals(rates=(2.0, 24.0), dwell=(6.0, 3.0), seed=11)
    sla_s = 2.5
    replan_s = 0.25

    policies = [
        SkedulixGreedy(),               # Alg. 1: ACD eviction loop
        PrivateOnly(),                  # $0 bracket
        PublicOnly(),                   # max-$ bracket
        RandomFeasible(p_offload=0.5, seed=3),
        NoahSharedQueue(),              # Stein 2018, arXiv 1809.06100
        CostAnalysisPlacement(),        # De Palma et al., arXiv 2310.20391
    ]
    print(f"{J} requests, MMPP({arrivals.rates[0]:g}/s calm, "
          f"{arrivals.rates[1]:g}/s burst), SLA {sla_s:g}s, "
          f"re-plan every {replan_s:g}s, fault axis [none, 0.2]\n")
    rep = sched.compare_policies(prompt_len, new_tokens, policies,
                                 sla_s=sla_s, arrivals=arrivals,
                                 replan_every_s=replan_s, use_ridge=False,
                                 engine="vector", faults=[None, 0.2])
    print(rep.table())
    hyb, pub = rep["skedulix"], rep["public"]
    ratio = hyb["cost_usd"] / max(pub["cost_usd"], 1e-12)
    print(f"\nFig-4 ordering: hybrid spends {100 * ratio:.1f}% of "
          f"public-only at SLA {hyb['sla']:.3f} vs {pub['sla']:.3f} "
          f"(policy decisions took {1e3 * rep.plan_s:.2f} ms)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
