"""Autoscaling frontier demo: pod sizing + Fig.-5-style straggler curves.

Replica counts are scenario *data* in the vector engine, so two of the
paper's hardest-to-sweep questions run as single batched device calls:

1. **How big should the serving pod be?** ``autoscale_frontier`` sweeps
   replica configs x scheduler deadlines in one call and returns the
   cost/SLA Pareto frontier — total cost = elastic overflow spend plus
   the reserved pod (replica-seconds at a committed-use discount),
   attainment measured against one fixed SLA target.

2. **How does the schedule degrade when replicas straggle?** A
   ``replica_speeds`` axis multiplies the same batched grid: replica 0
   of the decode pool at 1x..6x slowdown reproduces the shape of the
   paper's Fig.-5 robustness story, every point from the same call.

    PYTHONPATH=src python examples/autoscaling_frontier.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.configs.registry import get_config
from repro.serving import HybridServingScheduler


def main():
    print("== Skedulix autoscaling: llama3-8b pod sizing ==")
    cfg = get_config("llama3-8b")
    sched = HybridServingScheduler(cfg)

    rng = np.random.default_rng(0)
    J = 96
    prompt_len = rng.integers(128, 4096, J)
    new_tokens = rng.integers(32, 384, J)

    # -- 1. the cost/SLA frontier: 12 pool sizings x 4 deadline knobs ----
    replica_grid = [np.array([p, d, 1])
                    for p in (1, 2, 4) for d in (1, 2, 4, 8)]
    c_max_grid = (2.0, 4.0, 8.0, 16.0)
    fr = sched.autoscale_frontier(prompt_len, new_tokens, replica_grid,
                                  c_max_grid, sla_s=2.0, use_ridge=False)
    print(f"\n{fr.num_scenarios} scenarios "
          f"({len(replica_grid)} configs x {len(c_max_grid)} deadlines), "
          f"one batched call; SLA target {fr.sla_s:g}s; "
          f"{int(fr.pareto.sum())} points on the frontier:\n")
    print(fr.table())

    # -- 2. straggler degradation, batched on the speeds axis ------------
    pod = [np.array([2, 4, 1])]
    factors = (1.0, 1.5, 2.0, 3.0, 4.0, 6.0)
    speeds = [None if f == 1.0 else {(1, 0): f} for f in factors]
    sf = sched.autoscale_frontier(prompt_len, new_tokens, pod,
                                  c_max_grid=(2.0,), replica_speeds=speeds,
                                  use_ridge=False)
    print("\ndecode replica 0 straggling (2x4x1 pod, C_max 2s):\n")
    print(f"{'slowdown':>9} {'SLA':>6} {'makespan s':>11} {'total $':>9}")
    for i, f in enumerate(factors):
        print(f"{f:>8.1f}x {sf.sla[i]:6.3f} {sf.makespan[i]:11.3f} "
              f"{sf.total_usd[i]:9.4f}")
    print("\nthe greedy schedule degrades gracefully — and not "
          "monotonically: a straggling replica builds queue backlog, the "
          "ACD turns that backlog into evictions, and the elastic cloud "
          "absorbs it. SLA holds within a point; the straggler tax shows "
          "up as cost (the paper's Fig.-5 robustness story, every point "
          "from one batched call).")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
