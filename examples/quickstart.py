"""Quickstart: the whole Skedulix pipeline in one minute.

Generates execution traces for the Matrix Processing app (real JAX
matmul + LU stages on this host), fits the ridge performance models,
then schedules a batch against a deadline on the hybrid platform and
compares with the all-private / all-public baselines.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.apps import SPECS, fit_models, generate_traces, split_traces
from repro.core import (SkedulixScheduler, simulate_all_private,
                        simulate_all_public)


def main():
    print("== Skedulix quickstart: Matrix Processing (MM -> LU) ==")
    spec = SPECS["matrix"](scale=0.5)

    print("1. executing 60 jobs to collect traces (warm starts)...")
    traces = generate_traces(spec, 60, seed=0)
    train, test = split_traces(traces, 45)

    print("2. fitting ridge latency/size models (5-fold grid search)...")
    pm = fit_models(spec, train)
    sched = SkedulixScheduler(spec.dag, pm)

    pred_all = pm.predict(test["base_features"])
    pred = {k: pred_all[k] for k in ("P_private", "P_public",
                                     "upload", "download")}
    act = dict(P_private=test["private"], P_public=test["public"],
               upload=pred["upload"], download=pred["download"])

    priv = simulate_all_private(spec.dag, pred, act)
    pub = simulate_all_public(spec.dag, pred, act)
    print(f"   all-private: makespan={priv.makespan:6.2f}s  cost=$0")
    print(f"   all-public : makespan={pub.makespan:6.2f}s  "
          f"cost=${pub.cost_usd:.5f}")

    c_max = priv.makespan * 0.55
    print(f"3. scheduling with C_max={c_max:.2f}s (0.55x all-private):")
    for order in ("spt", "hcf"):
        rep = sched.schedule_batch(c_max=c_max, pred=pred, act=act,
                                   order=order)
        r = rep.result
        print(f"   {order.upper()}: makespan={r.makespan:6.2f}s "
              f"met={r.met_deadline} cost=${r.cost_usd:.5f} "
              f"({100 * r.cost_usd / pub.cost_usd:.0f}% of all-public), "
              f"offloaded {r.n_offloaded_stages} stage executions")
    print("done.")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
