"""The Video Processing DAG (Fig. 1) end to end: real JAX stages (frame
extraction, conv object detection, rescaling, merging), trace-driven
models, and a C_max sweep showing the cost/latency trade-off (Fig. 4b).

    PYTHONPATH=src python examples/video_pipeline.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.apps import SPECS, fit_models, generate_traces, run_job, split_traces
from repro.core import SkedulixScheduler, simulate_all_private, simulate_all_public


def main():
    spec = SPECS["video"](scale=0.4)
    print("== Video Processing: EF -> {DO, RI} -> ME ==")
    rng = np.random.default_rng(0)
    job, feats = spec.make_job(rng)
    outs = run_job(spec, job)
    print(f"demo job: video {tuple(job.shape)} -> frames {tuple(outs[0].shape)}"
          f" -> boxes {tuple(outs[1].shape)}, rescaled {tuple(outs[2].shape)}")

    print("collecting traces for 40 clips...")
    traces = generate_traces(spec, 40, seed=0)
    tr, te = split_traces(traces, 28)
    pm = fit_models(spec, tr)
    sched = SkedulixScheduler(spec.dag, pm)
    pred_all = pm.predict(te["base_features"])
    pred = {k: pred_all[k] for k in ("P_private", "P_public",
                                     "upload", "download")}
    act = dict(P_private=te["private"], P_public=te["public"],
               upload=pred["upload"], download=pred["download"])
    priv = simulate_all_private(spec.dag, pred, act)
    pub = simulate_all_public(spec.dag, pred, act)
    print(f"baselines: all-private {priv.makespan:.2f}s / $0 ; "
          f"all-public {pub.makespan:.2f}s / ${pub.cost_usd:.5f}")
    print(" C_max   makespan  met  cost      off%  (SPT)")
    for frac in (0.5, 0.65, 0.8, 0.95):
        c_max = priv.makespan * frac
        r = sched.schedule_batch(c_max=c_max, pred=pred, act=act,
                                 order="spt").result
        print(f" {c_max:6.2f}  {r.makespan:7.2f}  {int(r.met_deadline)}   "
              f"${r.cost_usd:.5f}  {100 * r.offload_fraction:4.0f}%")
    # the scheduler should prefer offloading the DO bottleneck (Sec. V-C)
    r = sched.schedule_batch(c_max=priv.makespan * 0.6, pred=pred, act=act,
                             order="spt").result
    names = [s.name for s in spec.dag.stages]
    print("per-stage offloads:",
          ", ".join(f"{n}={c}" for n, c in zip(names, r.per_stage_offloads)))
    print("done.")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
