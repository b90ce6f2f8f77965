"""Policy-comparison bench: the Fig.-4 harness as a ratcheted CI point.

Runs :func:`repro.serving.compare_policies` — every registered policy
(Alg. 1's ``SkedulixGreedy``, the NOAH and cost-analysis literature
baselines, the private/public/random brackets) over one serving stream,
optionally crossed with a fault axis — on both engines, asserts the
cross-engine checksum agrees, asserts the paper's qualitative Fig.-4
ordering (hybrid at a fraction of public-only cost without giving up
attainment), and writes ``BENCH_policies.json`` whose per-engine
scenarios/sec rows join the ``tools/check_bench_regression.py`` ratchet.

Usage:
    python -m benchmarks.bench_policies --smoke          # the CI point
    python -m benchmarks.bench_policies --jobs 512 --fault-rate 0.2
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.serving import (HybridServingScheduler,  # noqa: E402
                           elastic_portfolio)
from repro.serving.policies import (_LAST_POLICY_STATS,  # noqa: E402
                                    POLICIES, compare_policies,
                                    policy_from_mode)

# every registry policy, dedup'd (hybrid/skedulix alias the same class)
DEFAULT_POLICIES = ("skedulix", "private", "public", "random", "noah",
                    "costanalysis")


def build_stream(J: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return rng.integers(64, 2048, J), rng.integers(16, 256, J)


def run_point(J: int, engines, sla_s: float, replan_s: float,
              arrivals: str, fault_rate, providers: int,
              policy_names) -> dict:
    sched = HybridServingScheduler(get_config("llama3-8b"),
                                   portfolio=elastic_portfolio(providers))
    prompt_len, new_tokens = build_stream(J)
    pred, act = sched._pred_act(prompt_len, new_tokens, seed=1,
                                use_ridge=False)
    policies = [policy_from_mode(n) for n in policy_names]
    faults = [None, float(fault_rate)] if fault_rate else None
    kw = dict(arrivals=arrivals, replan_every_s=replan_s,
              cost_model=sched.cost_model, portfolio=sched.portfolio,
              faults=faults)

    point = {"J": J, "n_policies": len(policies),
             "policies": list(policy_names), "arrivals": arrivals,
             "fault_rate": float(fault_rate) if fault_rate else None,
             "providers": providers, "sla_s": sla_s, "replan_s": replan_s,
             "engines": {}}
    reports, checks = {}, {}
    for eng in engines:
        if eng == "vector":      # warm the compile cache before timing
            compare_policies(policies, sched.dag, pred, act, sla_s,
                             engine=eng, **kw)
        t0 = time.perf_counter()
        rep = compare_policies(policies, sched.dag, pred, act, sla_s,
                               engine=eng, **kw)
        wall = time.perf_counter() - t0
        n_scen = int(rep.cost_usd.size)
        point["engines"][eng] = {
            "wall_s": wall,
            "scenarios_per_sec": n_scen / wall,
            "plan_s": _LAST_POLICY_STATS.get("policy_s", 0.0),
        }
        reports[eng] = rep
        checks[eng] = float(np.nansum(rep.cost_usd)
                            + np.nansum(rep.makespan))
        print(f"  {eng:>6}: {n_scen} scenarios in {wall:.3f}s "
              f"({n_scen / wall:.2f} scen/s, "
              f"plan {1e3 * point['engines'][eng]['plan_s']:.2f}ms)")

    ref_eng = engines[0]
    for eng in engines[1:]:
        assert np.isclose(checks[eng], checks[ref_eng], rtol=1e-6), (
            f"engine checksum mismatch: {eng}={checks[eng]!r} vs "
            f"{ref_eng}={checks[ref_eng]!r}")
    point["checksum"] = checks[ref_eng]

    rep = reports[ref_eng]
    point["rows"] = rep.summary()
    print(rep.table())

    # the paper's qualitative Fig.-4 ordering must hold on this grid:
    # hybrid (Alg. 1) at <= half the public-only spend with matched
    # deadline attainment, and never cheaper than the $0 private pool
    hyb, pub, priv = rep["skedulix"], rep["public"], rep["private"]
    assert hyb["cost_usd"] <= 0.5 * pub["cost_usd"], (
        f"Fig-4 ordering broken: hybrid ${hyb['cost_usd']:.6f} > 50% of "
        f"public ${pub['cost_usd']:.6f}")
    assert hyb["sla"] >= pub["sla"] - 0.05, (
        f"Fig-4 ordering broken: hybrid SLA {hyb['sla']:.3f} below "
        f"public {pub['sla']:.3f} - 0.05")
    assert hyb["sla"] >= priv["sla"] - 1e-9, (
        f"Fig-4 ordering broken: hybrid SLA {hyb['sla']:.3f} below "
        f"private {priv['sla']:.3f}")
    assert priv["cost_usd"] == 0.0
    print("  Fig-4 ordering OK: hybrid cost "
          f"{100 * hyb['cost_usd'] / max(pub['cost_usd'], 1e-12):.1f}% of "
          f"public at SLA {hyb['sla']:.3f} (public {pub['sla']:.3f}, "
          f"private {priv['sla']:.3f})")
    return point


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="the small CI point (J=96)")
    ap.add_argument("--jobs", type=int, default=None, metavar="J",
                    help="request count (default: 96 smoke, 256 full)")
    ap.add_argument("--sla", type=float, default=4.0, metavar="S")
    ap.add_argument("--replan", type=float, default=0.5, metavar="S")
    ap.add_argument("--arrivals", default="poisson:8.0", metavar="SPEC")
    ap.add_argument("--fault-rate", type=float, default=0.3, metavar="R",
                    help="adds a [fault-free, rate-R] scenario axis "
                         "shared by every policy (0 disables)")
    ap.add_argument("--providers", type=int, default=3, metavar="N")
    ap.add_argument("--policies", default=",".join(DEFAULT_POLICIES),
                    metavar="A,B,...",
                    help=f"registry names (known: {sorted(POLICIES)})")
    ap.add_argument("--engines", default="des,vector", metavar="A,B")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_policies.json"))
    args = ap.parse_args(argv)

    J = args.jobs if args.jobs is not None else (96 if args.smoke else 256)
    engines = [e.strip() for e in args.engines.split(",") if e.strip()]
    names = [p.strip() for p in args.policies.split(",") if p.strip()]
    print(f"== policy comparison bench: J={J}, {len(names)} policies, "
          f"engines {engines} ==")
    point = run_point(J, engines, args.sla, args.replan, args.arrivals,
                      args.fault_rate, args.providers, names)

    report = {"bench": "policies", "devices": jax.local_device_count(),
              "points": [point],
              "headline": {eng: point["engines"][eng]["scenarios_per_sec"]
                           for eng in engines}}
    out = os.path.abspath(args.out)
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(f"wrote {out}")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
