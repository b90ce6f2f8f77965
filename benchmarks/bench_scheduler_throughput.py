"""Scheduler engine throughput: jobs/sec and scenarios/sec per engine.

Measures a Fig.-4-style scenario sweep — the 3 canonical apps x {SPT, HCF}
x a C_max grid — on three engines:

* ``seed``:   the frozen seed-revision DES (``_seed_baseline``), the perf
              trajectory's fixed reference point;
* ``des``:    the current event-heap DES (``repro.core.simulate``);
* ``vector``: the batched jit engine (``repro.core.sweep_scenarios``),
              whole grid per device call, scenario axis sharded across
              host devices.

``--providers N`` adds a multi-provider point (``demo_portfolio(N)``,
cheapest-feasible placement per offloaded stage) on the des/vector
engines — the frozen seed DES predates the portfolio and sits that one
out. The smoke run always includes a 3-provider point so CI tracks
multi-provider throughput alongside the scalar engines.

``--arrivals SPEC`` (e.g. ``poisson:4.0``, ``mmpp:1,10:10,2``; see
``repro.core.arrivals.parse_arrivals``) adds an online-arrival point:
the same Fig.-4 sweep with jobs released by an exogenous stream instead
of a batch at t0, on the des/vector engines (the frozen seed DES is
batch-only). Stochastic streams are re-seeded per application so the
apps see distinct traces; the des/vector agreement assertion covers the
arrival path too. CI's smoke run passes ``--arrivals poisson:4.0``.

``--replica-sweep N`` adds a replica-autoscaling point: each app's sweep
grows a ``replicas=`` scenario axis of N per-stage pool sizings
(deterministic per-app draws in 1..4), multiplying the grid N-fold —
the batched pod-sizing workload behind ``autoscale_frontier``. Replica
counts are scenario *data* in the vector engine (one executable per
(M, I_max, J, P, S, flags) shape family), so the N-fold grid is still
one device call per app; the DES replays it serially. des/vector
checksum-checked; the frozen seed DES predates replica-as-data and sits
it out. CI's smoke run passes ``--replica-sweep 8``.

``--price-traces N`` adds a time-dependent-pricing point: each app's
sweep grows a ``price_traces=`` scenario axis of N portfolio pricings —
a spot-market trace family per app (``spot_portfolio``, deterministic
per-(app, variant) seeds, 6 segments over the deadline horizon) — so
the grid multiplies N-fold and every offload is priced at its offload
epoch (segment-indexed [P, S, J, M] billing data, same executable).
des/vector checksum-checked; the seed DES predates portfolios and sits
it out. CI's smoke run passes ``--price-traces 4``.

``--fault-rate R`` adds a fault-injection point: each app's sweep grows
a ``faults=`` reliability axis of two configs — fault-free and a seeded
chaos scenario (iid per-attempt failures at rate R, one provider outage
window over the deadline horizon, mid-stage kills at 0.75 of the
duration) — under a 3-attempt retry policy with backoff re-placement
and private fallback. Failures are scenario *data* (seeded grids +
outage windows), so the vector engine unrolls a bounded attempt axis in
the same device call and the des/vector checksum assertion covers the
recovery path too. CI's smoke run passes ``--fault-rate 0.3``.

``--coldstart W`` adds a load-dependent-latency point: the same sweep
with per-provider concurrency caps of 2 slots (dispatch beyond the cap
queues FIFO and the wait bills) and a cold-start/keep-alive model
(``W``-second warm-up, keep-alive window of ``2*W``, scale-to-zero
pools). These are per-call configs shared by every scenario of the
grid — not new axes — so the grid size is unchanged but every start
time flows through the congestion machinery; the des/vector checksum
assertion covers the capped+cold path. The seed DES predates the load
model and sits it out. CI's smoke run passes ``--coldstart 0.5``.

Emits ``BENCH_scheduler.json`` next to this file (or ``--out``):
absolute wall times, jobs-scheduled/sec, scenarios/sec, and speedups vs
the seed baseline at each job count. ``--smoke`` runs a tiny instance and
asserts the engines agree — used by CI; ``--full`` adds the J=32768
single-scenario point (slow).

Run as ``python -m benchmarks.bench_scheduler_throughput`` from the repo
root.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# on a CPU run (JAX_PLATFORMS=cpu), shard the vector engine's scenario
# axis across all cores (must be set before jax initializes); on an
# accelerator the real devices shard it
if (os.environ.get("JAX_PLATFORMS") == "cpu"
        and "--one-device" not in sys.argv and "XLA_FLAGS" not in os.environ):
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={os.cpu_count() or 1}")

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.dag import APPS  # noqa: E402
from repro.core.simulator import simulate  # noqa: E402
from repro.core.vectorsim import sweep_scenarios  # noqa: E402

from benchmarks._seed_baseline import simulate_seed  # noqa: E402

N_DEADLINES = 5
DEADLINE_FRACS = np.linspace(0.45, 0.95, N_DEADLINES)
ORDERS = ("spt", "hcf")


def fig4_workload(J: int, jitter: float = 0.05):
    """Synthetic Fig-4-style batch per app: lognormal stage latencies,
    moderate prediction error, transfer latencies, deadline grid scaled
    off the ideal all-private makespan."""
    tasks = []
    for ai, (name, dag) in enumerate(sorted(APPS.items())):
        rng = np.random.default_rng(ai)
        M = dag.num_stages
        P_priv = rng.lognormal(0.0, 0.5, (J, M)) * 2.0
        pred = dict(P_private=P_priv,
                    P_public=P_priv * rng.uniform(0.8, 1.6, (J, M)),
                    upload=rng.uniform(0.05, 0.3, (J, M)),
                    download=rng.uniform(0.05, 0.3, (J, M)))
        act = {k: v * rng.lognormal(0, jitter, v.shape)
               for k, v in pred.items()}
        base = float(P_priv.sum()) / float(dag.replicas.sum())
        tasks.append(dict(name=name, dag=dag, pred=pred, act=act,
                          c_max_grid=tuple(float(base * f)
                                           for f in DEADLINE_FRACS),
                          orders=ORDERS))
    return tasks


def run_serial(tasks, sim_fn, portfolio=None):
    base = {} if portfolio is None else {"portfolio": portfolio}
    t0 = time.perf_counter()
    chk = 0.0
    n = 0
    for task in tasks:
        kw = dict(base)
        if task.get("arrivals") is not None:
            kw["arrivals"] = task["arrivals"]
        for order in task["orders"]:
            for c in task["c_max_grid"]:
                r = sim_fn(task["dag"], task["pred"], task["act"],
                           c_max=c, order=order, **kw)
                chk += r.makespan + r.cost_usd
                n += 1
    return time.perf_counter() - t0, chk, n


#: wall-time breakdown of the last ``run_vector`` call (``--profile``):
#: cold-call compile+run wall vs the timed call's host-prep / engine
#: dispatch+compute / host-finalize split from the engine's own
#: ``_LAST_RUN_STATS`` instrumentation, plus the inner-loop impl used.
LAST_PROFILE: dict = {}


def run_vector(tasks, warm: bool = True, portfolio=None, engine="vector",
               retry=None, **sweep_kw):
    """Whole-sweep runner: one batched call per app on ``vector``, a
    serial scenario-grid replay on ``des`` (the path that understands the
    ``replicas=``/``price_traces=``/``faults=`` axes). Per-call sweep
    configs (``concurrency=``/``coldstart=``) pass through ``sweep_kw``."""
    from repro.core import vectorsim as _vs

    keys = ("dag", "pred", "act", "c_max_grid", "orders", "arrivals",
            "replicas", "price_traces", "faults")
    calls = [{k: t[k] for k in keys if t.get(k) is not None} for t in tasks]
    LAST_PROFILE.clear()
    if warm and engine == "vector":  # compile outside the timed region
        tw = time.perf_counter()
        sweep_scenarios(calls, portfolio=portfolio, retry=retry, **sweep_kw)
        LAST_PROFILE["cold_wall_s"] = time.perf_counter() - tw
    t0 = time.perf_counter()
    outs = sweep_scenarios(calls, portfolio=portfolio, engine=engine,
                           retry=retry, **sweep_kw)
    dt = time.perf_counter() - t0
    if engine == "vector":
        st = _vs._LAST_RUN_STATS
        LAST_PROFILE.update(
            impl=st.get("impl"),
            warm_wall_s=dt,
            prep_s=st.get("prep_s", 0.0),
            # replan/policy-decision time: priority keys, placement
            # argmin matrices, offload-plan resolution (a prep_s
            # sub-bucket; 0.0 when the prep cache reused the decisions)
            plan_s=st.get("plan_s", 0.0),
            engine_s=st.get("engine_s", 0.0),
            finalize_s=st.get("finalize_s", 0.0))
        if "cold_wall_s" in LAST_PROFILE:
            # the cold call pays compile + one run; its excess over the
            # warm call is (to box noise) pure XLA compile time
            LAST_PROFILE["compile_s"] = max(
                0.0, LAST_PROFILE["cold_wall_s"] - dt)
    chk = float(sum(o.makespan.sum() + o.cost_usd.sum() for o in outs))
    return dt, chk, sum(o.num_scenarios for o in outs)


def attach_arrivals(tasks, spec: str):
    """Resolve ``spec`` to one release-time vector per task, re-seeding
    stochastic processes per application so traces are distinct."""
    import dataclasses

    from repro.core.arrivals import parse_arrivals, resolve_release

    proc = parse_arrivals(spec)
    J = tasks[0]["pred"]["P_private"].shape[0]
    for ai, t in enumerate(tasks):
        p = dataclasses.replace(proc, seed=proc.seed + ai) \
            if hasattr(proc, "seed") else proc
        t["arrivals"] = resolve_release(p, J)
    return tasks


def attach_replicas(tasks, n_cfgs: int):
    """Give each app a ``replicas=`` axis of ``n_cfgs`` per-stage pool
    sizings (deterministic draws in 1..4, re-seeded per application)."""
    for ai, t in enumerate(tasks):
        rng = np.random.default_rng(100 + ai)
        M = t["dag"].num_stages
        t["replicas"] = list(rng.integers(1, 5, size=(n_cfgs, M)))
    return tasks


def attach_price_traces(tasks, n_traces: int, providers: int):
    """Give each app a ``price_traces=`` axis of ``n_traces`` spot-market
    pricings of the portfolio (6-segment walks over the app's deadline
    horizon, deterministic per-(app, variant) seeds)."""
    from repro.core.cost import spot_portfolio

    for ai, t in enumerate(tasks):
        horizon = float(max(t["c_max_grid"]))
        t["price_traces"] = [
            spot_portfolio(providers, num_segments=6, horizon_s=horizon,
                           seed=1000 + 31 * ai + v)
            for v in range(n_traces)]
    return tasks


def attach_faults(tasks, rate: float):
    """Give each app a 2-point ``faults=`` reliability axis: fault-free
    plus a seeded chaos scenario (iid failures at ``rate``, one provider-0
    outage window over the deadline horizon, 0.75-duration kills)."""
    from repro.core.faults import FaultModel, RetryPolicy

    for ai, t in enumerate(tasks):
        J, M = t["pred"]["P_private"].shape
        h = float(max(t["c_max_grid"]))
        t["faults"] = [None, FaultModel.from_rate(
            rate, J, M, max_attempts=3, seed=200 + ai,
            outages=((0, 0.1 * h, 0.3 * h),), kill_frac=0.75)]
    return tasks, RetryPolicy(max_attempts=3, backoff_s=0.2,
                              jitter_frac=0.25)


def peak_rss_mb() -> float:
    """Process-lifetime peak RSS in MB (monotone; Linux reports KB)."""
    import resource

    ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return ru / 1024.0 if sys.platform.startswith("linux") else ru / 2**20


def measure_azure_point(J: int, engines, chunk_jobs: int = 4096,
                        c_max: float = 60.0, day: str = "tue"):
    """Streaming bench point: one azure-trace invocation day at scale J.

    The job axis is *paged* — the vector engine streams fixed-shape
    chunks (compile cache keyed on the chunk family, per-replica clocks
    carried across pages) and the DES admits arrival epochs in windows —
    so the point measures the memory-bounded regime the monolithic
    shape family cannot reach (J=1e5..1e6). One app, one order, one
    deadline keeps the serial DES replay CI-affordable. Reports
    process peak RSS alongside throughput; the smoke assertion requires
    it to stay under 4 GB.
    """
    from repro.core.vectorsim import _LAST_RUN_STATS, simulate_scenarios

    dag = APPS["image"]
    spec = f"azure:day={day},scale={J}"
    point = {"J": J, "apps": 1, "orders": 1, "deadlines": 1,
             "workload": f"azure:day={day}", "chunk_jobs": chunk_jobs,
             "engines": {}}
    checks = {}
    for eng in engines:
        t0 = time.perf_counter()
        out = simulate_scenarios(
            dag, None, workload=spec, c_max_grid=(c_max,),
            orders=("spt",), engine=eng, chunk_jobs=chunk_jobs)
        dt = time.perf_counter() - t0
        checks[eng] = float(out.makespan.sum() + out.cost_usd.sum())
        rss = peak_rss_mb()
        point["engines"][eng] = {
            "wall_s": round(dt, 4),
            "scenarios_per_sec": round(1.0 / dt, 5),
            "jobs_per_sec": round(J / dt, 1),
            "peak_rss_mb": round(rss, 1),
        }
        extra = ""
        if eng == "vector":
            point["pages"] = _LAST_RUN_STATS.get("pages")
            extra = f"  {point['pages']} pages"
        print(f"  J={J:>6} {eng:>6}: {dt:8.3f}s  "
              f"{J / dt:10.0f} jobs/s  rss {rss:7.1f} MB{extra}")
    ref = next(iter(checks.values()))
    for eng, chk in checks.items():
        if not np.isclose(chk, ref, rtol=1e-6):
            raise AssertionError(
                f"engine {eng} diverged on the azure point: "
                f"checksum {chk} vs {ref}")
    assert peak_rss_mb() < 4096.0, \
        f"azure streaming point exceeded 4 GB peak RSS ({peak_rss_mb():.0f} MB)"
    return point


def measure_point(J: int, engines, deadlines=N_DEADLINES, portfolio=None,
                  arrivals=None, replica_sweep=None, price_traces=None,
                  fault_rate=None, coldstart=None, profile=False):
    tasks = fig4_workload(J)
    if deadlines != N_DEADLINES:
        for t in tasks:
            t["c_max_grid"] = t["c_max_grid"][:deadlines]
    if arrivals is not None:
        tasks = attach_arrivals(tasks, arrivals)
    if replica_sweep is not None:
        tasks = attach_replicas(tasks, replica_sweep)
    if price_traces is not None:
        if portfolio is None:
            raise ValueError("--price-traces needs a portfolio")
        tasks = attach_price_traces(tasks, price_traces,
                                    portfolio.num_providers)
    retry = None
    if fault_rate is not None:
        tasks, retry = attach_faults(tasks, fault_rate)
    sweep_kw = {}
    if coldstart is not None:
        # per-call load configs (not scenario axes): 2-slot provider
        # caps + a W-second warm-up with a 2W keep-alive window
        from repro.core.coldstart import ColdStartModel

        sweep_kw = dict(
            concurrency=2,
            coldstart=ColdStartModel(warm_up_s=float(coldstart),
                                     keep_alive_s=2.0 * float(coldstart),
                                     scale_to_zero=True))
    point = {"J": J, "apps": len(tasks), "orders": len(ORDERS),
             "deadlines": len(tasks[0]["c_max_grid"]), "engines": {}}
    if portfolio is not None:
        point["providers"] = portfolio.num_providers
    if arrivals is not None:
        point["arrivals"] = arrivals
    if replica_sweep is not None:
        point["replica_configs"] = replica_sweep
    if price_traces is not None:
        point["price_traces"] = price_traces
    if fault_rate is not None:
        point["fault_rate"] = fault_rate
    if coldstart is not None:
        point["coldstart"] = coldstart
    checks = {}
    for eng in engines:
        if eng == "seed":
            if portfolio is not None:
                raise ValueError("the frozen seed DES has no portfolio")
            if arrivals is not None:
                raise ValueError("the frozen seed DES is batch-only")
            if replica_sweep is not None:
                raise ValueError("the frozen seed DES has no replica axis")
            if coldstart is not None:
                raise ValueError("the frozen seed DES has no load model")
            dt, chk, n = run_serial(tasks, simulate_seed)
        elif eng == "des":
            if (replica_sweep is not None or price_traces is not None
                    or fault_rate is not None or coldstart is not None):
                dt, chk, n = run_vector(tasks, portfolio=portfolio,
                                        engine="des", retry=retry,
                                        **sweep_kw)
            else:
                dt, chk, n = run_serial(tasks, simulate, portfolio=portfolio)
        else:
            dt, chk, n = run_vector(tasks, portfolio=portfolio, retry=retry,
                                    **sweep_kw)
        checks[eng] = chk
        point["engines"][eng] = {
            "wall_s": round(dt, 4),
            "scenarios_per_sec": round(n / dt, 3),
            "jobs_per_sec": round(n * J / dt, 1),
        }
        print(f"  J={J:>6} {eng:>6}: {dt:8.3f}s  "
              f"{n / dt:8.2f} scen/s  {n * J / dt:10.0f} jobs/s")
        if profile and eng == "vector" and LAST_PROFILE:
            pr = {k: (round(v, 5) if isinstance(v, float) else v)
                  for k, v in LAST_PROFILE.items()}
            point["engines"][eng]["profile"] = pr
            print(f"           profile[{pr.get('impl')}]: "
                  f"compile {pr.get('compile_s', 0.0) * 1e3:8.1f}ms | "
                  f"prep {pr.get('prep_s', 0.0) * 1e3:6.1f}ms "
                  f"(plan {pr.get('plan_s', 0.0) * 1e3:6.1f}ms) | "
                  f"engine {pr.get('engine_s', 0.0) * 1e3:8.1f}ms | "
                  f"finalize {pr.get('finalize_s', 0.0) * 1e3:6.1f}ms")
    ref = checks.get("seed", checks.get("des"))
    for eng, chk in checks.items():
        if not np.isclose(chk, ref, rtol=1e-6):
            raise AssertionError(
                f"engine {eng} diverged: checksum {chk} vs {ref}")
    for eng in point["engines"]:
        if eng != "seed" and "seed" in point["engines"]:
            point["engines"][eng]["speedup_vs_seed"] = round(
                point["engines"]["seed"]["wall_s"]
                / point["engines"][eng]["wall_s"], 2)
    return point


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny J, all engines, agreement assertion (CI)")
    ap.add_argument("--full", action="store_true",
                    help="add the very slow J=32768 point")
    ap.add_argument("--one-device", action="store_true",
                    help="do not shard the vector engine across cores")
    ap.add_argument("--profile", action="store_true",
                    help="emit a wall-time breakdown per vector-engine "
                         "point (XLA compile vs host prep — with the "
                         "replan/policy-decision sub-bucket — vs engine "
                         "dispatch+compute vs host finalize) so a "
                         "regression is attributable to a phase")
    ap.add_argument("--providers", type=int, default=3, metavar="N",
                    help="provider count for the multi-provider point "
                         "(demo_portfolio(N); des/vector engines)")
    ap.add_argument("--arrivals", default=None, metavar="SPEC",
                    help="add an online-arrival point with this stream "
                         "(e.g. poisson:4.0; des/vector engines)")
    ap.add_argument("--replica-sweep", type=int, default=None, metavar="N",
                    help="add a replica-autoscaling point: N pool sizings "
                         "per app batched on the scenario axis "
                         "(des/vector engines)")
    ap.add_argument("--price-traces", type=int, default=None, metavar="N",
                    help="add a time-dependent-pricing point: N spot-market "
                         "pricings of the portfolio per app batched on the "
                         "scenario axis (des/vector engines)")
    ap.add_argument("--fault-rate", type=float, default=None, metavar="R",
                    help="add a fault-injection point: fault-free vs a "
                         "seeded chaos scenario (rate-R failures, an "
                         "outage window, mid-stage kills) under a "
                         "3-attempt retry policy (des/vector engines)")
    ap.add_argument("--coldstart", type=float, default=None, metavar="W",
                    help="add a load-dependent-latency point: 2-slot "
                         "provider concurrency caps plus a W-second "
                         "warm-up / 2W keep-alive cold-start model as "
                         "per-call configs (des/vector engines)")
    ap.add_argument("--workload", default=None, metavar="FAM",
                    help="add a streaming trace-workload point (currently "
                         "'azure': one paged invocation day, des+vector, "
                         "peak-RSS reporting, <4 GB assertion)")
    ap.add_argument("--jobs", type=int, default=100000, metavar="J",
                    help="invocation count for the --workload point "
                         "(default 100000)")
    ap.add_argument("--chunk-jobs", type=int, default=4096, metavar="N",
                    help="streaming page size for the --workload point")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_scheduler.json"))
    args = ap.parse_args(argv)

    from repro.core.cost import demo_portfolio  # noqa: E402
    pf = demo_portfolio(args.providers)

    report = {"bench": "scheduler_throughput",
              "devices": None, "points": []}
    import jax
    report["devices"] = jax.local_device_count()

    if args.smoke:
        print("smoke: J=64, full sweep, all engines")
        report["points"].append(
            measure_point(64, ("seed", "des", "vector"),
                          profile=args.profile))
        print("smoke: J=512, 1 deadline, des+vector")
        # the ROADMAP speedup targets are stated at J=512, so CI tracks
        # a ratcheted point at that scale too; one deadline per
        # app/order keeps the serial DES replay affordable
        report["points"].append(
            measure_point(512, ("des", "vector"), deadlines=1,
                          profile=args.profile))
        print(f"smoke: J=64, {args.providers}-provider portfolio, "
              "des+vector")
        report["points"].append(
            measure_point(64, ("des", "vector"), portfolio=pf,
                          profile=args.profile))
        if args.arrivals:
            print(f"smoke: J=64, online arrivals ({args.arrivals}), "
                  "des+vector")
            report["points"].append(
                measure_point(64, ("des", "vector"),
                              arrivals=args.arrivals))
        if args.replica_sweep:
            print(f"smoke: J=64, {args.replica_sweep}-config replica "
                  "sweep, des+vector")
            report["points"].append(
                measure_point(64, ("des", "vector"),
                              replica_sweep=args.replica_sweep))
        if args.price_traces:
            print(f"smoke: J=64, {args.price_traces}-trace spot-pricing "
                  "sweep, des+vector")
            report["points"].append(
                measure_point(64, ("des", "vector"), portfolio=pf,
                              price_traces=args.price_traces))
        if args.fault_rate is not None:
            print(f"smoke: J=64, fault-injection sweep "
                  f"(rate {args.fault_rate}), des+vector")
            report["points"].append(
                measure_point(64, ("des", "vector"), portfolio=pf,
                              fault_rate=args.fault_rate))
        if args.coldstart is not None:
            print(f"smoke: J=64, capped+cold load model "
                  f"(warm-up {args.coldstart}s), des+vector")
            report["points"].append(
                measure_point(64, ("des", "vector"), portfolio=pf,
                              coldstart=args.coldstart,
                              profile=args.profile))
        if args.workload:
            if args.workload != "azure":
                raise SystemExit(f"unknown --workload {args.workload!r} "
                                 "(supported: azure)")
            print(f"smoke: streaming azure day, J={args.jobs}, "
                  f"chunk={args.chunk_jobs}, des+vector")
            report["points"].append(
                measure_azure_point(args.jobs, ("des", "vector"),
                                    chunk_jobs=args.chunk_jobs))
    else:
        print("sweep 3 apps x 2 orders x 5 deadlines:")
        report["points"].append(
            measure_point(512, ("seed", "des", "vector")))
        print(f"multi-provider sweep ({args.providers} providers, "
              "des/vector only):")
        report["points"].append(
            measure_point(512, ("des", "vector"), portfolio=pf))
        if args.arrivals:
            print(f"online-arrival sweep ({args.arrivals}, "
                  "des/vector only):")
            report["points"].append(
                measure_point(512, ("des", "vector"),
                              arrivals=args.arrivals))
        if args.replica_sweep:
            print(f"replica-autoscaling sweep ({args.replica_sweep} "
                  "configs/app, des/vector only):")
            report["points"].append(
                measure_point(512, ("des", "vector"),
                              replica_sweep=args.replica_sweep))
        if args.price_traces:
            print(f"spot-pricing sweep ({args.price_traces} trace "
                  "families/app, des/vector only):")
            report["points"].append(
                measure_point(512, ("des", "vector"), portfolio=pf,
                              price_traces=args.price_traces))
        if args.fault_rate is not None:
            print(f"fault-injection sweep (rate {args.fault_rate}, "
                  "des/vector only):")
            report["points"].append(
                measure_point(512, ("des", "vector"), portfolio=pf,
                              fault_rate=args.fault_rate))
        if args.coldstart is not None:
            print(f"capped+cold load-model sweep (warm-up "
                  f"{args.coldstart}s, des/vector only):")
            report["points"].append(
                measure_point(512, ("des", "vector"), portfolio=pf,
                              coldstart=args.coldstart))
        if args.workload:
            if args.workload != "azure":
                raise SystemExit(f"unknown --workload {args.workload!r} "
                                 "(supported: azure)")
            print(f"streaming azure day (J={args.jobs}, "
                  f"chunk={args.chunk_jobs}, des/vector only):")
            report["points"].append(
                measure_azure_point(args.jobs, ("des", "vector"),
                                    chunk_jobs=args.chunk_jobs))
        # large-J: seed is O(J^2 log J); one deadline keeps it bounded
        print("large-J point (1 deadline per app/order):")
        report["points"].append(
            measure_point(4096, ("seed", "des", "vector"), deadlines=1))
        if args.full:
            print("very-large-J point (des/vector only):")
            report["points"].append(
                measure_point(32768, ("des", "vector"), deadlines=1))

    head = report["points"][0]["engines"]
    if "vector" in head and "seed" in head:
        report["headline"] = {
            "sweep_J": report["points"][0]["J"],
            "speedup_vector_vs_seed": head["vector"]["speedup_vs_seed"],
            "speedup_des_vs_seed": head["des"]["speedup_vs_seed"],
        }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {os.path.abspath(args.out)}")
    return report


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
