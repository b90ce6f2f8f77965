"""Run the scheduler's vector engine on a TPU and check it against the DES.

Each phase drives the batched jit engine (``repro.core.vectorsim``)
through an entry point its users call, at the sizes they run, and
compares the result field by field with the event-heap DES
(``repro.core.simulator``), the plain reference:

1. batch sweep: the Fig-4 workload of
   ``benchmarks/bench_scheduler_throughput.py`` (3 apps x {spt, hcf} x
   5 deadlines) at J = 4096 jobs, a 3-provider portfolio and 8 pool
   sizings, i.e. 240 scenarios through ``sweep_scenarios``; then the
   bench's default point, one provider and the DAGs' own pools (30
   scenarios, every one DES-checked);
2. streaming day: ``SkedulixScheduler.schedule`` over a 10^5-invocation
   azure day, paged through the device in 4096-job chunks;
3. online controller: ``HybridServingScheduler.serve_online`` with the
   Skedulix policy over a seeded Poisson stream on the arctic-480b pod.

``--four-chips`` runs only the paths that shard the scenario axis over
four chips: the sweep with 7 pool sizings (210 scenarios, 70 per app,
so the pad to a multiple of 4 is exercised) and the paged day under 6
(order, deadline) scenarios.

Decision fields (placements, replicas, price segments, counters) must be
identical. Float fields (times, cost) must agree to the equivalence
suites' tolerance: XLA:TPU emulates float64 with float32 pairs, so they
cannot be bit-identical to numpy there; the number of elements that
differ in any bit is printed per field. Every time printed is host
wall-clock seconds, not a device metric. The last line of standard
output is a JSON object naming the device. Exits non-zero when JAX finds
no TPU, and on any mismatch or error.

    python chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: fields the DES==vector equivalence suites compare
#: (``tests/test_vectorsim.py``: ``FIELDS`` plus ``public_mask``)
FLOAT_FIELDS = ("makespan", "cost_usd", "completion", "start", "end",
                "queue_wait")
EXACT_FIELDS = ("public_mask", "n_offloaded_stages", "n_init_offloaded_jobs",
                "per_stage_offloads", "provider", "replica", "segment",
                "attempts", "failed", "abandoned", "cold")
#: ``tests/test_vectorsim.assert_equivalent``'s float tolerance
RTOL = ATOL = 1e-9

ORDERS = ("spt", "hcf")
AZURE = "azure:day=tue,scale={n}"


class CompileClock:
    """Sums XLA backend compile seconds reported by JAX while active."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event == self.EVENT:
            self.seconds += duration

    def __enter__(self):
        self.seconds, self.active = 0.0, True
        return self

    def __exit__(self, *exc):
        self.active = False


def compare(vec, des, tag: str) -> dict:
    """Field-by-field comparison; returns {field: elements off} for the
    fields that fail, and prints the bit-level count of every field."""
    bad, bits = {}, {}
    for fld in EXACT_FIELDS + FLOAT_FIELDS:
        a = np.asarray(getattr(vec, fld))
        b = np.asarray(getattr(des, fld))
        if a.shape != b.shape:
            bad[fld] = f"shape {a.shape} vs {b.shape}"
            continue
        if fld in FLOAT_FIELDS:
            a, b = a.astype(np.float64), b.astype(np.float64)
            same = (a == b) | (np.isnan(a) & np.isnan(b))
            bits[fld] = int((~same).sum())
            ok = np.isclose(a, b, rtol=RTOL, atol=ATOL, equal_nan=True)
        else:
            ok = a == b
        if not np.all(ok):
            bad[fld] = int(np.size(ok) - np.count_nonzero(ok))
    off = {k: v for k, v in bits.items() if v}
    print(f"  {tag}: float elements not bit-identical {off or 0}; "
          f"mismatched fields {bad or 0}")
    return bad


def batch_sweep(J: int = 4096, n_pools: int = 8, providers: int = 3) -> int:
    """Fig-4 sweep through ``sweep_scenarios``. With a pool-sizing axis
    (``n_pools``), DES-checks one scenario per (app, pool sizing),
    alternating order and deadline, so every (app, order) pair and every
    pool sizing is covered; without one (the bench's default point: the
    DAGs' own pools), DES-checks every scenario."""
    import jax

    from benchmarks.bench_scheduler_throughput import (attach_replicas,
                                                       fig4_workload)
    from repro.core import demo_portfolio, simulate, sweep_scenarios
    from repro.core import vectorsim

    tasks = fig4_workload(J)
    keys = ("dag", "pred", "act", "c_max_grid", "orders")
    if n_pools:
        tasks, keys = attach_replicas(tasks, n_pools), keys + ("replicas",)
    pf = demo_portfolio(providers) if providers > 1 else None
    tag = f"sweep-p{providers}"
    with CompileClock() as cc:
        t0 = time.perf_counter()
        outs = sweep_scenarios([{k: t[k] for k in keys} for t in tasks],
                               portfolio=pf)
        wall = time.perf_counter() - t0
    n_scen = sum(o.num_scenarios for o in outs)
    print(f"[{tag}] apps={len(tasks)} orders={len(ORDERS)} "
          f"deadlines={len(tasks[0]['c_max_grid'])} pool_sizings="
          f"{n_pools or 'dag'} providers={providers} J={J} "
          f"scenarios={n_scen} devices={jax.local_device_count()} "
          f"impl={vectorsim._LAST_RUN_STATS.get('impl')} "
          f"compile_s={cc.seconds:.3f} wall_s={wall:.3f}")
    report_lanes(tag, [o.num_scenarios for o in outs])
    n_bad = checked = 0
    t0 = time.perf_counter()
    for task, out in zip(tasks, outs):
        orders, c_max = np.asarray(out.orders), np.asarray(out.c_max)
        if n_pools:
            grid = task["c_max_grid"]
            pick = [int(np.flatnonzero(
                (orders == ORDERS[r % len(ORDERS)])
                & (c_max == grid[r % len(grid)])
                & (out.replicas == np.asarray(cfg)).all(axis=1))[0])
                for r, cfg in enumerate(task["replicas"])]
        else:
            pick = range(out.num_scenarios)
        for s in pick:
            cfg = np.asarray(out.replicas[s])
            des = simulate(task["dag"].with_replicas(cfg), task["pred"],
                           task["act"], c_max=float(c_max[s]),
                           order=str(orders[s]), portfolio=pf)
            n_bad += len(compare(
                out.scenario(s), des, f"{task['name']} {orders[s]} "
                f"c_max={c_max[s]:.2f} replicas={cfg.tolist()}"))
            checked += 1
    print(f"[{tag}] des_scenarios={checked} des_wall_s="
          f"{time.perf_counter() - t0:.3f} mismatched_fields={n_bad}")
    return n_bad


def streaming_day(n_jobs: int = 100_000, chunk: int = 4096,
                  c_max_grid=(60.0,), orders=("spt",)) -> int:
    """A paged azure invocation day: ``schedule`` for one scenario,
    ``schedule_sweep`` for an (order x deadline) grid."""
    from repro.core import APPS, SkedulixScheduler
    from repro.core import vectorsim

    sched = SkedulixScheduler(APPS["image"])
    spec = AZURE.format(n=n_jobs)
    one = len(c_max_grid) * len(orders) == 1

    def run(engine):
        if one:
            return sched.schedule(c_max_grid[0], workload=spec,
                                  chunk_jobs=chunk, order=orders[0],
                                  engine=engine).result
        return sched.schedule_sweep(c_max_grid, workload=spec,
                                    chunk_jobs=chunk, orders=orders,
                                    engine=engine)

    with CompileClock() as cc:
        t0 = time.perf_counter()
        vec = run("vector")
        wall = time.perf_counter() - t0
    st = dict(vectorsim._LAST_RUN_STATS)
    n_scen = 1 if one else vec.num_scenarios
    print(f"[azure] workload={spec} app=image jobs={n_jobs} "
          f"chunk_jobs={chunk} scenarios={n_scen} pages={st.get('pages')} "
          f"carried_jobs={st.get('carried_jobs')} compile_s={cc.seconds:.3f} "
          f"wall_s={wall:.3f}")
    report_lanes("azure", [n_scen] * st["engine_calls"])
    t0 = time.perf_counter()
    des = run("des")
    bad = compare(vec, des, f"{n_scen} scenario(s)")
    print(f"[azure] des_wall_s={time.perf_counter() - t0:.3f} "
          f"mismatched_fields={len(bad)}")
    return len(bad)


def online(n_req: int = 4096, rate: float = 8.0, sla_s: float = 4.0,
           replan_s: float = 0.5, seed: int = 0) -> int:
    """``serve_online`` with the Skedulix policy on the arctic-480b pod."""
    from repro.configs.registry import get_config
    from repro.core.arrivals import PoissonArrivals
    from repro.serving import HybridServingScheduler

    sched = HybridServingScheduler(get_config("arctic-480b"))
    rng = np.random.default_rng(seed)
    plen, ntok = rng.integers(64, 2048, n_req), rng.integers(16, 256, n_req)
    kw = dict(arrivals=PoissonArrivals(rate=rate, seed=seed), sla_s=sla_s,
              replan_every_s=replan_s, use_ridge=False, policy="skedulix")
    with CompileClock() as cc:
        t0 = time.perf_counter()
        vec = sched.serve_online(plen, ntok, engine="vector", **kw)
        wall = time.perf_counter() - t0
    print(f"[online] config=arctic-480b requests={n_req} "
          f"arrivals=poisson:{rate} sla_s={sla_s} replan_s={replan_s} "
          f"offload_frac={vec.result.offload_fraction:.4f} "
          f"sla_attainment={vec.sla_attainment:.4f} "
          f"compile_s={cc.seconds:.3f} wall_s={wall:.3f}")
    t0 = time.perf_counter()
    des = sched.serve_online(plen, ntok, engine="des", **kw)
    bad = compare(vec.result, des.result, "serve_online")
    print(f"[online] des_wall_s={time.perf_counter() - t0:.3f} "
          f"mismatched_fields={len(bad)}")
    return len(bad)


def report_lanes(tag: str, scenarios_per_call) -> None:
    """Scenario lanes per device over a run's engine calls, from the
    layout ``vectorsim._dispatch`` gives a call of S > 1 scenarios on n
    devices: S pads to a multiple of n and lane i runs on device i % n
    (a single scenario runs unsharded, on the first device)."""
    import jax

    n_dev = jax.local_device_count()
    if n_dev == 1:
        return
    lanes, real = np.zeros(n_dev, int), np.zeros(n_dev, int)
    for S in scenarios_per_call:
        if S == 1:
            lanes[0], real[0] = lanes[0] + 1, real[0] + 1
            continue
        S_pad = S + (-S) % n_dev
        lanes += S_pad // n_dev
        real += (np.arange(S_pad) < S).reshape(-1, n_dev).sum(axis=0)
    print(f"[{tag}] sharded_calls={len(scenarios_per_call)} "
          f"lanes_per_device={lanes.tolist()} "
          f"scenarios_per_device={real.tolist()} (lanes minus padding)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Run the vector engine on a TPU and check it against "
                    "the DES.")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the scenario-axis sharded paths on 4 "
                         "chips")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"devices: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found; refusing to run on "
              f"{dev['platform']}", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if dev["count"] < want:
        print(f"chip_smoke: needs {want} chips, found {dev['count']}",
              file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        bad = batch_sweep(n_pools=7)
        bad += streaming_day(c_max_grid=(30.0, 60.0, 120.0), orders=ORDERS)
    else:
        bad = (batch_sweep() + batch_sweep(n_pools=0, providers=1)
               + streaming_day() + online())
    if bad:
        print(f"chip_smoke: {bad} mismatched field(s)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
