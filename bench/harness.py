"""One run of one cell: set-up, the measured window, the metrics and the
check against the plain reference.

1. Set-up: find the chip, turn on the persistent compilation cache in
   the checkout, build every query of the run from the seed, and run
   one warm-up query of each shape family (its own inputs, from another
   stream of the seed). `setup_s` runs from the process's start to here.
2. Window: one client sends the queries back to back, each only after
   the previous one returned, while less than `seconds` have passed;
   the window closes when the last one returns. Backend compiles inside
   it are counted. With `trace`, the profiler records the window and
   the program's phases carry `bench:` spans.
3. Metrics: the cell's metrics, each from its reader under `metrics/`.
4. Check: a seeded sample of the window's scenarios, the longest among
   them, is run through `reference.simulate` on the same inputs and
   compared (`compare`) with what the window produced. The numbers and
   their limits, from the configuration, decide `correct`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from bench import compare, generate, reference, spec


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell needs."""


class CompileClock:
    """Counts the programs XLA compiles or loads from the persistent
    cache while active, the cache's hits among them, and their seconds."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.count = self.hits = 0
        self.seconds, self.active = 0.0, False
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if self.active and event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if self.active and event == self.HIT:
            self.hits += 1

    def __enter__(self):
        self.count = self.hits = 0
        self.seconds, self.active = 0.0, True
        return self

    def __exit__(self, *exc):
        self.active = False

    def __str__(self):
        return (f"programs={self.count} cache_hits={self.hits} "
                f"compiled={self.count - self.hits} "
                f"compile_s={self.seconds:.3f}")


class Tracer:
    """The profiler over the window's first `max_calls` engine calls.

    The device records every operation of the engine's while loops, and
    its trace buffer holds a few million events, so a traced window is
    cut short after a fixed number of engine calls (`trace_calls` in the
    mix). The `bench:window` span marks the traced stretch. Python
    function tracing stays off: it would slow the host it measures."""

    def __init__(self, logdir: str, max_calls: int):
        self.logdir, self.max_calls = logdir, int(max_calls)
        self.calls, self.active, self._span = 0, False, None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench:window")
        self._span.__enter__()
        self.active = True

    def after_call(self):
        if self.active:
            self.calls += 1
            if self.calls >= self.max_calls:
                self.stop()

    def stop(self):
        if self.active:
            import jax

            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""

    cell: str
    setup_s: float
    window_s: float
    records: List[dict]            # per query: latency_s, work, stats
    trace: Optional[dict] = None   # `trace.reduce` of the window


def log(msg: str) -> None:
    print(msg, flush=True)


def require_chips(devices, n: int) -> None:
    if not devices or devices[0].platform == "cpu":
        raise NoChip("no accelerator found (JAX platform "
                     f"{devices[0].platform if devices else 'none'}); "
                     "the benchmark does not run on the CPU")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devices)}")


def enable_compile_cache() -> str:
    """The persistent compilation cache: `JAX_COMPILATION_CACHE_DIR`
    where set, else `.jax_cache` at the checkout's root (a fixed path:
    the path is part of an entry's key)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(spec.ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def peak_memory_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def sample(queries: List[generate.Query], n_run: int, seed: int,
           n: int) -> List[tuple]:
    """`n` (query, scenario) pairs of the window, drawn from the seed and
    spread evenly over the applications, so the longest is among them."""
    by_app: Dict[str, List[tuple]] = {}
    for qi in range(n_run):
        q = queries[qi]
        for sc in q.scenarios:
            by_app.setdefault(q.tasks[sc.task]["app"], []).append((qi, sc))
    rng = np.random.default_rng(generate.seed_words(seed) + [2])
    per = max(1, math.ceil(n / max(len(by_app), 1)))
    out = []
    for app in sorted(by_app):
        pool = by_app[app]
        pick = rng.choice(len(pool), size=min(per, len(pool)), replace=False)
        out += [pool[i] for i in sorted(pick)]
    return out


def check(config: dict, queries: List[generate.Query], results: List,
          picks: List[tuple]) -> tuple:
    """(numbers, queries found wrong) of the sampled scenarios."""
    from bench.system import System

    limits = config["correct"]["limits"]
    parts, wrong = [], set()
    t0 = float(config["scheduler"]["t0"])
    for qi, sc in picks:
        task = queries[qi].tasks[sc.task]
        ref = reference.simulate(
            config["apps"][task["app"]], task["pred"], task["act"],
            sc.c_max, sc.order, config["public_cloud"],
            replicas=sc.replicas, release=task["release"], t0=t0,
            init_window=config["scheduler"].get("init_window_s"))
        try:
            res = results[qi][sc.task]
            got = System.fields(res, sc.index)
            part = compare.compare(got, ref)
            if System.labels(res, sc.index) != (sc.order, sc.c_max,
                                                sc.replicas):
                part["decision_mismatches"] += 1
        except (IndexError, KeyError, AttributeError, TypeError) as e:
            print(f"check: scenario {sc} of query {qi}: {e!r}",
                  file=sys.stderr)
            part = dict(decision_mismatches=1, float_gap=1.0)
        if not compare.verdict(part, limits):
            wrong.add(qi)
        parts.append(part)
    return compare.combine(parts), len(wrong)


def run_cell(cell: str, seed: int, seconds: float, traced: bool, *,
             control: bool = False, check_device: bool = True,
             t_start: Optional[float] = None, bm: Optional[dict] = None,
             config: Optional[dict] = None,
             mix: Optional[dict] = None) -> dict:
    """One run of `cell`; returns the result line's object."""
    t_start = time.perf_counter() if t_start is None else t_start
    bm = spec.benchmark() if bm is None else bm
    entry = spec.workload(bm, cell)
    config = spec.config(bm, entry["config"]) if config is None else config
    mix = spec.traffic(entry["traffic"]) if mix is None else mix

    import jax

    devices = jax.devices()
    t_devices = time.perf_counter()
    if check_device:
        require_chips(devices, int(entry["chips"]))
    cache = enable_compile_cache()
    from bench.system import System

    system = System(config)
    t_build = time.perf_counter()
    n_max = int(math.ceil(seconds * float(mix["queries_per_s_max"]))) + 1
    queries = generate.build(config, mix, seed, n_max)
    warm = generate.warmup(config, mix, seed)
    sent = queries
    if control:
        # the control: the timed path fed float32-rounded inputs
        sent = [generate.rounded(q) for q in queries]
        warm = [generate.rounded(q) for q in warm]
    t_warm = time.perf_counter()
    with CompileClock() as cc:
        for q in warm:
            system.run(q)
    setup_s = time.perf_counter() - t_start
    log(f"setup: cell={cell} seed={seed} setup_s={setup_s:.3f} "
        f"(to devices {t_devices - t_start:.3f}, build "
        f"{t_warm - t_build:.3f} for {len(queries)} queries, warm-up "
        f"{setup_s - (t_warm - t_start):.3f} for {len(warm)} queries: {cc}) "
        f"cache={cache}")
    del warm

    tracer = None
    records, results = [], []
    with contextlib.ExitStack() as stack:
        if traced:
            tracer = Tracer(tempfile.mkdtemp(prefix="bench-trace-"),
                            mix["trace_calls"])
            stack.enter_context(system.spans(after_dispatch=tracer.after_call))
            tracer.start()
            stack.callback(tracer.stop)

        def span(name):
            return (jax.profiler.TraceAnnotation(name) if traced
                    else contextlib.nullcontext())

        with CompileClock() as cc:
            t_w0 = time.perf_counter()
            while True:
                i = len(records)
                if i == len(sent):
                    raise RuntimeError(
                        f"the run needs more than {len(sent)} queries: "
                        "raise the mix's queries_per_s_max")
                ts = time.perf_counter()
                with span("bench:query"):
                    res = system.run(sent[i])
                te = time.perf_counter()
                records.append(dict(latency_s=te - ts, work=sent[i].work,
                                    stats=system.stats()))
                results.append(res)
                if te - t_w0 >= seconds:
                    break
    window_s = te - t_w0
    log(f"window: queries={len(records)} window_s={window_s:.6f} "
        f"in the window: {cc}")
    device = dict(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices),
                  memory_peak_bytes=peak_memory_bytes(devices))
    run = Run(cell, setup_s, window_s, records)
    if traced:
        from bench import trace

        t_tr = time.perf_counter()
        run.trace = trace.reduce(trace.load(trace.find_xplane(tracer.logdir)))
        run.trace["engine_calls"] = tracer.calls
        shutil.rmtree(tracer.logdir, ignore_errors=True)
        device.update(busy_s=run.trace["busy_s"],
                      window_s=run.trace["window_s"])
        log(f"trace: engine_calls={tracer.calls} "
            f"window_s={run.trace['window_s']:.6f} "
            f"busy_s={run.trace['busy_s']:.6f} "
            f"dropped={run.trace['dropped']} "
            f"read_s={time.perf_counter() - t_tr:.3f}")
    metrics = {}
    for m in spec.metrics(bm, cell, traced):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = dict(value=float(value), unit=m["unit"])

    t_chk = time.perf_counter()
    picks = sample(queries, len(records),
                   seed, int(mix["check_scenarios"]))
    numbers, n_wrong = check(config, queries, results, picks)
    limits = config["correct"]["limits"]
    ok = compare.verdict(numbers, limits)
    log(f"check: scenarios={len(picks)} "
        f"queries={len({qi for qi, _ in picks})} "
        f"reference_s={time.perf_counter() - t_chk:.3f} correct={ok}")
    out = dict(correct=ok, attempted=len(records), failed=n_wrong,
               metrics=metrics, device=device)
    if traced:
        out["breakdown"] = dict(device_ops=run.trace["device_ops"],
                                idle_gaps=run.trace["idle_gaps"])
    out["compared"] = {k: dict(value=numbers[k], limit=limits[k])
                       for k in limits}
    return out
