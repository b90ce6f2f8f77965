"""The vector engine's own spans and counters (``vectorsim._LAST_RUN_STATS``).

Every sweep times its host phases as ``vs:`` spans (on the profiler's
host plane and in the record), splits each engine call into transfer,
launch, wait and copy-back, counts the call's bytes each way and the
arrays handed over and copied back, and counts the while-loop trips its lanes needed
against those the lockstep loop ran. The suite pins:

* every key in the record after a sweep on each inner-loop impl, the
  byte counts against the shapes of what crossed (the engine's outputs
  at their emitted widths, less the pager's carry), the two buffers a
  monolithic call hands over and the ten arrays it copies back, and the
  four ``_dispatch`` spans inside
  ``engine_s``;
* occupancy: 100% for a one-scenario call, and a hand reduction of the
  ``trips`` output for a fused call of unequal scenarios;
* a paged stream: still exact, counted per page (each page copies the
  carry back too), ``trips`` dropped;
* the spans' nesting in a CPU profiler trace.
"""
import glob

import jax
import numpy as np
import pytest

from repro.core import APPS
from repro.core import vectorsim
from repro.core.vectorsim import sweep_scenarios
from tests.test_streaming import assert_bit_exact, burst_workload, run_vec
from tests.test_vectorsim import grid_for, workload

SPANS = ("prep_s", "plan_s", "engine_s", "h2d_s", "launch_s", "wait_s",
         "d2h_s", "finalize_s")
COUNTERS = ("engine_calls", "h2d_arrays", "h2d_bytes", "d2h_arrays",
            "d2h_bytes", "loop_trips", "lane_trips", "lane_slots")
#: what a monolithic call of a fault-free, uncapped, warm engine copies
#: back: the index outputs as int32, the pager's carry and the outputs
#: its flags make constant left on the device
MONOLITHIC = {"start": np.float64, "end": np.float64,
              "completion": np.float64, "cost_j": np.float64,
              "public_mask": np.bool_, "init_off": np.bool_,
              "trips": np.int32, "provider": np.int32, "replica": np.int32,
              "segment": np.int32}


def _nbytes(arrays):
    return sum(int(np.prod(np.shape(a))) * np.asarray(a).dtype.itemsize
               for a in arrays)


def _emitted_shapes(fn, args):
    """Shapes and dtypes of everything the compiled engine emits."""
    layout, bufs = vectorsim._pack(args)
    with jax.enable_x64(True):
        return jax.eval_shape(lambda *b: fn(layout, *b), *bufs)


@pytest.fixture
def calls(monkeypatch):
    """(args, outputs, emitted) of every engine call: outputs as
    `_dispatch` returned them, emitted the engine's own output shapes."""
    seen = []
    orig = vectorsim._dispatch

    def spy(fn, args, S, n_dev, **kw):
        out = orig(fn, args, S, n_dev, **kw)
        seen.append((args, dict(out), _emitted_shapes(fn, args)))
        return out

    monkeypatch.setattr(vectorsim, "_dispatch", spy)
    return seen


def _whatif(seed, J=48):
    """One application x {spt, hcf} x 5 deadlines: a what-if query."""
    dag = APPS["image"]
    pred, act = workload(dag, J, seed)
    return [dict(dag=dag, pred=pred, act=act, orders=("spt", "hcf"),
                 c_max_grid=grid_for(dag, pred,
                                     (0.3, 0.45, 0.6, 0.9, 1.2)))]


def _lockstep(trips):
    """(loop, lane, slots) trips of one single-device call, by hand."""
    loop = sum(max(int(trips[l, k]) for l in range(trips.shape[0]))
               for k in range(trips.shape[1]))
    lane = sum(int(x) for x in trips.ravel())
    return loop, lane, trips.shape[0] * loop


@pytest.mark.parametrize("impl", ["loop", "scan"])
def test_record_holds_every_span_and_counter(impl, calls):
    sweep_scenarios(_whatif(seed=101 if impl == "loop" else 102),
                    engine_impl=impl)
    st = vectorsim._LAST_RUN_STATS
    for key in SPANS + COUNTERS:
        assert key in st, key
    assert st["impl"] == impl
    assert all(st[k] >= 0.0 for k in SPANS)
    assert (st["h2d_s"] + st["launch_s"] + st["wait_s"] + st["d2h_s"]
            <= st["engine_s"])
    assert st["plan_s"] <= st["prep_s"]
    (args, out, emitted), = calls
    assert st["engine_calls"] == 1
    # the 24 arguments cross as one float64 and one bool buffer
    assert len(args) == 24 and st["h2d_arrays"] == 2
    assert st["h2d_bytes"] == _nbytes(args)
    copied = {k: v for k, v in emitted.items()
              if k not in ("qexit", "clocks")}
    assert st["d2h_bytes"] == sum(v.size * v.dtype.itemsize
                                  for v in copied.values())
    assert st["d2h_arrays"] == len(copied) == 10
    assert {k: v.dtype for k, v in out.items()} == MONOLITHIC
    assert out["trips"].shape == (10, 3) and out["trips"].dtype == np.int32
    assert (st["loop_trips"], st["lane_trips"], st["lane_slots"]) \
        == _lockstep(out["trips"])
    assert 0 < st["lane_trips"] <= st["lane_slots"]


def test_one_scenario_call_is_fully_occupied(calls):
    task = dict(_whatif(seed=103)[0], orders=("spt",), c_max_grid=(20.0,))
    sweep_scenarios([task])
    st = vectorsim._LAST_RUN_STATS
    (_, out, _), = calls
    assert out["trips"].shape == (1, 3)
    assert st["loop_trips"] == int(out["trips"].sum()) > 0
    assert st["lane_trips"] == st["lane_slots"]


@pytest.mark.parametrize("impl", ["loop", "scan"])
def test_fused_call_occupancy_is_the_trips_reduced_by_hand(impl, calls):
    """Video (4 stages) and image (3, padded with an inert stage) fuse
    into one call at a common J: lanes need unequal trips."""
    J = 40
    tasks = []
    for i, name in enumerate(("video", "image")):
        dag = APPS[name]
        pred, act = workload(dag, J, seed=110 + i)
        tasks.append(dict(dag=dag, pred=pred, act=act,
                          orders=("spt", "hcf"),
                          c_max_grid=grid_for(dag, pred, (0.2, 0.6, 1.5))))
    sweep_scenarios(tasks, engine_impl=impl)
    st = vectorsim._LAST_RUN_STATS
    (_, out, _), = calls
    trips = out["trips"]
    assert trips.shape == (12, 4)
    assert st["engine_calls"] == 1
    assert (st["loop_trips"], st["lane_trips"], st["lane_slots"]) \
        == _lockstep(trips)
    occupancy = 100.0 * st["lane_trips"] / st["lane_slots"]
    assert 0.0 < occupancy < 100.0
    # the image lanes' padded stage is inert: its loop never runs
    assert (trips[6:, 3] == 0).all() and (trips[:6] > 0).all()


def test_paged_stream_is_exact_and_counted_per_page(calls, monkeypatch):
    dag = APPS["image"]
    pred, act, release = burst_workload(dag, 64, seed=3)
    kw = dict(c_max_grid=(8.0, 40.0), orders=("spt", "hcf"))
    mono = run_vec(dag, pred, act, release, None, **kw)
    finalized = []
    orig = vectorsim._finalize

    def spy(task, out):
        finalized.append(set(out))
        return orig(task, out)

    monkeypatch.setattr(vectorsim, "_finalize", spy)
    calls.clear()
    paged = run_vec(dag, pred, act, release, 17, **kw)
    assert_bit_exact(paged, mono)
    st = vectorsim._LAST_RUN_STATS
    assert st["pages"] > 1 and st["page_retries"] == 0
    assert st["engine_calls"] == len(calls) == st["pages"]
    assert st["h2d_arrays"] == 2 * len(calls)
    assert st["h2d_bytes"] == sum(_nbytes(a) for a, _, _ in calls)
    # a page copies every output the engine emits, the carry included
    assert st["d2h_bytes"] == sum(
        sum(v.size * v.dtype.itemsize for v in e.values())
        for _, _, e in calls)
    assert st["d2h_arrays"] == sum(len(e) for _, _, e in calls) \
        == 12 * len(calls)
    assert all({"qexit", "clocks"} <= set(o) for _, o, _ in calls)
    assert st["loop_trips"] == sum(_lockstep(o["trips"])[0]
                                   for _, o, _ in calls)
    assert st["lane_trips"] <= st["lane_slots"]
    assert "plan_s" in st and "engine_s" in st
    (keys,) = finalized
    assert "trips" not in keys and "clocks" not in keys


def _vs_events(logdir):
    from jax.profiler import ProfileData

    path, = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("vs:"):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_spans_nest_under_one_sweep_in_the_profiler_trace(tmp_path):
    tasks = _whatif(seed=104)
    sweep_scenarios(tasks)  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        sweep_scenarios(tasks)
    finally:
        jax.profiler.stop_trace()
    events = _vs_events(tmp_path)
    (_, s0, s1, meta), = [e for e in events if e[0] == "vs:sweep"]
    assert meta["sweep"] == next(vectorsim._SWEEP_IDS) - 1
    names = {e[0] for e in events}
    assert {"vs:prep", "vs:engine", "vs:h2d", "vs:launch", "vs:wait",
            "vs:d2h", "vs:finalize"} <= names
    for name, a, b, _ in events:
        assert s0 <= a <= b <= s1, name
    engines = [(a, b) for n, a, b, _ in events if n == "vs:engine"]
    for name, a, b, _ in events:
        if name in ("vs:h2d", "vs:launch", "vs:wait", "vs:d2h"):
            assert any(e0 <= a <= b <= e1 for e0, e1 in engines), name
