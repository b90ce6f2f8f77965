"""The trace reduction on a small hand-made trace and on an extract of a
trace recorded on a TPU v5e."""
import json
import os

import numpy as np
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _line(name, events):
    names = [e[0] for e in events]
    return dict(name=name, names=names,
                start_ns=np.array([e[1] for e in events], np.float64),
                dur_ns=np.array([e[2] for e in events], np.float64))


def _planes():
    host = dict(name="/host:CPU", lines=[_line("python", [
        ("bench:window", 0, 100), ("bench:query", 0, 70),
        ("bench:prep", 0, 15), ("bench:dispatch", 15, 50),
        ("not ours", 0, 100)])])
    dev = dict(name="/device:TPU:0", lines=[
        _line("XLA Ops", [("fusion.1", 10, 20), ("while.2", 20, 20),
                          ("fusion.1", 50, 10), ("copy.3", 95, 15),
                          ("late", 120, 5)]),
        _line("XLA Modules", [("jit_run_one(1)", 10, 50),
                              ("jit_other", 95, 15)])])
    return [host, dev]


def test_busy_is_the_union_of_op_intervals_clipped_to_the_window():
    r = trace.reduce(_planes())
    # [10, 40] + [50, 60] + [95, 100]: overlap counted once, the op that
    # runs past the window and the one after it cut off
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["idle_pct"] == pytest.approx(55.0)
    assert r["n_devices"] == 1


def test_top_ops_and_modules():
    r = trace.reduce(_planes())
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(30e-9)]
    assert {k for k, _ in r["device_ops"]} == {"fusion.1", "while.2",
                                                 "copy.3"}
    assert r["modules"] == {"jit_run_one(1)": pytest.approx(50e-9),
                            "jit_other": pytest.approx(15e-9)}


def test_idle_time_goes_to_the_innermost_host_span():
    r = trace.reduce(_planes())
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # gaps [0, 10], [40, 50], [60, 95]; spans prep [0, 15], dispatch
    # [15, 65], query [0, 70], window [0, 100]: the gap [60, 95] splits
    # into dispatch (60-65), query (65-70) and the window alone (70-95)
    assert gaps == {"prep": pytest.approx(10e-9),
                    "dispatch": pytest.approx(15e-9),
                    "query": pytest.approx(5e-9),
                    "window": pytest.approx(25e-9)}
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])


def test_union_merges_nested_and_touching_intervals():
    s, e = trace.union(np.array([5.0, 0.0, 1.0, 10.0]),
                       np.array([10.0, 4.0, 2.0, 12.0]))
    assert s.tolist() == [0.0, 5.0] and e.tolist() == [4.0, 12.0]


def test_without_a_window_span_the_device_ops_bound_the_window():
    planes = _planes()
    planes[0]["lines"][0] = _line("python", [("bench:query", 0, 70)])
    r = trace.reduce(planes)
    assert r["window_s"] == pytest.approx(115e-9)  # 10 .. 125


def test_recorded_v5e_trace_extract():
    """An extract of a trace the harness recorded on one TPU v5e chip:
    `paper.whatif`, the first 9.25 ms of the window's second query (its
    first 3,000 device operations), times from that query's start, and
    the window span set to the extract."""
    path = os.path.join(DATA, "v5e_whatif_extract.json")
    with open(path) as f:
        raw = json.load(f)
    planes = [dict(name=p["name"], lines=[
        dict(name=ln["name"], names=ln["names"],
             start_ns=np.asarray(ln["start_ns"], np.float64),
             dur_ns=np.asarray(ln["dur_ns"], np.float64))
        for ln in p["lines"]]) for p in raw]
    r = trace.reduce(planes)
    assert r["n_devices"] == 1
    ops = trace.line(trace.device_planes(planes)[0], trace.OPS_LINE)
    assert 0 < r["busy_s"] <= r["window_s"]
    # the union never exceeds the summed durations
    assert r["busy_s"] <= ops["dur_ns"].sum() / 1e9 + 1e-12
    assert 0 <= r["idle_pct"] < 100
    gap_s = sum(v for _, v in r["idle_gaps"])
    assert gap_s <= r["window_s"] - r["busy_s"] + 1e-9
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    # the device waits while the host prepares the query, plans the
    # offload and copies the inputs over; every idle second has a span
    assert {"prep", "plan", "dispatch"} <= set(gaps)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["modules"] and not r["dropped"]
