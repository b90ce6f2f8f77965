"""Reduction of a profiler trace to the benchmark's device numbers.

`load` reads the `.xplane.pb` that `jax.profiler` writes into plain
planes: each a name and lines, each line a name and its events as
(names, start ns, duration ns). `reduce` then works on those alone, so
the tests can feed it a small recorded trace:

* busy: the union of the intervals of the device's operations (the
  `XLA Ops` line of each `/device:` plane), clipped to the window, and
  averaged over the devices;
* window: the harness's `bench:window` host span (else the span of the
  device's operations);
* idle share: 1 - busy / window;
* top device operations by summed duration, and the summed duration of
  each XLA module (a jitted program);
* idle gaps: the stretches of the window in which the first device runs
  nothing, split at the edges of the harness's `bench:` host spans and
  summed per innermost span: what the host was doing while the device
  sat idle;
* dropped: whether the device reported that its trace buffer overflowed
  (`Trace Buffers Dropped`), which leaves operations out of the trace.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional

import numpy as np

OPS_LINE = "XLA Ops"
DROPPED = "Trace Buffers Dropped"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load(path: str) -> List[dict]:
    """The trace's planes as plain data."""
    from jax.profiler import ProfileData

    planes = []
    for pl in ProfileData.from_file(path).planes:
        lines = []
        for ln in pl.lines:
            names, starts, durs = [], [], []
            for ev in ln.events:
                # an HLO op's event name is its whole instruction text;
                # keep the instruction's name
                names.append(ev.name.split(" = ", 1)[0])
                starts.append(ev.start_ns)
                durs.append(ev.duration_ns)
            lines.append(dict(name=ln.name, names=names,
                              start_ns=np.asarray(starts, np.float64),
                              dur_ns=np.asarray(durs, np.float64)))
        planes.append(dict(name=pl.name, lines=lines))
    return planes


def line(plane: dict, name: str) -> Optional[dict]:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln
    return None


def device_planes(planes: List[dict]) -> List[dict]:
    """Planes of accelerators that carry operations."""
    return [p for p in planes if p["name"].startswith("/device:")
            and not p["name"].startswith("/device:CPU")
            and line(p, OPS_LINE) is not None]


def host_spans(planes: List[dict], prefix: str = SPAN_PREFIX):
    """(names, start ns, end ns) of the host's annotated spans."""
    names, s, e = [], [], []
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for ln in p["lines"]:
            for n, st, d in zip(ln["names"], ln["start_ns"], ln["dur_ns"]):
                if n.startswith(prefix):
                    names.append(n)
                    s.append(st)
                    e.append(st + d)
    return names, np.asarray(s, np.float64), np.asarray(e, np.float64)


def union(starts: np.ndarray, ends: np.ndarray):
    """Disjoint, sorted (starts, ends) covering the given intervals."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    cm = np.maximum.accumulate(e)
    brk = np.concatenate([[True], s[1:] > cm[:-1]])
    first = np.flatnonzero(brk)
    last = np.concatenate([first[1:] - 1, [s.size - 1]])
    return s[first], cm[last]


def _clip(s, e, w0, w1):
    s, e = np.maximum(s, w0), np.minimum(e, w1)
    keep = e > s
    return s[keep], e[keep]


def _top(totals: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_by_span(gaps_s, gaps_e, names, hs, he, w0, w1) -> Dict[str, float]:
    """Idle seconds per host span: the window is cut at every span's
    edges, each piece goes to the innermost span that covers it, and
    gets the idle time that falls inside it."""
    edges = np.unique(np.clip(np.concatenate([[w0, w1], hs, he]), w0, w1))
    mid = (edges[:-1] + edges[1:]) / 2.0
    label = np.full(mid.size, "(no span)", dtype=object)
    # paint the spans longest first, so shorter (inner) ones overwrite
    for k in np.argsort(-(he - hs), kind="stable"):
        lo = np.searchsorted(mid, hs[k], side="left")
        hi = np.searchsorted(mid, he[k], side="left")
        label[lo:hi] = names[k][len(SPAN_PREFIX):]
    # idle time before t: the gaps that ended, plus the one open at t
    done = np.concatenate([[0.0], np.cumsum(gaps_e - gaps_s)])
    starts = np.append(gaps_s, np.inf)

    def idle_before(t):
        i = np.searchsorted(gaps_e, t, side="left")
        return done[i] + np.maximum(t - starts[i], 0.0)

    idle = idle_before(edges[1:]) - idle_before(edges[:-1])
    out: Dict[str, float] = {}
    for lab, g in zip(label, idle / 1e9):
        if g > 0:
            out[lab] = out.get(lab, 0.0) + float(g)
    return out


def reduce(planes: List[dict]) -> dict:
    """busy_s, window_s, idle_pct, device_ops, modules, idle_gaps,
    dropped."""
    devs = device_planes(planes)
    names, hs, he = host_spans(planes)
    win = [i for i, n in enumerate(names) if n == WINDOW_SPAN]
    if win:
        w0, w1 = float(hs[win[0]]), float(he[win[0]])
    elif devs:
        ops = [line(p, OPS_LINE) for p in devs]
        w0 = min(float(o["start_ns"].min()) for o in ops if o["names"])
        w1 = max(float((o["start_ns"] + o["dur_ns"]).max())
                 for o in ops if o["names"])
    else:
        w0 = w1 = 0.0
    window_s = (w1 - w0) / 1e9
    busy, op_tot, mod_tot = [], {}, {}
    gaps_s = gaps_e = np.zeros(0)
    for i, p in enumerate(devs):
        ops = line(p, OPS_LINE)
        s, e = _clip(ops["start_ns"], ops["start_ns"] + ops["dur_ns"],
                     w0, w1)
        us, ue = union(s, e)
        busy.append(float((ue - us).sum()) / 1e9)
        inside = (ops["start_ns"] < w1) & (ops["start_ns"] + ops["dur_ns"]
                                           > w0)
        for n, d in zip(np.asarray(ops["names"], object)[inside],
                        ops["dur_ns"][inside]):
            op_tot[n] = op_tot.get(n, 0.0) + d / 1e9
        mods = line(p, MODULES_LINE)
        if mods is not None:
            for n, st, d in zip(mods["names"], mods["start_ns"],
                                mods["dur_ns"]):
                if st < w1 and st + d > w0:
                    mod_tot[n] = mod_tot.get(n, 0.0) + d / 1e9
        if i == 0:
            gaps_s = np.concatenate([[w0], ue])
            gaps_e = np.concatenate([us, [w1]])
            keep = gaps_e > gaps_s
            gaps_s, gaps_e = gaps_s[keep], gaps_e[keep]
    n_dev = max(len(devs), 1)
    op_tot = {k: v / n_dev for k, v in op_tot.items()}
    mod_tot = {k: v / n_dev for k, v in mod_tot.items()}
    gap_tot = idle_by_span(gaps_s, gaps_e, names, hs, he, w0, w1)
    busy_s = float(np.mean(busy)) if busy else 0.0
    dropped = any(DROPPED in ln["names"] for p in devs for ln in p["lines"])
    return dict(
        busy_s=busy_s, window_s=window_s, n_devices=len(devs),
        dropped=dropped,
        idle_pct=(100.0 * (1.0 - busy_s / window_s) if window_s > 0
                  else None),
        device_ops=_top(op_tot), modules=mod_tot, idle_gaps=_top(gap_tot))
