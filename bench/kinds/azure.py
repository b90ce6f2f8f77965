"""Queries of a replayed Azure Functions night: one fixed stretch of a
recorded invocation day, paged through the engine.

The day is drawn once, from the configuration's `stream_seed`, with the
sampling of the program's `azure:` workload family, at the committed
sample's own hourly counts (no dilution): each invocation is one of the
sample's functions, drawn in proportion to its daily count; its release
hour follows the function's hourly profile, uniform within the hour; its
duration is the function's mean, jittered lognormally at the function's
coefficient of variation and split over the application's stages by the
function's fixed stage weights; public latencies are 0.8-1.6 times the
private ones and transfers scale with the function's memory; what ran
is that times the model error (lognormal, sigma `noise`). The recorded
night is the first `invocations` releases at or after
`stretch_start_s` with what ran: the same in every query of every run.

A query replays the recorded night against three deadlines, one drawn
in each of the mix's tiers, under each order. The scheduler plans with
predictions drawn afresh for each query around what ran, at the model
error. It pages at `chunk_jobs`. The warm-up pages the night at every
size of `page_families`, so that every page size either side of a
comparison can reach is compiled before the window.

This module reads the sample file itself; it imports nothing of the
program.
"""
from __future__ import annotations

import csv
import functools
import gzip
import json
import os

import numpy as np

from bench import spec

#: the `azure:` family draws the per-function stage weights with this
#: entropy tag, independent of the stream seed
SAMPLE_TAG = 20190715


@functools.lru_cache(maxsize=2)
def _sample(path: str):
    with gzip.open(os.path.join(spec.ROOT, path), "rt", newline="") as f:
        rows = list(csv.reader(f))
    col = {name: i for i, name in enumerate(rows[0])}
    body = rows[1:]

    def num(name):
        return np.array([float(r[col[name]]) for r in body])

    hourly = np.array([[float(r[col[f"h{h:02d}"]]) for h in range(24)]
                       for r in body])
    return num("mem_mb"), num("avg_dur_s"), num("cv_dur"), hourly


def day(wl: dict, M: int):
    """(what ran, release) of the whole recorded day for an application
    of `M` stages, every invocation in draw order."""
    mem_mb, avg_dur, cv_dur, counts = _sample(wl["sample"])
    J, F = int(wl["day_invocations"]), counts.shape[0]
    rng = np.random.default_rng([int(wl["stream_seed"]), 0, 911])
    p_f = counts.sum(axis=1)
    f_j = rng.choice(F, size=J, p=p_f / p_f.sum())
    cp = np.cumsum(counts / counts.sum(axis=1, keepdims=True), axis=1)
    h_j = np.minimum((rng.random(J)[:, None] > cp[f_j]).sum(axis=1), 23)
    release = (h_j + rng.random(J)) * (float(wl["day_horizon_s"]) / 24.0)
    cv = cv_dur[f_j]
    dur = avg_dur[f_j] * np.exp(rng.normal(0.0, 1.0, J) * cv - 0.5 * cv * cv)
    wts = np.random.default_rng([SAMPLE_TAG, 7, M]).gamma(2.0, 1.0, (F, M))
    wts = wts / wts.sum(axis=1, keepdims=True)
    P_priv = dur[:, None] * wts[f_j]
    gb = mem_mb[f_j][:, None] / 512.0
    pred = dict(P_private=P_priv,
                P_public=P_priv * rng.uniform(0.8, 1.6, (J, M)),
                upload=gb * rng.uniform(0.02, 0.2, (J, M)),
                download=gb * rng.uniform(0.02, 0.2, (J, M)))
    noise = float(wl["noise"])
    return ({k: v * rng.lognormal(0.0, noise, v.shape)
             for k, v in pred.items()}, release)


@functools.lru_cache(maxsize=4)
def _stretch(wl_json: str, M: int):
    wl = json.loads(wl_json)
    act, release = day(wl, M)
    later = np.flatnonzero(release >= float(wl["stretch_start_s"]))
    pick = later[np.argsort(release[later], kind="stable")]
    pick = pick[:int(wl["invocations"])]
    act, release = {k: v[pick] for k, v in act.items()}, release[pick]
    for x in list(act.values()) + [release]:
        x.flags.writeable = False
    return act, release


def stretch(config: dict, name: str):
    """(what ran, release) of the recorded night, in release order; the
    arrays are shared by every query and read-only."""
    return _stretch(json.dumps(config["workload"], sort_keys=True),
                    len(config["apps"][name]["stages"]))


def _tasks(config, mix, rng, name):
    act, release = stretch(config, name)
    noise = float(config["workload"]["noise"])
    pred = {k: v * rng.lognormal(0.0, noise, v.shape)
            for k, v in act.items()}
    c_max = tuple(float(rng.uniform(lo, hi))
                  for lo, hi in mix["deadline_tiers_s"])
    return [dict(app=name, pred=pred, act=act, release=release,
                 c_max_grid=c_max, orders=tuple(mix["orders"]),
                 replicas=None)]


def tasks(config, mix, rng, app_names):
    """(tasks, page size) of one query: the recorded night of the one
    application, against one deadline of each tier."""
    (name,) = app_names
    return _tasks(config, mix, rng, name), int(config["workload"]["chunk_jobs"])


def warm(config, mix, rng, app_names):
    """The recorded night paged at each size of the family ladder."""
    (name,) = app_names
    return [(_tasks(config, mix, rng, name), int(f))
            for f in config["workload"]["page_families"]]


def small(config):
    """The configuration at a size a CPU test runs: a stretch of 96
    invocations paged at 16."""
    wl = config["workload"]
    wl.update(invocations=96, chunk_jobs=16, page_families=[16, 32, 64, 96])
    return config
