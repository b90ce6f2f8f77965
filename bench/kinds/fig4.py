"""Queries of the Fig-4 batch: each application's batch released at
once, with lognormal private latencies, public latencies a uniform
factor of them, uniform transfer times and lognormal prediction error;
deadlines are fractions of the ideal all-private makespan on the DAG's
own pool. A mix with `pool_sizings` adds that many seeded pool sizings
to each application's grid.
"""
from __future__ import annotations

import numpy as np

from bench.generate import app_replicas


def _inputs(rng: np.random.Generator, app: dict, lat: dict):
    J, M = int(app["jobs"]), len(app["stages"])
    P_priv = (rng.lognormal(0.0, lat["private_lognormal_sigma"], (J, M))
              * lat["private_scale_s"])
    lo, hi = lat["public_over_private"]
    tlo, thi = lat["transfer_s"]
    pred = dict(P_private=P_priv,
                P_public=P_priv * rng.uniform(lo, hi, (J, M)),
                upload=rng.uniform(tlo, thi, (J, M)),
                download=rng.uniform(tlo, thi, (J, M)))
    act = {k: v * rng.lognormal(0.0, lat["prediction_error_sigma"], v.shape)
           for k, v in pred.items()}
    return pred, act


def tasks(config, mix, rng, app_names):
    """(tasks, page size) of one query: a batch never pages."""
    wl, apps = config["workload"], config["apps"]
    out = []
    for name in app_names:
        app = apps[name]
        pred, act = _inputs(rng, app, wl["latency_model"])
        base = float(pred["P_private"].sum()) / float(app_replicas(app).sum())
        out.append(dict(app=name, pred=pred, act=act, release=None,
                        c_max_grid=tuple(base * f
                                         for f in wl["deadline_fracs"]),
                        orders=tuple(mix["orders"]), replicas=None))
    n_pools = int(mix.get("pool_sizings", 0))
    if n_pools:
        lo, hi = wl["pool_sizing_replicas"]
        # redraw until the largest pool reaches the bound, so every query
        # compiles to the same replica-bound shape family
        while True:
            pools = [rng.integers(lo, hi + 1,
                                  size=(n_pools, len(apps[t["app"]]["stages"])))
                     for t in out]
            if max(int(p.max()) for p in pools) == hi:
                break
        for t, p in zip(out, pools):
            t["replicas"] = [row for row in p]
    return out, None


def warm(config, mix, rng, app_names):
    """One query of the application set compiles every family its
    queries use."""
    return [tasks(config, mix, rng, app_names)]


def small(config):
    """The configuration at a size a CPU test runs."""
    for app in config["apps"].values():
        app["jobs"] = 12
    return config
