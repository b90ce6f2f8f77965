"""Sec. V-B tables: per-stage MAPE of the latency and output-size models.

Paper: matrix 6.5/4.6%% private; video 4.4/1.4/8.5/51%%; image 13.7/12.2/
12.9%% (high-variance small-latency regime); size models 0.2-38%%.
"""
from __future__ import annotations


from repro.core import mape

from .common import app_setup, print_rows, row, timed


def run(full: bool = False):
    rows = []
    for app in ("matrix", "video", "image"):
        spec, sched, pred_d, act, tr, te = app_setup(app, full)
        pm = sched.perf_model
        pred, t = timed(pm.predict, te["base_features"])
        M = spec.dag.num_stages
        names = [s.name for s in spec.dag.stages]
        priv = [mape(te["private"][:, k], pred["P_private"][:, k])
                for k in range(M)]
        pub = [mape(te["public"][:, k], pred["P_public"][:, k])
               for k in range(M)]
        size = [mape(te["outsize"][:, k], pred["sizes"][:, k])
                for k in range(M)]
        J = te["private"].shape[0]
        rows.append(row(
            f"mape/{app}", t / J * 1e6,
            "priv=" + "|".join(f"{n}:{v:.1f}" for n, v in zip(names, priv))
            + ";pub=" + "|".join(f"{v:.1f}" for v in pub)
            + ";size=" + "|".join(f"{v:.1f}" for v in size)))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys
    print_rows(run(full="--full" in sys.argv))
