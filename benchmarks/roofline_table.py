"""Roofline table from the dry-run JSONs (results/dryrun/*.json).

Prints one row per (arch x shape x mesh): the three terms, dominant
bottleneck, MODEL_FLOPS/HLO_FLOPs, and the roofline fraction.
"""
from __future__ import annotations

import glob
import json
import os

from .common import print_rows, row

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")


def run(full: bool = False, variant: str = "baseline", results_dir=RESULTS):
    rows = []
    for path in sorted(glob.glob(os.path.join(results_dir, f"*_{variant}.json"))):
        r = json.load(open(path))
        tag = f"{r['arch']}/{r['shape']}/{r['mesh']}"
        if r.get("skipped"):
            rows.append(row(f"roofline/{tag}", 0.0, "SKIP:" + r["reason"][:60]))
            continue
        if not r.get("ok"):
            rows.append(row(f"roofline/{tag}", 0.0, "FAIL"))
            continue
        t = r["terms"]
        hbm = r["memory"].get("per_device_hbm_bytes", 0) / 2 ** 30
        rows.append(row(
            f"roofline/{tag}", t["bound_s"] * 1e6,
            f"comp={t['compute_s']:.4f}s;mem={t['memory_s']:.4f}s;"
            f"coll={t['collective_s']:.4f}s;dom={t['dominant']};"
            f"useful={t['useful_flops_ratio']:.2f};"
            f"frac={t['roofline_fraction']:.3f};hbm={hbm:.1f}GiB"))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys
    print_rows(run(full="--full" in sys.argv))
