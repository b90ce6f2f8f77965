# One function per paper table/figure. Prints ``name,us_per_call,derived``
# CSV. Default = quick pass (reduced scale); ``--full`` = paper scale
# (774/150, 800/200 jobs, full input sizes, longer MILP budget).
import argparse
import sys
import traceback

from . import (bench_hybrid_serving, bench_kernels, fig3_optimal_vs_greedy,
               fig4_cmax_sweep, fig5_makespan_accuracy, headline_speedup_cost,
               roofline_table, table_model_mape)
from .common import print_rows

MODULES = [
    ("fig3", fig3_optimal_vs_greedy),
    ("fig4", fig4_cmax_sweep),
    ("fig5", fig5_makespan_accuracy),
    ("mape", table_model_mape),
    ("headline", headline_speedup_cost),
    ("kernels", bench_kernels),
    ("serving", bench_hybrid_serving),
    ("roofline", roofline_table),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig3,fig4,...")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    ok = True
    for name, mod in MODULES:
        if only and name not in only:
            continue
        try:
            print_rows(mod.run(full=args.full))
        except Exception:
            ok = False
            print(f"{name},0,ERROR", file=sys.stdout)
            traceback.print_exc()
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
