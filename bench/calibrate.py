"""Read the numbers `correct` compares over many seeds in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds 5 [--control] [--out readings.json]

Each seed is one run of the cell as `run.py` makes it (set-up, window,
check), sharing the process's compiled programs, so a dozen seeds cost
one set-up. With `--control` the timed path gets float32-rounded
inputs. Prints one line per seed and, with `--out`, writes every
reading as JSON. The limits in the configurations are set from these
readings: above the largest that sound runs give, below the smallest
the control gives. The benchmark's own runs never call this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from bench import harness

    readings = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        try:
            out = harness.run_cell(args.workload, seed, args.seconds, False,
                                   control=args.control,
                                   t_start=T_START if i == 0 else None)
        except harness.NoChip as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 3
        row = dict(seed=seed, control=args.control, correct=out["correct"],
                   attempted=out["attempted"], failed=out["failed"],
                   compared={k: v["value"]
                             for k, v in out["compared"].items()},
                   metrics={k: v["value"] for k, v in out["metrics"].items()},
                   device=out["device"])
        readings.append(row)
        print("reading " + json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(readings, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
