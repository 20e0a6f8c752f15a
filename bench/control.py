"""Run a cell with the timed path broken, on several seeds, in one process.

    python bench/control.py --workload <name> --fault <control|...> \\
        --seeds 11,12,13 --seconds 10

Each seed is one run of the harness under the fault (`bench/faults.py`);
its result line must read `"correct": false`. The benchmark's own runs
never call this.
"""
import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import faults, harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True, choices=sorted(faults.FAULTS))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default="10")
    args = ap.parse_args()
    rc = 0
    for seed in args.seeds.split(","):
        with faults.FAULTS[args.fault]():
            rc |= harness.main(["--workload", args.workload, "--seed", seed,
                                "--seconds", args.seconds, "--trace", "0"],
                               root=ROOT, t_start=time.perf_counter())
    return rc


if __name__ == "__main__":
    sys.exit(main())
