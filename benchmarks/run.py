"""Benchmark entrypoint: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV; ``--json`` additionally writes the
same rows machine-readable (BENCH_engine.json) for the CI perf smoke, with
an ``env`` header (devices / platform / mesh_shape) so baselines captured
on different hosts stay comparable.

  PYTHONPATH=src python -m benchmarks.run              # fast subset (CI)
  PYTHONPATH=src python -m benchmarks.run --full       # larger workloads
  PYTHONPATH=src python -m benchmarks.run --only fig7,sched
  PYTHONPATH=src python -m benchmarks.run --json BENCH_engine.json
"""
from __future__ import annotations

import argparse
import json

from . import batch_bench, paper_figs, scheduler_bench


def parse_rows(rows: list[str]) -> dict:
    out = {}
    for row in rows:
        name, us, derived = row.split(",", 2)
        entry = {"us_per_call": float(us), "derived": derived}
        # structured compile timing (fig7 rows emit compile_us=<float>)
        for part in derived.split(";"):
            if part.startswith("compile_us="):
                entry["compile_us"] = float(part.split("=", 1)[1])
        out[name] = entry
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    ap.add_argument("--json", nargs="?", const="BENCH_engine.json",
                    default=None, metavar="PATH",
                    help="also write rows to PATH (default BENCH_engine.json)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    scale = 0.08 if args.full else 0.03

    benches = {
        "fig7": lambda: paper_figs.fig7_total_time(scale=scale),
        "fig8a": lambda: paper_figs.fig8a_query_size(scale=scale),
        "fig8b": lambda: paper_figs.fig8b_limit(scale=max(scale, 0.05)),
        "t3": lambda: paper_figs.t3_unsolved(scale=max(scale, 0.05)),
        "t4": lambda: paper_figs.t4_memory(scale=scale),
        "fig10": lambda: paper_figs.fig10_ablations(scale=scale),
        "fig11": lambda: paper_figs.fig11_lsqb(),
        "fig14": lambda: paper_figs.fig14_eps(scale=max(scale, 0.05)),
        "fig15": lambda: paper_figs.fig15_session(scale=max(scale, 0.05)),
        "sched": lambda: (scheduler_bench.sched_supersteps(scale=scale)
                          + scheduler_bench.sched_session(
                              scale=max(scale, 0.05))),
        "batch": lambda: batch_bench.batch_throughput(scale=scale),
    }
    only = set(args.only.split(",")) if args.only else None
    collected: list[str] = []
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        if only and name not in only:
            continue
        try:
            for row in fn():
                collected.append(row)
                print(row, flush=True)
        except Exception as e:   # noqa: BLE001
            row = f"{name}.ERROR,0,{type(e).__name__}:{e}"
            collected.append(row)
            print(row, flush=True)
    if args.json:
        from .common import bench_env
        with open(args.json, "w") as f:
            json.dump({"env": bench_env(), "rows": parse_rows(collected)},
                      f, indent=1, sort_keys=True)
            f.write("\n")


if __name__ == "__main__":
    main()
