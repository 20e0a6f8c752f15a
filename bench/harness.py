"""The chip benchmark's harness: one run of one cell.

Everything a cell is made of is data, found by name from `BENCHMARK.json`:

* the configuration: `configs[].file` (the graph, the query pool, the
  limit and the guarantees; see `bench/configs/`);
* the traffic mix: `bench/traffic/<traffic>.json`, read by the one
  generator `bench/traffic.py`;
* each metric: `bench/metrics/<name>.py`, a reader `read(run)` that returns
  a number or None (nothing to read in this cell).

A run builds the configuration's graph (`bench/graphgen.py`) and hands the
program only that graph and the pool's queries; `--seed` drives the
traffic. Set-up warms every program shape the traffic can reach; then the
window runs for `--seconds` and nothing may compile in it. With `--trace 1`
the first seconds of the window are profiled (`bench/trace_reduce.py`) and
the per-layer metrics are printed instead of the end-to-end ones. After the
window every answer is checked against the plain reference
(`bench/reference.py`).

The last line of standard output is one JSON object (`correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` also `breakdown`, and last
`checks`: each number compared with its limit). The checks are also the
last lines of standard error.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import graphgen, reference, trace_reduce, traffic

__all__ = ["main", "Run", "load_cell", "metric_names"]

TRACE_SECONDS = 4.0        # profiled part of the window in a --trace 1 run
# JAX reports a program loaded from the persistent cache as a backend
# compile too; compiles proper are the backend compiles less the loads
EVENTS = {"/jax/core/compile/backend_compile_duration": "backend",
          "/jax/compilation_cache/cache_retrieval_time_sec": "loads",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings"}


class CellError(Exception):
    """The cell cannot run here: unknown name, missing file, no chip."""


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers read it."""

    seconds: float
    setup_s: float = 0.0
    window_s: float = 0.0            # the measured window on the host clock
    latencies_ms: list = dataclasses.field(default_factory=list)
    attempted: int = 0
    completed: int = 0
    failed: int = 0
    counters: dict = dataclasses.field(default_factory=dict)
    engines: dict = dataclasses.field(default_factory=dict)  # answers each
    trace: object = None             # trace_reduce.Reduction, --trace 1


# ------------------------------------------------------------------- lookup
def load_cell(root: Path, name: str) -> tuple[dict, dict, dict, dict]:
    """(manifest, cell, configuration, traffic mix) of workload `name`."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in manifest["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    return manifest, cell, config, mix


def metric_names(manifest: dict, cell: str, per_layer: bool) -> list[dict]:
    """The metrics this cell reports: those that list it, or list none."""
    group = manifest["per_layer" if per_layer else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(root: Path, name: str, run: Run):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", root / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


# -------------------------------------------------------------------- jax
class JaxEvents:
    """Counts the programs this process lowers, loads from the persistent
    compilation cache, and compiles."""

    def __init__(self):
        import jax
        self.n = {v: 0 for v in EVENTS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_) -> None:
        kind = EVENTS.get(event)
        if kind is not None:
            self.n[kind] += 1

    def snapshot(self) -> dict:
        return dict(self.n, compiles=self.n["backend"] - self.n["loads"])


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else `<checkout>/.jax_cache`, a fixed path (the path is part of
    what an entry is found by). Every compile is cached."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


class TraceWindow:
    """Profiles the first `seconds` of the window into a temporary
    directory (--trace 1), inside a `bench.window` host span."""

    def __init__(self, on: bool, seconds: float, tmp: str):
        self.on, self.seconds, self.dir = on, seconds, os.path.join(tmp, "tr")
        self.active, self.span = False, None

    def start(self) -> None:
        if not self.on:
            return
        import jax
        jax.profiler.start_trace(self.dir)
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()
        self.active = True

    def tick(self, elapsed: float) -> None:
        if self.active and elapsed >= self.seconds:
            self.stop()

    def stop(self) -> None:
        if not self.active:
            return
        import jax
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    def reduce(self, n_devices: int):
        if not self.on:
            return None
        self.stop()
        return trace_reduce.reduce_dir(self.dir, n_devices=n_devices)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# ------------------------------------------------------------------ inputs
def build_inputs(config: dict):
    """The configuration's graph, the program's Dataset of it, and the
    pool's queries as the program's graphs."""
    from repro.api import Dataset
    from repro.core.graph import build_graph
    g = graphgen.data_graph(config["graph"])
    ds = Dataset.from_edges(g.n, g.edges, g.labels, n_labels=g.n_labels,
                            name=config["name"])
    pool = [build_graph(len(q["labels"]), np.asarray(q["edges"]),
                        q["labels"], n_labels=g.n_labels)
            for q in config["pool"]]
    return g, ds, pool


def match_options(config: dict):
    from repro.api import MatchOptions
    return MatchOptions(limit=int(config["limit"]),
                        **config.get("match_options", {}))


def _add_stats(acc: dict, stats) -> None:
    for k, v in dataclasses.asdict(stats).items():
        if isinstance(v, (int, float)):
            acc[k] = acc.get(k, 0) + v


# ------------------------------------------------------------ closed loop
def closed_loop(run: Run, config, mix, seed, ds, pool, tw, events,
                t_start):
    """One client: `Matcher.count` back to back for `seconds`."""
    from repro.api import Matcher
    m = Matcher(ds, match_options(config))
    with span("bench.warmup"):
        for k, q in enumerate(pool):
            t = time.perf_counter()
            m.count(q)
            m.count(q)
            print(f"bench: warm-up {config['pool'][k].get('set', k)} "
                  f"{time.perf_counter() - t:.3f} s", file=sys.stderr)
    answers = []
    draws = traffic.closed_requests(mix, len(pool), seed)
    ev0 = events.snapshot()
    run.setup_s = time.perf_counter() - t_start
    tw.start()
    t0 = time.perf_counter()
    end = t0
    while end - t0 < run.seconds:
        k = next(draws)
        with span("bench.count"):
            t = time.perf_counter()
            out = m.count(pool[k])
            end = time.perf_counter()
        run.latencies_ms.append((end - t) * 1e3)
        answers.append((k, out.count, out.timed_out))
        run.engines[out.engine] = run.engines.get(out.engine, 0) + 1
        _add_stats(run.counters, out.stats)
        tw.tick(end - t0)
    run.window_s = end - t0
    run.attempted = run.completed = len(answers)
    run.failed = sum(1 for a in answers if a[2])
    return answers, ev0, events.snapshot()


LOOPS = {"closed": closed_loop}


# ------------------------------------------------------------- correctness
def check(answers, config, g) -> dict:
    """Each number compared, with its limit: every answer of the window
    against the reference's count of its query on this run's graph. Most
    pool queries have fewer embeddings than the limit, so their answers
    are exact counts of the whole enumeration."""
    limit = int(config["limit"])
    want = {}
    for k in sorted({a[0] for a in answers}):
        q = config["pool"][k]
        want[k] = reference.count_embeddings(g, q["labels"], q["edges"],
                                             limit)
    return {"wrong_counts": {
        "value": sum(1 for k, c, timed_out in answers
                     if timed_out or c != want[k]), "limit": 0}}


# ------------------------------------------------------------------- main
def parse_args(argv):
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(cell: dict, require_tpu: bool) -> tuple[list, dict]:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise CellError(f"needs a TPU; JAX's first device is on "
                        f"{devs[0].platform!r}")
    if len(devs) < cell["chips"]:
        raise CellError(f"needs {cell['chips']} chips, JAX has {len(devs)}")
    devs = devs[:cell["chips"]]
    return devs, {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def main(argv, *, root, t_start=None, require_tpu=True) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    root = Path(root)
    try:
        manifest, cell, config, mix = load_cell(root, args.workload)
        devs, device = device_info(cell, require_tpu)
    except (CellError, OSError, KeyError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    enable_compile_cache(root)
    events = JaxEvents()
    run = Run(seconds=args.seconds)
    tmp = tempfile.mkdtemp(prefix="bench-")
    try:
        g, ds, pool = build_inputs(config)
        tw = TraceWindow(bool(args.trace), min(TRACE_SECONDS, args.seconds),
                         tmp)
        answers, ev0, ev1 = LOOPS[mix["loop"]](
            run, config, mix, args.seed, ds, pool, tw, events, t_start)
        tw.stop()
        device["memory_peak_bytes"] = peak_bytes(devs)
        run.trace = tw.reduce(len(devs))
        del ds, pool
        gc.collect()
        checks = check(answers, config, g)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"bench: setup_s={run.setup_s:.3f} window_s={run.window_s:.3f} "
          f"setup_lowerings={ev0['lowerings']} setup_loads={ev0['loads']} "
          f"setup_compiles={ev0['compiles']} "
          f"window_lowerings={ev1['lowerings'] - ev0['lowerings']} "
          f"window_compiles={ev1['compiles'] - ev0['compiles']} "
          f"completed={run.completed} failed={run.failed} "
          f"below_limit={sum(a[1] < int(config['limit']) for a in answers)} "
          f"engines={json.dumps(run.engines, separators=(',', ':'))}",
          flush=True)
    metrics = {}
    for m in metric_names(manifest, cell["name"], bool(args.trace)):
        value = read_metric(root, m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        out["device"].update(busy_s=run.trace.busy_s,
                             window_s=run.trace.window_s)
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
