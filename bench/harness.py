"""The chip benchmark's harness: one run of one cell.

A cell is made of files found by name from `BENCHMARK.json`:

* the configuration: `configs[].file`, data (the graph's parameters, the
  query pool, the limit and the guarantees; see `bench/configs/`);
* the graph model: `bench/graphs/<model>.py`, named by the configuration's
  "graph" section, whose `graph(spec)` makes the edges and labels
  (`bench/graphgen.py` builds the rest);
* the traffic mix: `bench/traffic/<traffic>.json`, data, whose "loop"
  names the request loop;
* the request loop: `bench/loops/<loop>.py` (below);
* each metric: `bench/metrics/<name>.py`, a reader `read(run)` that returns
  a number or None (nothing to read in this cell).

A cell on a new deployment is new files alone, and edits none that is
here: its configuration and traffic mix always, and a graph model, a loop
or a metric only where none here fits.

A request loop exports `run(run, config, mix, seed, ds, pool, tw, events,
t_start)`: `run` the `Run` to fill, `config` and `mix` the cell's data,
`seed` the run's `--seed`, `ds` the program's `Dataset` of the graph,
`pool` the pool's queries as the program's graphs, `tw` the
`TraceWindow`, `events` the `JaxEvents`, `t_start` the process's start on
`time.perf_counter`. It warms up every program shape its traffic reaches,
sets `run.setup_s`, calls `tw.start()` as the window opens and
`tw.tick(elapsed)` as it goes, and fills `window_s`, `latencies_ms`,
`attempted`, `completed`, `failed`, `engines` and `counters` (numbers of
its own go into `counters` under their own names). It returns the answers,
one `(pool index, count, timed_out)` each, and the `events.snapshot()` at
the window's start and at its end. It may also export `checks(run,
answers)`, which returns further `{name: {"value", "limit"}}` numbers
compared; they join the reference's in `checks` and in `correct`.

A run builds the configuration's graph and hands the program only that
graph and the pool's queries; `--seed` drives the traffic. Set-up warms
every program shape the traffic can reach; then the window runs for
`--seconds` and nothing may compile in it. With `--trace 1` the first
seconds of the window are profiled (`bench/trace_reduce.py`) and the
per-layer metrics are printed instead of the end-to-end ones. After the
window every answer is checked against the plain reference
(`bench/reference.py`).

The last line of standard output is one JSON object (`correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` also `breakdown`, and last
`checks`: each number compared with its limit). The checks are also the
last lines of standard error. An unknown cell, model or loop, or a missing
chip, exits with code 2 and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import graphgen, lookup, reference, trace_reduce
from bench.lookup import CellError

__all__ = ["main", "Run", "load_cell", "metric_names"]

TRACE_SECONDS = 4.0        # profiled part of the window in a --trace 1 run
# JAX reports a program loaded from the persistent cache as a backend
# compile too; compiles proper are the backend compiles less the loads
EVENTS = {"/jax/core/compile/backend_compile_duration": "backend",
          "/jax/compilation_cache/cache_retrieval_time_sec": "loads",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings"}


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
    """(manifest, cell, configuration, traffic mix) of workload `name`,
    once its graph model's and its loop's files are found."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in manifest["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    lookup.path(root, "graphs", config["graph"]["model"])
    lookup.path(root, "loops", mix["loop"])
    return manifest, cell, config, mix


def metric_names(manifest: dict, cell: str, per_layer: bool) -> list[dict]:
    """The metrics this cell reports: those that list it, or list none."""
    group = manifest["per_layer" if per_layer else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(root: Path, name: str, run: Run):
    return lookup.module(root, "metrics", name).read(run)


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
def build_inputs(config: dict, root: Path):
    """The configuration's graph, the program's Dataset of it, and the
    pool's queries as the program's graphs."""
    from repro.api import Dataset
    from repro.core.graph import build_graph
    g = graphgen.data_graph(config["graph"], root)
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


def add_stats(acc: dict, stats) -> None:
    """Adds a request's numeric stats into a run's counters."""
    for k, v in dataclasses.asdict(stats).items():
        if isinstance(v, (int, float)):
            acc[k] = acc.get(k, 0) + v


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
        loop = lookup.module(root, "loops", mix["loop"])
        g, ds, pool = build_inputs(config, root)
        tw = TraceWindow(bool(args.trace), min(TRACE_SECONDS, args.seconds),
                         tmp)
        answers, ev0, ev1 = loop.run(
            run, config, mix, args.seed, ds, pool, tw, events, t_start)
        tw.stop()
        device["memory_peak_bytes"] = peak_bytes(devs)
        run.trace = tw.reduce(len(devs))
        del ds, pool
        gc.collect()
        checks = check(answers, config, g)
        if hasattr(loop, "checks"):
            checks.update(loop.checks(run, answers))
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
