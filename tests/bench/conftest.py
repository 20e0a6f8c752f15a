"""The benchmark's tests import it as the package `bench` from the
checkout's root."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


import json  # noqa: E402
import shutil  # noqa: E402
from pathlib import Path  # noqa: E402

import pytest  # noqa: E402

TINY_GRAPH = {"model": "coauthorship", "vertices": 6000, "edges": 12000,
              "labels": 4, "author_slots": 14400, "productivity_alpha": 0.4,
              "paper_size_beta": 3.4, "max_paper_size": 8, "seed": 0}


RING_GRAPH = {"model": "cliquering", "vertices": 600, "clique": 5,
              "labels": 3, "seed": 0}

# a graph model, a request loop and two metric readers that no cell of the
# benchmark has: the cell `tiny-ring.replay` is made of new files alone
CLIQUERING = '''"""Graph model `cliquering`: cliques of `clique` vertices, the
first vertex of each joined to the second of the next, uniform labels."""
import numpy as np


def graph(spec):
    n, k = int(spec["vertices"]), int(spec["clique"])
    iu, ju = np.triu_indices(k, 1)
    starts = np.arange(0, n, k)
    inner = np.stack([(starts[:, None] + iu).ravel(),
                      (starts[:, None] + ju).ravel()], 1)
    ring = np.stack([starts, np.roll(starts, -1) + 1], 1)
    edges = np.unique(np.sort(np.concatenate([inner, ring]), axis=1), axis=0)
    labels = np.random.default_rng(int(spec["seed"])).integers(
        0, int(spec["labels"]), size=n)
    return edges.astype(np.int64), labels.astype(np.int32)
'''
REPLAY = '''"""Request loop `replay`: every pool query once, in pool order, with no
warm-up; checks that each was asked exactly once."""
import time

from bench import harness


def run(run, config, mix, seed, ds, pool, tw, events, t_start):
    from repro.api import Matcher
    m = Matcher(ds, harness.match_options(config))
    ev0 = events.snapshot()
    run.setup_s = time.perf_counter() - t_start
    tw.start()
    t0 = time.perf_counter()
    answers = []
    for k, q in enumerate(pool):
        t = time.perf_counter()
        out = m.count(q)
        run.latencies_ms.append((time.perf_counter() - t) * 1e3)
        answers.append((k, out.count, out.timed_out))
        run.engines[out.engine] = run.engines.get(out.engine, 0) + 1
        tw.tick(time.perf_counter() - t0)
    run.window_s = time.perf_counter() - t0
    run.attempted = run.completed = len(answers)
    run.failed = sum(1 for a in answers if a[2])
    run.counters.update(pool_size=len(pool), replayed=len(answers))
    return answers, ev0, events.snapshot()


def checks(run, answers):
    asked = [a[0] for a in answers]
    return {"not_asked_once": {"value": sum(
        asked.count(k) != 1 for k in range(run.counters["pool_size"])),
        "limit": 0}}
'''
METRICS = {
    "replay_mean_ms": "def read(run):\n    lat = run.latencies_ms\n"
                      "    return sum(lat) / len(lat) if lat else None\n",
    "replayed.tiny": "def read(run):\n"
                     "    return run.counters.get(\"replayed\")\n"}


def tiny_pool(spec: dict, sizes=(4, 5), per_set: int = 1,
              root: Path = None) -> list:
    """Random-walk query sets of the tiny graph, as a configuration lists
    them."""
    from bench import graphgen
    g = graphgen.data_graph(spec, root or graphgen.ROOT)
    return graphgen.draw_pool(g, sizes, per_set)


@pytest.fixture(scope="session")
def pool_of():
    return tiny_pool


def _write(path: Path, text: str) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    """A checkout holding only the benchmark's files plus two new cells:
    `tiny.count`, a new configuration (`tiny`) and traffic mix on the
    benchmark's own graph model, loop and metrics, and `tiny-ring.replay`,
    whose configuration, graph model, traffic mix, loop and metrics are all
    new files."""
    root = tmp_path_factory.mktemp("bench_root")
    src = Path(ROOT)
    for part in ("graphs", "loops", "metrics"):
        shutil.copytree(src / "bench" / part, root / "bench" / part)
    _write(root / "bench" / "graphs" / "cliquering.py", CLIQUERING)
    _write(root / "bench" / "loops" / "replay.py", REPLAY)
    for name, text in METRICS.items():
        _write(root / "bench" / "metrics" / f"{name}.py", text)
    configs = {"tiny": (TINY_GRAPH, "tiny_mix", "closed"),
               "tiny-ring": (RING_GRAPH, "replay_mix", "replay")}
    for name, (spec, mix, loop) in configs.items():
        config = {"name": name, "graph": spec, "limit": 1000,
                  "pool": tiny_pool(spec, root=root), "match_options": {}}
        _write(root / "bench" / "configs" / f"{name}.json",
               json.dumps(config))
        _write(root / "bench" / "traffic" / f"{mix}.json",
               json.dumps({"loop": loop}))
    man = json.loads((src / "BENCHMARK.json").read_text())
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.count")
    man["configs"] += [{"name": name, "source": "tests/bench",
                        "file": f"bench/configs/{name}.json", "reduced": [],
                        "why": "a cell of new files"} for name in configs]
    man["workloads"] += [
        {"name": "tiny.count", "config": "tiny", "traffic": "tiny_mix",
         "chips": 1, "why": "closed loop on the tiny graph"},
        {"name": "tiny-ring.replay", "config": "tiny-ring",
         "traffic": "replay_mix", "chips": 1,
         "why": "each pool query once on a ring of cliques"}]
    man["end_to_end"].append(
        {"name": "replay_mean_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock",
         "workloads": ["tiny-ring.replay"]})
    man["per_layer"].append(
        {"name": "replayed.tiny", "unit": "queries", "better": "higher",
         "source": "program_counter", "layer": "request loop",
         "moves": "replay_mean_ms", "workloads": ["tiny-ring.replay"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


@pytest.fixture
def run_cell(monkeypatch, capsys):
    """Runs one cell of a root through the harness on the CPU (the look
    for a chip skipped) and returns (exit code, result line or None where
    the run printed nothing)."""
    from bench import harness
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "")

    def run(root, name, seed=2**31 + 11, seconds=2.0, trace=0):
        capsys.readouterr()
        rc = harness.main(["--workload", name, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)],
                          root=root, require_tpu=False)
        out = capsys.readouterr().out.strip().splitlines()
        return rc, (json.loads(out[-1]) if out else None)
    return run
