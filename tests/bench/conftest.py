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


def tiny_pool(spec: dict, sizes=(4, 5), per_set: int = 1) -> list:
    """Random-walk query sets of the tiny graph, as a configuration lists
    them."""
    from bench import graphgen
    return graphgen.draw_pool(graphgen.data_graph(spec), sizes, per_set)


@pytest.fixture(scope="session")
def pool_of():
    return tiny_pool


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    """A checkout holding only the benchmark's files plus one new
    configuration (`tiny`), one new traffic mix and a new cell,
    `tiny.count`: a cell defined by new files alone."""
    root = tmp_path_factory.mktemp("bench_root")
    src = Path(ROOT)
    shutil.copytree(src / "bench" / "metrics", root / "bench" / "metrics")
    (root / "bench" / "configs").mkdir()
    (root / "bench" / "traffic").mkdir()
    config = {"name": "tiny", "graph": TINY_GRAPH, "limit": 1000,
              "pool": tiny_pool(TINY_GRAPH), "match_options": {}}
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    (root / "bench" / "traffic" / "tiny_mix.json").write_text(json.dumps(
        {"loop": "closed"}))
    man = json.loads((src / "BENCHMARK.json").read_text())
    man["configs"].append({"name": "tiny", "source": "tests/bench",
                           "file": "bench/configs/tiny.json", "reduced": [],
                           "why": "a cell of new files only"})
    man["workloads"].append(
        {"name": "tiny.count", "config": "tiny", "traffic": "tiny_mix",
         "chips": 1, "why": "closed loop on the tiny graph"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.count")
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


@pytest.fixture
def run_cell(monkeypatch, capsys):
    """Runs one cell of a root through the harness on the CPU (the look
    for a chip skipped) and returns (exit code, result line)."""
    from bench import harness
    monkeypatch.setattr(harness, "enable_compile_cache", lambda root: "")

    def run(root, name, seed=2**31 + 11, seconds=2.0, trace=0):
        capsys.readouterr()
        rc = harness.main(["--workload", name, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)],
                          root=root, require_tpu=False)
        out = capsys.readouterr().out.strip().splitlines()
        return rc, (json.loads(out[-1]) if rc == 0 else None)
    return run
