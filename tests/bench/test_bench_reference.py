"""The benchmark's data generator and plain reference: the configuration's
graph and pool are what its recipe gives, and the reference counts as the
program's host DFS (`engine="ref"`) does where the counts lie under the
limit."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import graphgen, reference


CONFIG = json.loads((Path(__file__).resolve().parents[2] / "bench"
                     / "configs" / "coauthor-dblpsize.json").read_text())


def test_the_model_has_the_configured_size():
    g = graphgen.data_graph(CONFIG["graph"])
    reads = CONFIG["model_reads"]
    assert (g.n, len(g.edges)) == (CONFIG["graph"]["vertices"],
                                   CONFIG["graph"]["edges"])
    assert (reads["vertices"], reads["edges"]) == (g.n, len(g.edges))
    assert int(g.degree().max()) == reads["max_degree"]
    assert int((g.degree() == 0).sum()) == reads["isolated_vertices"]
    assert np.all(g.edges[:, 0] < g.edges[:, 1])
    assert len(np.unique(g.edges, axis=0)) == len(g.edges)
    assert np.bincount(g.labels).shape[0] == CONFIG["graph"]["labels"]


def test_the_pool_is_the_recipes_and_mostly_under_the_limit():
    g = graphgen.data_graph(CONFIG["graph"])
    pool = graphgen.draw_pool(g, CONFIG["query_sizes"],
                              CONFIG["queries_per_set"],
                              CONFIG["dense_min_avg_degree"],
                              CONFIG["pool_first_walk_seed"])
    drop = ("reference_count",)
    assert [{k: v for k, v in q.items() if k not in drop}
            for q in CONFIG["pool"]] == pool
    counts = [reference.count_embeddings(g, q["labels"], q["edges"],
                                         CONFIG["limit"]) for q in pool]
    assert counts == [q["reference_count"] for q in CONFIG["pool"]]
    assert sum(c < CONFIG["limit"] for c in counts) > len(counts) // 2
    assert CONFIG["limit"] in counts


def test_every_walk_query_embeds_where_it_was_drawn(pool_of):
    from conftest import TINY_GRAPH
    g = graphgen.data_graph(TINY_GRAPH)
    for q in pool_of(TINY_GRAPH, sizes=(4, 6, 8), per_set=3):
        n = len(q["labels"])
        assert 2 * len(q["edges"]) / n >= 3 if q["set"].endswith("D") \
            else 2 * len(q["edges"]) / n < 3
        assert reference.count_embeddings(g, q["labels"], q["edges"],
                                          10**6) >= 1


def _edges(q):
    return [(u, int(w)) for u in range(q.n) for w in q.neighbors(u) if u < w]


@pytest.mark.parametrize("name,scale", [("yeast", 0.3), ("hprd", 0.3),
                                        ("dblp", 0.01), ("wordnet", 0.05)])
def test_counts_equal_the_programs_host_dfs(name, scale):
    from repro.api import Dataset, Matcher, MatchOptions
    from repro.core.graph import random_walk_query, synthetic_dataset
    pg = synthetic_dataset(name, scale=scale)
    m = Matcher(Dataset.from_graph(pg), MatchOptions(limit=10**7))
    checked = 0
    for seed in range(6):
        for size in (4, 5, 6):
            try:
                q = random_walk_query(pg, size, seed=seed,
                                      dense=bool(seed % 2))
            except RuntimeError:
                continue
            want = m.count(q, engine="ref").count
            if want >= 10**7:
                continue
            assert reference.count_embeddings(pg, q.labels, _edges(q),
                                              10**7) == want
            checked += 1
    assert checked >= 8


def test_limit_is_cut_exactly_and_the_control_is_not():
    from repro.core.graph import random_walk_query, synthetic_dataset
    pg = synthetic_dataset("dblp", scale=0.05)
    q = random_walk_query(pg, 5, seed=1)
    full = reference.count_embeddings(pg, q.labels, _edges(q), 10**9)
    assert full > 1000
    assert reference.count_embeddings(pg, q.labels, _edges(q), 1000) == 1000
    over = reference.count_embeddings(pg, q.labels, _edges(q), 1000,
                                      exact_cut=False, chunk=64)
    assert 1000 < over <= full
