"""The benchmark's data: a configuration's graph and its random-walk queries.

The graph (`data_graph`) is made by the graph model that the
configuration's "graph" section names: `bench/graphs/<model>.py`, whose
`graph(spec)` returns unique edges u < v as an (m, 2) int64 array and the
vertices' labels as an (n,) int32 array. This module adds the sorted CSR
that the queries and the reference walk.

The queries (`draw_pool`) follow the study's recipe: a random walk from a
uniform start vertex until it has visited `size` distinct vertices, and the
induced subgraph on them; a query is dense (D) when its average degree is
at least 3, sparse (S) below. Each set takes the first walks, in walk-seed
order, that meet its class. Every query has at least one embedding.

The graph and the pool are the configuration's alone; `--seed` drives only
the traffic (PERF.md says why the graph is not renamed per seed).
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

import numpy as np

from bench import lookup

__all__ = ["DataGraph", "build_csr", "data_graph", "walk_query",
           "draw_pool"]

ROOT = Path(__file__).resolve().parents[1]   # the checkout's root


@dataclasses.dataclass(frozen=True)
class DataGraph:
    """An undirected vertex-labelled graph as the benchmark hands it out:
    unique edges (u < v) and labels, plus a sorted CSR for the reference."""

    n: int
    n_labels: int
    edges: np.ndarray        # (m, 2) int64, u < v, unique, no self loops
    labels: np.ndarray       # (n,) int32
    indptr: np.ndarray       # (n + 1,) int64
    indices: np.ndarray      # (2m,) int32, sorted within each row

    def neighbors(self, v: int) -> np.ndarray:
        return self.indices[self.indptr[v]:self.indptr[v + 1]]

    def degree(self) -> np.ndarray:
        return np.diff(self.indptr)


def build_csr(n: int, edges: np.ndarray):
    """Sorted CSR (both directions) of unique undirected edges."""
    src = np.concatenate([edges[:, 0], edges[:, 1]])
    dst = np.concatenate([edges[:, 1], edges[:, 0]])
    order = np.lexsort((dst, src))
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, src + 1, 1)
    np.cumsum(indptr, out=indptr)
    return indptr, dst[order].astype(np.int32)


def data_graph(spec: dict, root: Path = ROOT) -> DataGraph:
    """The configuration's graph; `spec` is its "graph" section, whose
    "model" names the file `<root>/bench/graphs/<model>.py` that makes it
    (its `graph(spec)` returns the edges and labels)."""
    edges, labels = lookup.module(root, "graphs", spec["model"]).graph(spec)
    n = int(spec["vertices"])
    indptr, indices = build_csr(n, edges)
    return DataGraph(n=n, n_labels=int(spec["labels"]), edges=edges,
                     labels=labels, indptr=indptr, indices=indices)


def walk_query(g: DataGraph, size: int, seed: int):
    """(labels, edges) of the induced subgraph on the first `size` distinct
    vertices of a random walk, or None when the walk does not reach them in
    30 x size steps."""
    rng = np.random.default_rng(seed)
    deg = g.degree()
    cur = int(rng.integers(0, g.n))
    if deg[cur] == 0:
        return None
    visited, pos = [cur], {cur: 0}
    for _ in range(30 * size):
        if len(visited) == size:
            break
        nbrs = g.neighbors(cur)
        cur = int(nbrs[int(rng.integers(0, len(nbrs)))])
        if cur not in pos:
            pos[cur] = len(visited)
            visited.append(cur)
    if len(visited) < size:
        return None
    edges = sorted((pos[v], pos[int(w)]) for v in visited
                   for w in g.neighbors(v)
                   if int(w) in pos and pos[v] < pos[int(w)])
    return [int(g.labels[v]) for v in visited], [list(e) for e in edges]


def draw_pool(g: DataGraph, sizes, per_set: int, dense_min: float = 3.0,
              first_seed: int = 0) -> list[dict]:
    """Query sets Q<size>D and Q<size>S, `per_set` queries each: the first
    random walks from `first_seed` on that meet the class."""
    pool = []
    for size in sizes:
        for dense in (True, False):
            found, seed = 0, first_seed
            while found < per_set:
                q = walk_query(g, size, seed)
                if q is not None and (2 * len(q[1]) / size >= dense_min) \
                        == dense:
                    pool.append({"set": f"Q{size}{'DS'[not dense]}",
                                 "walk_seed": seed, "labels": q[0],
                                 "edges": q[1]})
                    found += 1
                seed += 1
    return pool
