"""The benchmark's data: a co-authorship graph and its random-walk queries.

The graph (`coauthor_graph`) is made as a co-authorship network is: papers
whose authors all become pairwise neighbours. Each author is on at least one
paper; the other author slots go by a productivity weight i^-alpha over
randomly permuted ids, and paper sizes follow P(s) ~ s^-beta on
2..max_paper_size. Edges are kept in the order their papers come, unique,
up to the configuration's edge count, so the graph has exactly the
configuration's vertices and edges. Vertex labels are uniform, as the
study's protocol gives a graph that has none. The configuration states
every parameter; `bench/configs/` says which published statistics they
were fitted to.

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

import numpy as np

__all__ = ["DataGraph", "build_csr", "coauthor_graph", "data_graph",
           "walk_query", "draw_pool"]


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


def coauthor_graph(n: int, m: int, author_slots: int, alpha: float,
                   beta: float, max_paper_size: int,
                   seed: int) -> np.ndarray:
    """(m, 2) unique edges u < v of the co-authorship model (module doc),
    in the order their papers come."""
    rng = np.random.default_rng(seed)
    w = np.arange(1, n + 1, dtype=np.float64) ** (-alpha)
    w /= w.sum()
    perm = rng.permutation(n)
    slots = np.concatenate([rng.permutation(n),
                            perm[rng.choice(n, author_slots - n, p=w)]])
    slots = slots[rng.permutation(author_slots)]
    ks = np.arange(2, max_paper_size + 1)
    p = ks.astype(np.float64) ** (-beta)
    sizes = rng.choice(ks, size=author_slots // 2, p=p / p.sum())
    sizes = sizes[:np.searchsorted(np.cumsum(sizes), author_slots, "right")]
    starts = np.cumsum(sizes) - sizes
    paper, us, vs = [], [], []
    for s in np.unique(sizes):
        at = np.flatnonzero(sizes == s)
        iu, ju = np.triu_indices(s, 1)
        paper.append(np.repeat(at, len(iu)))
        us.append(slots[(starts[at, None] + iu).ravel()])
        vs.append(slots[(starts[at, None] + ju).ravel()])
    order = np.argsort(np.concatenate(paper), kind="stable")
    u, v = np.concatenate(us)[order], np.concatenate(vs)[order]
    keep = u != v
    lo = np.minimum(u[keep], v[keep]).astype(np.int64)
    hi = np.maximum(u[keep], v[keep]).astype(np.int64)
    keys, first = np.unique(lo * n + hi, return_index=True)
    if len(keys) < m:
        raise ValueError(f"the model gives {len(keys)} edges, under {m}")
    keys = np.sort(keys[np.argsort(first)][:m])
    return np.stack([keys // n, keys % n], 1)


def build_csr(n: int, edges: np.ndarray):
    """Sorted CSR (both directions) of unique undirected edges."""
    src = np.concatenate([edges[:, 0], edges[:, 1]])
    dst = np.concatenate([edges[:, 1], edges[:, 0]])
    order = np.lexsort((dst, src))
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(indptr, src + 1, 1)
    np.cumsum(indptr, out=indptr)
    return indptr, dst[order].astype(np.int32)


def data_graph(spec: dict) -> DataGraph:
    """The configuration's graph; `spec` is its "graph" section."""
    n, n_labels = int(spec["vertices"]), int(spec["labels"])
    edges = coauthor_graph(n, int(spec["edges"]), int(spec["author_slots"]),
                           float(spec["productivity_alpha"]),
                           float(spec["paper_size_beta"]),
                           int(spec["max_paper_size"]), int(spec["seed"]))
    labels = np.random.default_rng([int(spec["seed"]), 1]).integers(
        0, n_labels, size=n).astype(np.int32)
    indptr, indices = build_csr(n, edges)
    return DataGraph(n=n, n_labels=n_labels, edges=edges, labels=labels,
                     indptr=indptr, indices=indices)


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
