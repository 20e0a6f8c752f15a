"""Graph model `coauthorship`: a co-authorship network with uniform labels.

The graph is made as a co-authorship network is: papers whose authors all
become pairwise neighbours. Each author is on at least one paper; the
other author slots go by a productivity weight i^-alpha over randomly
permuted ids, and paper sizes follow P(s) ~ s^-beta on 2..max_paper_size.
Edges are kept in the order their papers come, unique, up to the
configuration's edge count, so the graph has exactly the configuration's
vertices and edges. Vertex labels are uniform, as the study's protocol
gives a graph that has none. The configuration states every parameter;
`bench/configs/` says which published statistics they were fitted to.
"""
from __future__ import annotations

import numpy as np

__all__ = ["graph", "coauthor_graph"]


def graph(spec: dict) -> tuple[np.ndarray, np.ndarray]:
    """(edges, labels) of the configuration's "graph" section."""
    n = int(spec["vertices"])
    edges = coauthor_graph(n, int(spec["edges"]), int(spec["author_slots"]),
                           float(spec["productivity_alpha"]),
                           float(spec["paper_size_beta"]),
                           int(spec["max_paper_size"]), int(spec["seed"]))
    labels = np.random.default_rng([int(spec["seed"]), 1]).integers(
        0, int(spec["labels"]), size=n).astype(np.int32)
    return edges, labels


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
