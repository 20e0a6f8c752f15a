"""Plain reference: count the embeddings of a labelled query, up to a limit.

An embedding maps every query vertex to a distinct data vertex of the same
label, so that every query edge lands on a data edge (subgraph isomorphism,
not induced). The count is min(number of embeddings, limit), which is what
the system under test promises for `limit` (the study's 10^5 cut).

This is a straight backtracking search in numpy over the benchmark's own
CSR (`graphgen.DataGraph`). It imports nothing of the program and shares no
index, order or table with it. Per query vertex it keeps the data vertices
of its label that have, for every label, at least as many neighbours of that
label as the query vertex has (every embedding satisfies this). It extends
blocks of partial embeddings one query vertex at a time along a connected
order, depth first, and counts the last vertex's extensions as a whole.

`exact_cut=False` is the control: it keeps the count of the block in which
the limit was crossed instead of cutting it at the limit, the shortcut of
stopping at the first leaf tile that passes it.
"""
from __future__ import annotations

import numpy as np

__all__ = ["count_embeddings", "search_order"]


def search_order(q_labels, q_adj, label_sizes) -> list[int]:
    """A connected order that places every vertex of degree two or more
    before any of degree one, so that a dead end shows before the leaves
    multiply the search: the vertex with the fewest data candidates per
    query neighbour first, then always the unplaced vertex with the most
    placed neighbours (ties: fewer candidates, then lower id)."""
    n = len(q_labels)
    first = min(range(n), key=lambda u: (label_sizes[q_labels[u]]
                                         / max(len(q_adj[u]), 1), u))
    order, placed = [first], {first}
    while len(order) < n:
        nxt = max((u for u in range(n) if u not in placed
                   and any(w in placed for w in q_adj[u])),
                  key=lambda u: (len(q_adj[u]) > 1,
                                 sum(w in placed for w in q_adj[u]),
                                 -label_sizes[q_labels[u]], -u))
        order.append(nxt)
        placed.add(nxt)
    return order


def count_embeddings(g, q_labels, q_edges, limit: int, *,
                     exact_cut: bool = True, chunk: int = 1 << 20) -> int:
    """min(#embeddings of the query in `g`, limit); see the module doc.
    `g` has `n`, `labels`, `indptr`, `indices` (sorted rows) and
    `degree()`. Partial embeddings are extended a block of rows at a time,
    depth first, each block's extension holding at most about `chunk`
    rows."""
    q_labels = [int(x) for x in q_labels]
    n = len(q_labels)
    q_adj = [set() for _ in range(n)]
    for a, b in q_edges:
        q_adj[int(a)].add(int(b))
        q_adj[int(b)].add(int(a))
    deg = g.degree()
    label_sizes = np.bincount(g.labels, minlength=max(q_labels) + 1)
    order = search_order(q_labels, q_adj, label_sizes)
    pos = {u: i for i, u in enumerate(order)}
    # neighbour-label counts: v can take u only if, for every label, v has
    # at least as many neighbours of that label as u has
    n_lab = max(int(g.labels.max()), max(q_labels)) + 1
    nlf = np.zeros((g.n, n_lab), dtype=np.int32)
    np.add.at(nlf, (np.repeat(np.arange(g.n), deg), g.labels[g.indices]), 1)
    ok = []
    for u in order:
        need = np.bincount([q_labels[w] for w in q_adj[u]], minlength=n_lab)
        m = g.labels == q_labels[u]
        for lab in np.flatnonzero(need):
            m &= nlf[:, lab] >= need[lab]
        ok.append(m)
    # back[i]: the positions of the already-placed neighbours of order[i]
    back = [sorted(pos[w] for w in q_adj[u] if pos[w] < i)
            for i, u in enumerate(order)]
    indptr, indices = g.indptr, g.indices.astype(np.int64)
    nv = np.int64(g.n)
    # every directed edge as one sorted key, for membership tests
    src = np.repeat(np.arange(g.n, dtype=np.int64), deg)
    edge_keys = src * nv + indices

    def has_edge(a: np.ndarray, b: np.ndarray) -> np.ndarray:
        key = a * nv + b
        at = np.minimum(np.searchsorted(edge_keys, key), len(edge_keys) - 1)
        return edge_keys[at] == key

    def extend(rows: np.ndarray, i: int) -> np.ndarray:
        """All extensions of the partial embeddings `rows` (positions
        0..i-1) by a data vertex for position i."""
        anchor = rows[:, back[i][0]]
        d = indptr[anchor + 1] - indptr[anchor]
        parent = np.repeat(np.arange(rows.shape[0]), d)
        first = np.repeat(indptr[anchor] - np.cumsum(d) + d, d)
        cand = indices[first + np.arange(parent.shape[0])]
        keep = ok[i][cand]
        parent, cand = parent[keep], cand[keep]
        for j in back[i][1:]:
            keep = has_edge(rows[parent, j], cand)
            parent, cand = parent[keep], cand[keep]
        for j in range(i):
            keep = rows[parent, j] != cand
            parent, cand = parent[keep], cand[keep]
        return np.concatenate([rows[parent], cand[:, None]], axis=1)

    def blocks(rows: np.ndarray, i: int):
        """`rows` cut so that each block's extension at i is ~chunk rows."""
        anchor = rows[:, back[i][0]]
        cum = np.cumsum(indptr[anchor + 1] - indptr[anchor])
        cuts = np.searchsorted(cum, np.arange(chunk, int(cum[-1]), chunk))
        return [b for b in np.split(rows, np.unique(cuts)) if b.shape[0]]

    first_rows = np.flatnonzero(ok[0]).astype(np.int64)[:, None]
    if n == 1:
        return min(first_rows.shape[0], limit) if exact_cut \
            else first_rows.shape[0]
    total = 0
    stack = [(b, 1) for b in reversed(blocks(first_rows, 1))] \
        if first_rows.shape[0] else []
    while stack and total < limit:
        rows, i = stack.pop()
        ext = extend(rows, i)
        if i == n - 1:
            total += ext.shape[0]
        elif ext.shape[0]:
            stack.extend((b, i + 1) for b in reversed(blocks(ext, i + 1)))
    return min(total, limit) if exact_cut else total
