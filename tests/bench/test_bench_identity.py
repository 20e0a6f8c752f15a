"""Moving the graph model and the request loop into files of their own
changed no byte of what a run builds or asks: the graphs' edges and
labels and the closed loop's first requests, as literals read from the
code before the move."""
import hashlib
import json

import pytest

from bench import graphgen, lookup
from conftest import TINY_GRAPH

CONFIG = json.loads((graphgen.ROOT / "bench" / "configs"
                     / "coauthor-dblpsize.json").read_text())
GRAPHS = {
    "tiny": (TINY_GRAPH, (12000, 2),
             "ed8285303689bad1f854f925264efcb764e12b9e4fa4e94d40d930bbea263106",
             "46194c04dad7bcc4e49dfdaf98ad5068eb709b0f06a5ed841ab2b26a69416413"),
    "coauthor-dblpsize": (
        CONFIG["graph"], (1049866, 2),
        "f04845f251033e6bde1554d05a9f8ed0788f538e10ad5e766e1f4d26c5b2d655",
        "300a8a72acf6eaad1abee1a3ed1b69025b967feb1d4b0949582a0d7e8f1435bb"),
}
# the first 48 pool indices of the closed loop over the cell's 12 queries
REQUESTS = {
    2**31 + 11: [7, 8, 9, 4, 3, 2, 6, 1, 5, 10, 11, 0, 3, 4, 8, 0, 5, 1, 9,
                 2, 7, 6, 11, 10, 11, 8, 4, 3, 2, 1, 7, 9, 10, 6, 0, 5, 6,
                 2, 10, 0, 8, 3, 4, 11, 7, 9, 5, 1],
    4100000011: [1, 0, 10, 7, 8, 9, 11, 6, 2, 5, 3, 4, 3, 0, 7, 9, 4, 2, 6,
                 1, 5, 11, 10, 8, 3, 10, 4, 7, 6, 0, 2, 11, 5, 1, 9, 8, 1,
                 8, 10, 2, 4, 11, 7, 9, 5, 0, 6, 3],
}


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_graph_bytes_are_unchanged(name):
    spec, shape, edges_sha, labels_sha = GRAPHS[name]
    g = graphgen.data_graph(spec)
    assert (g.edges.shape, str(g.edges.dtype), str(g.labels.dtype)) == \
        (shape, "int64", "int32")
    assert hashlib.sha256(g.edges.tobytes()).hexdigest() == edges_sha
    assert hashlib.sha256(g.labels.tobytes()).hexdigest() == labels_sha


@pytest.mark.parametrize("seed", sorted(REQUESTS))
def test_closed_loop_requests_are_unchanged(seed):
    closed = lookup.module(graphgen.ROOT, "loops", "closed")
    draws = closed.requests({}, len(CONFIG["pool"]), seed)
    assert [next(draws) for _ in range(48)] == REQUESTS[seed]
