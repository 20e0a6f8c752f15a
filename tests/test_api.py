"""Tests for the repro.api session layer (Dataset / MatchOptions / Matcher):
engine agreement through the facade, plan-cache behavior, options validation,
streaming, queue integration, and deprecation shims."""
import numpy as np
import pytest
from strategies import fig1_pair

import repro.core as core
from repro.api import (AUTO_VECTOR_MIN_ROWS, Dataset, MatchOptions, Matcher,
                       graph_signature)
from repro.core import build_graph, random_walk_query, synthetic_labeled_graph
from repro.core.ref_engine import cemr_match


# --------------------------------------------------------- engine agreement

def test_fig1_ref_vector_agree_through_matcher():
    data, query = fig1_pair()
    m = Matcher(Dataset.from_graph(data, name="fig1"))
    ref = m.count(query, engine="ref", limit=10**9)
    vec = m.count(query, engine="vector", limit=10**9)
    expect = cemr_match(query, data, limit=10**9).count
    assert ref.engine == "ref" and vec.engine == "vector"
    assert ref.count == vec.count == expect > 0


SYNTH_WORKLOADS = [
    # (n, avg_degree, n_labels, graph_seed, query_size, query_seed)
    (300, 5.0, 4, 0, 4, 1),
    (400, 6.0, 3, 1, 5, 2),
    (600, 7.0, 5, 2, 6, 3),
    (800, 8.0, 6, 3, 6, 4),
    (500, 6.0, 2, 4, 5, 5),
    (1000, 8.0, 8, 5, 7, 6),
]


@pytest.mark.parametrize("n,deg,labels,gseed,qsize,qseed", SYNTH_WORKLOADS)
def test_ref_vector_agree_synthetic(n, deg, labels, gseed, qsize, qseed):
    g = synthetic_labeled_graph(n, deg, labels, seed=gseed)
    q = random_walk_query(g, qsize, seed=qseed)
    m = Matcher(Dataset.from_graph(g))
    ref = m.count(q, engine="ref", limit=10**9)
    vec = m.count(q, engine="vector", limit=10**9, tile_rows=128)
    assert ref.count == vec.count
    assert ref.count >= 1           # random-walk queries have >=1 embedding


# -------------------------------------------------------------- plan caching

def test_compile_same_query_twice_builds_plan_once(monkeypatch):
    import repro.api.matcher as matcher_mod
    data, query = fig1_pair()
    m = Matcher(Dataset.from_graph(data))

    calls = {"preprocess": 0, "build_plan": 0}
    real_pre = matcher_mod.preprocess
    real_bp = matcher_mod.build_plan

    def counting_pre(*a, **kw):
        calls["preprocess"] += 1
        return real_pre(*a, **kw)

    def counting_bp(*a, **kw):
        calls["build_plan"] += 1
        return real_bp(*a, **kw)

    monkeypatch.setattr(matcher_mod, "preprocess", counting_pre)
    monkeypatch.setattr(matcher_mod, "build_plan", counting_bp)

    a = m.count(query, engine="vector", limit=10**9)
    b = m.count(query, engine="vector", limit=10**9)
    assert a.count == b.count
    assert calls["preprocess"] == 1
    assert calls["build_plan"] == 1
    assert not a.plan_cached and b.plan_cached
    info = m.cache_info()
    assert info.misses == 1 and info.hits >= 1 and info.size == 1


def test_compile_s_reported_for_both_engines():
    data, query = fig1_pair()
    m = Matcher(Dataset.from_graph(data))
    for engine in ("ref", "vector"):
        m.clear_cache()
        cold = m.count(query, engine=engine, limit=10**9)
        warm = m.count(query, engine=engine, limit=10**9)
        assert cold.compile_s > 0.0, engine
        assert not cold.plan_cached and warm.plan_cached
        # a cache hit skips filtering/analysis/plan build entirely; bound it
        # absolutely rather than against cold's wall clock (timing flake)
        assert warm.compile_s < 0.05, engine
        # elapsed_s is enumeration only: both fields are reported separately
        assert warm.elapsed_s >= 0.0 and warm.count == cold.count


def test_plan_cache_keyed_by_plan_relevant_options():
    data, query = fig1_pair()
    m = Matcher(Dataset.from_graph(data))
    m.compile(query)                                  # encoding="cost"
    m.compile(query, encoding="all_black")            # different plan
    m.compile(query, engine="vector", tile_rows=64)   # runtime knob: same plan
    info = m.cache_info()
    assert info.misses == 2
    assert info.hits == 1


def test_plan_cache_lru_eviction():
    g = synthetic_labeled_graph(300, 5.0, 4, seed=0)
    queries = [random_walk_query(g, 4, seed=s) for s in (1, 2, 3)]
    m = Matcher(Dataset.from_graph(g), plan_cache_size=2)
    for q in queries:
        m.compile(q)
    assert m.cache_info().size == 2
    m.compile(queries[0])                 # evicted -> recompiles
    assert m.cache_info().misses == 4


def test_signature_distinguishes_labels_and_edges():
    g1 = build_graph(3, [(0, 1), (1, 2)], [0, 1, 0])
    g2 = build_graph(3, [(0, 1), (1, 2)], [0, 1, 1])
    g3 = build_graph(3, [(0, 1), (0, 2)], [0, 1, 0])
    sigs = {graph_signature(g) for g in (g1, g2, g3)}
    assert len(sigs) == 3
    assert graph_signature(g1) == graph_signature(
        build_graph(3, [(1, 2), (0, 1)], [0, 1, 0]))   # edge order-insensitive


# ---------------------------------------------------------- options/validation

@pytest.mark.parametrize("bad_kw", [
    dict(engine="gpu"),
    dict(encoding="rainbow"),
    dict(order_heuristic="zzz"),
    dict(tile_rows=0),
    dict(tile_rows=-4),
    dict(limit=0),
    dict(budget=0),
    dict(budget=-1),
    dict(refine_rounds=-1),
])
def test_match_options_validation_errors(bad_kw):
    with pytest.raises(ValueError):
        MatchOptions(**bad_kw)


def test_match_options_replace_revalidates():
    opts = MatchOptions()
    assert opts.replace(limit=5).limit == 5
    with pytest.raises(ValueError):
        opts.replace(engine="nope")


def test_auto_engine_heuristic_documented_threshold():
    data, query = fig1_pair()
    m = Matcher(Dataset.from_graph(data))
    cq = m.compile(query)
    # tiny candidate space -> DFS engine
    assert int(cq.cs.sizes().sum()) < AUTO_VECTOR_MIN_ROWS
    assert cq.resolve_engine("auto") == "ref"
    assert m.count(query).engine == "ref"
    # directed data always resolves to the validated ref path
    gd = synthetic_labeled_graph(200, 5.0, 3, seed=1, directed=True)
    qd = random_walk_query(gd, 4, seed=2)
    md = Matcher(Dataset.from_graph(gd))
    assert md.compile(qd).resolve_engine("auto") == "ref"


# ------------------------------------------------------------------ streaming

def _is_embedding(query, data, emb):
    if set(emb.keys()) != set(range(query.n)):
        return False
    if len(set(emb.values())) != query.n:     # injective
        return False
    for u in range(query.n):
        if data.labels[emb[u]] != query.labels[u]:
            return False
        for w in query.neighbors(u):
            if not data.has_edge(emb[u], emb[int(w)]):
                return False
    return True


def test_stream_yields_valid_embeddings_and_honors_limit():
    data, query = fig1_pair()
    m = Matcher(Dataset.from_graph(data))
    total = m.count(query, limit=10**9).count
    embs = list(m.stream(query))
    assert len(embs) == total
    assert all(_is_embedding(query, data, e) for e in embs)
    assert len(list(m.stream(query, limit=2))) == 2
    # laziness: creating the iterator does no work until first item
    it = m.stream(query)
    assert hasattr(it, "__next__")


@pytest.mark.parametrize("limit", [1, 3, 40])
def test_vector_materialize_stops_at_limit(limit):
    """A leaf tile's rows expand to the product of their bitmap sets, far
    more than `limit` on a large graph: decoding stops at the limit, and
    what it keeps is the prefix of the uncapped enumeration."""
    g = synthetic_labeled_graph(300, 6.0, 2, seed=3)
    q = random_walk_query(g, 4, seed=2)
    m = Matcher(Dataset.from_graph(g))
    full = m.count(q, engine="vector", materialize=True, limit=10**9)
    assert full.count > 40 and len(full.embeddings) == full.count
    out = m.count(q, engine="vector", materialize=True, limit=limit)
    assert out.count == limit
    assert out.embeddings == full.embeddings[:limit]
    assert all(_is_embedding(q, g, e) for e in out.embeddings)


def test_vector_materialize_reads_strided_host_copies(monkeypatch):
    """A leaf tile copied from the TPU can come back with rows that are not
    contiguous in host memory: decoding must not depend on the host
    copy's memory order."""
    from repro.core.engine import VectorEngine
    g = synthetic_labeled_graph(300, 6.0, 2, seed=3)
    q = random_walk_query(g, 4, seed=2)
    want = Matcher(Dataset.from_graph(g)).count(
        q, engine="vector", materialize=True, limit=10**9).embeddings
    decode = VectorEngine._materialize

    def strided(self, tile, cap):
        bm = {u: np.asfortranarray(np.asarray(v))
              for u, v in tile["bm"].items()}
        assert any(b.ndim == 2 and b.shape[1] > 1 and b.shape[0] > 1
                   for b in bm.values())
        return decode(self, dict(tile, bm=bm), cap)

    monkeypatch.setattr(VectorEngine, "_materialize", strided)
    got = Matcher(Dataset.from_graph(g)).count(
        q, engine="vector", materialize=True, limit=10**9).embeddings
    assert got == want and len(got) > 40


def test_match_many_shares_cache():
    g = synthetic_labeled_graph(300, 5.0, 4, seed=0)
    q = random_walk_query(g, 4, seed=1)
    m = Matcher(Dataset.from_graph(g))
    outs = m.match_many([q, q, q], limit=10**6)
    assert len({o.count for o in outs}) == 1
    assert m.cache_info().misses == 1
    assert m.cache_info().hits >= 2


def test_empty_candidate_space_short_circuits():
    g = synthetic_labeled_graph(200, 5.0, 3, seed=0)
    # a query label that does not exist in the data graph
    q = build_graph(2, [(0, 1)], [7, 7], n_labels=8)
    m = Matcher(Dataset.from_graph(g))
    for engine in ("ref", "vector", "auto"):
        out = m.count(q, engine=engine)
        assert out.count == 0 and not out.timed_out
        assert out.stats is not None


# -------------------------------------------------------------------- explain

def test_explain_mentions_order_colors_and_stages():
    data, query = fig1_pair()
    m = Matcher(Dataset.from_graph(data))
    text = m.explain(query, engine="vector")
    assert "order:" in text and "stages:" in text
    assert "engine: vector" in text
    assert "vector plan:" in text
    assert ("black" in text) or ("white" in text)


# ------------------------------------------------------------ queue + shims

def test_queue_counts_plan_cache_hits(tmp_path):
    from repro.runtime.queue import MatchQueueRuntime
    g = synthetic_labeled_graph(120, 5.0, 3, seed=0, power_law=False)
    q = random_walk_query(g, 4, seed=1)
    rt = MatchQueueRuntime(g, tile_rows=64)
    rt.submit([q, q, q], limit=10**6)
    results = rt.run()
    assert len(results) == 3 and len(set(results.values())) == 1
    assert rt.stats["cache_hits"] == 2      # duplicates reuse the plan


def test_deprecated_shims_warn_once_per_process():
    data, query = fig1_pair()
    core._DEPRECATION_WARNED.discard("cemr_match")
    with pytest.warns(DeprecationWarning, match="repro.api"):
        first = core.cemr_match(query, data, limit=10**9)
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")            # second call must stay silent
        second = core.cemr_match(query, data, limit=10**9)
    assert first.count == second.count


def test_deprecated_vector_shim_matches_engine():
    data, query = fig1_pair()
    core._DEPRECATION_WARNED.discard("vector_match")
    with pytest.warns(DeprecationWarning, match="repro.api"):
        res = core.vector_match(query, data, limit=10**9)
    assert res.count == cemr_match(query, data, limit=10**9).count
