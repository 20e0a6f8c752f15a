"""The superstep's call signature on the tiny bench graph: a call takes
the tile, `r`, the cursor, one buffer per table width group and one
packed ring record per CER and failure stage of its ladder, and hands
back one record per stage. Counts and the CER and failure counters equal
the ones the per-field dict layout gave on the same queries (the literals
below), with tiles and rings small enough that frontiers overflow and
both rings wrap."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import TINY_GRAPH

from bench import graphgen
from repro.api import Dataset, Matcher, MatchOptions
from repro.core.graph import build_graph
from repro.core.scheduler import (TileScheduler, _empty_cer_record,
                                  _empty_fail_record, _init_cer_buffer,
                                  _init_fail_buffer, _pack_cer, _pack_fail,
                                  _unpack_cer, _unpack_fail)

OPTS = MatchOptions(engine="vector", limit=10**5, tile_rows=32,
                    cer_buffer_slots=16, failure_cache_slots=8)
# pool index: (count, cer_hits, fail_hits, fail_pruned_rows) of the dict
# layout, where every ring field and every table crossed the call alone
DICT_LAYOUT = {1: (1656, 34, 0, 0),           # Q8S, walk seed 0
               2: (6086, 536, 81, 81)}        # Q16D, walk seed 1


@pytest.fixture(scope="module")
def tiny(pool_of):
    g = graphgen.data_graph(TINY_GRAPH)
    ds = Dataset.from_edges(g.n, g.edges, g.labels, n_labels=g.n_labels,
                            name="tiny")
    pool = pool_of(TINY_GRAPH, sizes=(8, 16), per_set=1)
    return ds, [build_graph(len(q["labels"]), np.asarray(q["edges"]),
                            q["labels"], n_labels=g.n_labels) for q in pool]


@pytest.fixture
def calls(monkeypatch):
    """Every superstep call of the test: (scheduler, args, outputs,
    seg_cer, seg_fail)."""
    seen = []
    build = TileScheduler._superstep

    def spy(self, b):
        fn, exit_bounds, seg_cer, seg_fail, *rest = build(self, b)

        def call(*args):
            outs = fn(*args)
            seen.append((self, args, outs, seg_cer, seg_fail))
            return outs
        return (call, exit_bounds, seg_cer, seg_fail, *rest)

    monkeypatch.setattr(TileScheduler, "_superstep", spy)
    return seen


@pytest.mark.parametrize("qi", sorted(DICT_LAYOUT),
                         ids=[f"pool{qi}" for qi in sorted(DICT_LAYOUT)])
def test_superstep_takes_one_buffer_per_group_and_ring(tiny, calls, qi):
    ds, queries = tiny
    out = Matcher(ds).count(queries[qi], OPTS)
    st = out.stats
    assert (out.count, st.cer_hits, st.fail_hits,
            st.fail_pruned_rows) == DICT_LAYOUT[qi]
    assert st.supersteps == len(calls) > 1
    assert st.dedup_unique > OPTS.cer_buffer_slots    # the CER ring wraps
    if st.fail_hits:                                  # so does the failure one
        assert st.fail_inserts > OPTS.failure_cache_slots
    eng = calls[0][0].eng
    plan = eng.plan
    widths = {t.shape[1] for t in plan.tables.values()}
    widths |= {m.shape[0] for m in plan.masks.values()}
    assert len(eng.packed.groups) == len(widths)
    assert eng.packed.widths == sorted(widths)
    total = 0
    for sched, args, outs, seg_cer, seg_fail in calls:
        tile, r, cursor, bufs, fbufs, groups = args
        assert groups is eng.packed.groups
        assert isinstance(cursor, np.int32)
        assert sorted(bufs) == seg_cer and sorted(fbufs) == seg_fail
        n_in = len(jax.tree.leaves(tile)) + 1 + 1 + len(groups) \
            + len(seg_cer) + len(seg_fail)
        assert len(jax.tree.leaves(args)) == n_in
        bufs2, fbufs2 = outs[6], outs[7]
        for before, after in ((bufs, bufs2), (fbufs, fbufs2)):
            assert sorted(after) == sorted(before)
            for si, rec in after.items():
                assert isinstance(rec, jax.Array)
                assert rec.shape == before[si].shape
                assert rec.dtype == before[si].dtype
        total += n_in + len(jax.tree.leaves(outs))
    assert st.dispatch_buffers == total


def test_table_view_reads_the_plans_tables_and_masks(tiny):
    ds, queries = tiny
    eng = Matcher(ds).compile(queries[2], OPTS).vector_engine(OPTS)
    tables, masks = eng.packed.view(eng.packed.groups)
    plan = eng.plan
    assert sorted(tables) == sorted(f"{u}:{w}" for (u, w) in plan.tables)
    for (u, w), t in plan.tables.items():
        np.testing.assert_array_equal(np.asarray(tables[f"{u}:{w}"]), t)
        assert eng.packed.shape(f"{u}:{w}") == t.shape
    assert sorted(masks) == sorted(plan.masks)
    for u, m in plan.masks.items():
        np.testing.assert_array_equal(np.asarray(masks[u]), m)


def test_ring_records_round_trip_every_field():
    rng = np.random.default_rng(3)
    i32 = lambda *s: jnp.asarray(  # noqa: E731
        rng.integers(-2**31, 2**31, size=s, dtype=np.int64).astype(np.int32))
    cer = {"keys": i32(16, 3), "hash": i32(16), "pops": i32(16),
           "valid": jnp.asarray(rng.random(16) < 0.5),
           "vals": jnp.asarray(rng.integers(0, 2**32, size=(16, 5),
                                            dtype=np.uint64)
                               .astype(np.uint32)),
           "ptr": jnp.int32(11)}
    fail = {"keys": i32(8, 2), "hash": i32(8), "wit": i32(8),
            "valid": jnp.asarray(rng.random(8) < 0.5), "ptr": jnp.int32(5)}
    for buf, rec, back in (
            (cer, _pack_cer(cer), lambda rec: _unpack_cer(rec, 3)),
            (fail, _pack_fail(fail), _unpack_fail)):
        assert rec.ndim == 2
        got = back(rec)
        assert sorted(got) == sorted(buf)
        for k, v in buf.items():
            assert got[k].dtype == v.dtype and got[k].shape == v.shape, k
            np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(v))


def test_empty_ring_records_pack_the_empty_buffers():
    np.testing.assert_array_equal(
        np.asarray(_empty_cer_record(16, 3, 5)),
        np.asarray(_pack_cer(_init_cer_buffer(16, 3, 5))))
    np.testing.assert_array_equal(
        np.asarray(_empty_fail_record(8, 2)),
        np.asarray(_pack_fail(_init_fail_buffer(8, 2))))
