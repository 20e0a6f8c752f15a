"""Overlap bit-identity differentials: double-buffered supersteps
(`overlap=True`, the default) must change *when* readbacks happen and
nothing else. Counts AND VectorStats — modulo the two new overlap
counters `readbacks` / `overlapped_supersteps` — must be bit-identical
to the synchronous path across fig1, seeded random pairs,
directed / edge-labeled regimes, CER on/off, failure cache on/off, the
fused expand+intersect kernel, the cross-query superbatch, and the
forced-4-device sharded path; plus the readback accounting invariant.

Run standalone (or via scripts/ci.sh) the module forces
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before jax loads
so the sharded assertions run; inside a full-suite run with one device
they skip."""
import dataclasses
import os
import sys

if "jax" not in sys.modules and "--xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

import jax
import pytest
from strategies import HAS_HYPOTHESIS, batch_workload, fig1_pair, random_pair

from repro.api import Dataset, Matcher, MatchOptions
from repro.core.engine import vector_match

MULTI = len(jax.devices()) > 1
needs_devices = pytest.mark.skipif(
    not MULTI, reason="needs XLA_FLAGS=--xla_force_host_platform_"
                      "device_count=4 (run this file standalone)")

OVERLAP_COUNTERS = ("readbacks", "overlapped_supersteps")


def stats_mod_overlap(st, *, warmth=False):
    """VectorStats as a dict with the overlap-timing counters and the host
    span timings (`span_*_s`, wall times, never equal twice) removed —
    every remaining field must be bit-identical across overlap on/off.
    `warmth=True` also drops `bucket_recompiles`: superbatch programs are
    shared through a module-level jit cache keyed without overlap (the
    program is overlap-agnostic by design), so whichever run goes second
    inherits warm traces and legitimately reports fewer recompiles."""
    d = {k: v for k, v in dataclasses.asdict(st).items()
         if not k.startswith("span_")}
    for k in OVERLAP_COUNTERS:
        d.pop(k)
    if warmth:
        d.pop("bucket_recompiles")
    return d


def assert_overlap_invariant(st):
    """One coalesced readback of N in-flight supersteps counts as one
    `readbacks` plus N-1 `overlapped_supersteps`."""
    assert st.readbacks <= st.supersteps
    assert st.readbacks + st.overlapped_supersteps == st.supersteps


def _run_pair(query, data, *, overlap, **kw):
    return vector_match(query, data, limit=10**9, overlap=overlap, **kw)


# ------------------------------------------------------------ single query

@pytest.mark.parametrize("intersect", ["auto", "fused"])
@pytest.mark.parametrize("tile_rows", [8, 64])
def test_overlap_fig1_bit_identical(intersect, tile_rows):
    data, query = fig1_pair()
    on = _run_pair(query, data, overlap=True, tile_rows=tile_rows,
                   intersect=intersect)
    off = _run_pair(query, data, overlap=False, tile_rows=tile_rows,
                    intersect=intersect)
    assert on.count == off.count
    assert stats_mod_overlap(on.stats) == stats_mod_overlap(off.stats)
    assert_overlap_invariant(on.stats)
    assert_overlap_invariant(off.stats)
    # the synchronous path never holds two dispatches in flight
    assert off.stats.overlapped_supersteps == 0


@pytest.mark.parametrize("seed", [0, 3, 7, 12])
@pytest.mark.parametrize("intersect", ["auto", "fused"])
def test_overlap_random_pairs_bit_identical(seed, intersect):
    query, data = random_pair(seed, qsize=5)
    if query is None:
        pytest.skip("random walk failed for this seed")
    on = _run_pair(query, data, overlap=True, tile_rows=32,
                   intersect=intersect)
    off = _run_pair(query, data, overlap=False, tile_rows=32,
                    intersect=intersect)
    assert on.count == off.count
    assert stats_mod_overlap(on.stats) == stats_mod_overlap(off.stats)
    assert_overlap_invariant(on.stats)


@pytest.mark.parametrize("directed,n_el", [(True, None), (False, 2),
                                           (True, 2)])
def test_overlap_directed_edge_labeled(directed, n_el):
    query, data = random_pair(5, directed=directed, n_edge_labels=n_el,
                              qsize=4)
    if query is None:
        pytest.skip("random walk failed for this seed")
    on = _run_pair(query, data, overlap=True, tile_rows=16)
    off = _run_pair(query, data, overlap=False, tile_rows=16)
    assert on.count == off.count
    assert stats_mod_overlap(on.stats) == stats_mod_overlap(off.stats)


@pytest.mark.parametrize("cer,fc", [(True, False), (False, True),
                                    (False, False)])
def test_overlap_composes_with_cer_and_failure_cache(cer, fc):
    """The CER ring buffer and the failure cache fold forward at dispatch
    time as asynchronous device values — their hit/miss/insert counters
    must not move when readbacks are deferred."""
    query, data = random_pair(11, qsize=6)
    if query is None:
        pytest.skip("random walk failed for this seed")
    kw = dict(tile_rows=16, use_cer_buffer=cer, use_failure_cache=fc)
    on = _run_pair(query, data, overlap=True, **kw)
    off = _run_pair(query, data, overlap=False, **kw)
    assert on.count == off.count
    assert stats_mod_overlap(on.stats) == stats_mod_overlap(off.stats)


def test_overlap_actually_overlaps():
    """With small tiles a multi-superstep run must coalesce at least one
    readback — otherwise the double-buffering never engaged and the other
    tests are vacuous."""
    query, data = random_pair(12, qsize=5)
    res = _run_pair(query, data, overlap=True, tile_rows=8)
    assert res.stats.supersteps > 1
    assert res.stats.overlapped_supersteps > 0
    assert res.stats.readbacks < res.stats.supersteps


# -------------------------------------------------------------- superbatch

def test_overlap_superbatch_bit_identical():
    data, queries = batch_workload(seed=4, n=200, n_queries=3, dup=2)
    m = Matcher(Dataset.from_graph(data))
    base = dict(engine="vector", tile_rows=32, limit=10**9)
    on = m.match_many(queries, MatchOptions(overlap=True, **base),
                      batch="auto")
    off = m.match_many(queries, MatchOptions(overlap=False, **base),
                       batch="auto")
    assert [o.count for o in on] == [o.count for o in off]
    stats_on = {id(o.stats): o.stats for o in on}.values()
    stats_off = {id(o.stats): o.stats for o in off}.values()
    assert ([stats_mod_overlap(s, warmth=True) for s in stats_on]
            == [stats_mod_overlap(s, warmth=True) for s in stats_off])
    for s in stats_on:
        assert_overlap_invariant(s)


# ----------------------------------------------------------------- sharded

@needs_devices
@pytest.mark.parametrize("intersect", ["auto", "fused"])
def test_overlap_sharded_bit_identical(intersect):
    query, data = random_pair(3, qsize=5)
    if query is None:
        pytest.skip("random walk failed for this seed")
    m = Matcher(Dataset.from_graph(data))
    base = dict(engine="vector", tile_rows=16, limit=10**9, mesh=4,
                intersect=intersect)
    on = m.count(query, MatchOptions(overlap=True, **base))
    off = m.count(query, MatchOptions(overlap=False, **base))
    seq = m.count(query, MatchOptions(overlap=True, engine="vector",
                                      tile_rows=16, limit=10**9,
                                      intersect=intersect))
    assert on.count == off.count == seq.count
    assert stats_mod_overlap(on.stats) == stats_mod_overlap(off.stats)
    assert_overlap_invariant(on.stats)
    assert_overlap_invariant(off.stats)


@needs_devices
def test_overlap_sharded_superbatch_bit_identical():
    data, queries = batch_workload(seed=6, n=220, n_queries=3, dup=2)
    m = Matcher(Dataset.from_graph(data))
    base = dict(engine="vector", tile_rows=32, limit=10**9, mesh=4)
    on = m.match_many(queries, MatchOptions(overlap=True, **base),
                      batch="auto")
    off = m.match_many(queries, MatchOptions(overlap=False, **base),
                       batch="auto")
    assert [o.count for o in on] == [o.count for o in off]
    stats_on = {id(o.stats): o.stats for o in on}.values()
    stats_off = {id(o.stats): o.stats for o in off}.values()
    assert ([stats_mod_overlap(s, warmth=True) for s in stats_on]
            == [stats_mod_overlap(s, warmth=True) for s in stats_off])


# ---------------------------------------------------------------- options

def test_overlap_option_validation():
    with pytest.raises(ValueError, match="overlap"):
        MatchOptions(overlap="yes")
    assert MatchOptions().overlap is True
    assert MatchOptions(overlap=False).overlap is False


# ------------------------------------------------------------- hypothesis
if HAS_HYPOTHESIS:
    from hypothesis import given, settings
    from strategies import overlap_regime

    @pytest.mark.tier2
    @settings(max_examples=12, deadline=None)
    @given(overlap_regime())
    def test_overlap_parity_property(regime):
        (seed, directed, n_el, qsize, tile_rows, intersect, cer,
         fc) = regime
        query, data = random_pair(seed, directed=directed,
                                  n_edge_labels=n_el, qsize=qsize)
        if query is None:
            return
        kw = dict(tile_rows=tile_rows, intersect=intersect,
                  use_cer_buffer=cer, use_failure_cache=fc)
        on = _run_pair(query, data, overlap=True, **kw)
        off = _run_pair(query, data, overlap=False, **kw)
        assert on.count == off.count
        assert (stats_mod_overlap(on.stats)
                == stats_mod_overlap(off.stats))
        assert_overlap_invariant(on.stats)
        assert_overlap_invariant(off.stats)
