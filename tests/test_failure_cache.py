"""Differential + adversarial tests for the failure-reuse negative cache:
counts must be bit-identical with `use_failure_cache` on and off across the
ref engine, the single-query vector path, superbatched `match_many`, and the
sharded path — on fig1 and the shared `strategies` workloads (undirected /
directed / edge-labeled), with ring capacities small enough to force
wraparound, and composed with the CER buffer in every combination. The
adversarial half corrupts live buffer entries mid-run through the
`fail_debug_hook` test hook and asserts the exact-key verify rejects them: a
poisoned slot may cost a recompute, never a count.

Run standalone (or via scripts/ci.sh) the module forces
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before jax loads so
the sharded assertions run; inside a full-suite run where jax already holds
one device they skip."""
import dataclasses
import os
import sys

if "jax" not in sys.modules and "--xla_force_host_platform_device_count" \
        not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

import jax
import jax.numpy as jnp
import pytest
from strategies import HAS_HYPOTHESIS, batch_workload, fig1_pair, random_pair

from repro.api import Dataset, Matcher, MatchOptions
from repro.core.scheduler import _pack_fail, _unpack_fail

MULTI = len(jax.devices()) > 1
needs_devices = pytest.mark.skipif(
    not MULTI, reason="needs XLA_FLAGS=--xla_force_host_platform_"
                      "device_count=4 (run this file standalone)")


def _counts(outs):
    return [o.count for o in outs]


def _on_off(data, query, **kw):
    """(on, on-warm, off) outcomes from one Matcher — the warm second run
    re-enumerates against the populated ring buffer, so any unsound hit
    would desynchronize it from the cold and cache-off counts."""
    m = Matcher(Dataset.from_graph(data))
    base = dict(engine="vector", limit=10**9)
    base.update(kw)
    on = m.count(query, MatchOptions(use_failure_cache=True, **base))
    on2 = m.count(query, MatchOptions(use_failure_cache=True, **base))
    off = m.count(query, MatchOptions(use_failure_cache=False, **base))
    return on, on2, off


def _scheduler_of(m, query, opts):
    """The live TileScheduler behind `m.count(query, opts)` (engine and
    scheduler instances are cached per option key, so hooks installed here
    fire on subsequent counts with the same options)."""
    cq = m.compile(query, opts)
    eng = cq.vector_engine(opts)
    return eng._scheduler


# --------------------------------------------------------------- parity

def test_fig1_parity_across_engines():
    data, query = fig1_pair()
    on, on2, off = _on_off(data, query)
    ref = Matcher(Dataset.from_graph(data)).count(
        query, MatchOptions(engine="ref", limit=10**9))
    assert on.count == on2.count == off.count == ref.count == 3
    m = Matcher(Dataset.from_graph(data))
    for fc in (True, False):
        opts = MatchOptions(engine="vector", limit=10**9,
                            use_failure_cache=fc)
        bat = m.match_many([query, query], opts, batch="auto")
        assert _counts(bat) == [3, 3]


@pytest.mark.parametrize("seed,qsize", [(3, 4), (3, 6), (5, 6), (7, 6),
                                        (13, 5), (21, 6)])
def test_random_pairs_parity(seed, qsize):
    query, data = random_pair(seed, qsize=qsize)
    if query is None:
        pytest.skip("random walk failed for this seed")
    on, on2, off = _on_off(data, query)
    ref = Matcher(Dataset.from_graph(data)).count(
        query, MatchOptions(engine="ref", limit=10**9))
    assert on.count == on2.count == off.count == ref.count


@pytest.mark.parametrize("directed,n_el", [(True, None), (False, 3),
                                           (True, 3)])
def test_ref_engine_regimes_stay_schema_stable(directed, n_el):
    """Directed / edge-labeled data resolves to the ref engine under
    engine="auto": the knob must be inert there (identical counts) and the
    outcome schema stable either way."""
    query, data = random_pair(11, directed=directed, n_edge_labels=n_el)
    if query is None:
        pytest.skip("random walk failed for this seed")
    m = Matcher(Dataset.from_graph(data))
    on = m.count(query, MatchOptions(engine="auto", limit=10**9,
                                     use_failure_cache=True))
    off = m.count(query, MatchOptions(engine="auto", limit=10**9,
                                      use_failure_cache=False))
    assert on.engine == off.engine == "ref"
    assert on.count == off.count


def test_warm_buffer_hits_prune_and_stay_exact():
    """Second run against the populated buffer: known failures must be
    looked up (hits), masked (pruned rows), and the count unchanged."""
    query, data = random_pair(7, qsize=6)
    on, on2, off = _on_off(data, query)
    assert on.stats.fail_inserts > 0
    assert on2.stats.fail_hits > 0
    assert on2.stats.fail_pruned_rows >= on2.stats.fail_hits > 0
    assert on.count == on2.count == off.count
    assert off.stats.fail_hits == off.stats.fail_inserts == 0


def test_ring_wraparound_slots2():
    """failure_cache_slots=2 with more distinct failing keys than capacity:
    the ring pointer wraps, evicted entries just recompute, counts hold."""
    query, data = random_pair(7, qsize=6)
    on, on2, off = _on_off(data, query, failure_cache_slots=2)
    assert on.stats.fail_inserts > 2          # exceeded capacity -> wrapped
    assert on2.stats.fail_hits > 0
    assert on.count == on2.count == off.count


@pytest.mark.parametrize("cer,fail", [(True, True), (True, False),
                                      (False, True), (False, False)])
def test_composes_with_cer_buffer(cer, fail):
    """Every CER-buffer x failure-cache combination agrees; with the CER
    buffer off the compat stage-at-a-time loop runs, which has no failure
    cache wiring and must report its stats as zeros."""
    query, data = random_pair(3, qsize=6)
    base = Matcher(Dataset.from_graph(data)).count(
        query, MatchOptions(engine="ref", limit=10**9)).count
    m = Matcher(Dataset.from_graph(data))
    o = m.count(query, MatchOptions(engine="vector", limit=10**9,
                                    use_cer_buffer=cer,
                                    use_failure_cache=fail))
    assert o.count == base
    if not cer:
        assert o.stats.fail_hits == o.stats.fail_misses == 0
        assert o.stats.fail_inserts == o.stats.fail_pruned_rows == 0


def test_composes_with_dedup_off():
    query, data = random_pair(7, qsize=6)
    on, on2, off = _on_off(data, query, use_dedup=False)
    assert on.count == on2.count == off.count


def test_compat_loop_reports_zero_fail_stats():
    """use_cer_buffer=False selects the compat loop: the fail-cache counters
    must exist (schema-stable benchmark JSON rows) and read zero."""
    query, data = random_pair(3)
    m = Matcher(Dataset.from_graph(data))
    o = m.count(query, MatchOptions(engine="vector", limit=10**9,
                                    use_cer_buffer=False))
    d = dataclasses.asdict(o.stats)
    for k in ("fail_hits", "fail_misses", "fail_inserts",
              "fail_pruned_rows"):
        assert d[k] == 0


def test_superbatch_parity_and_activity():
    data, queries = batch_workload(seed=9, n=260, n_queries=4, dup=2,
                                   qsizes=(5, 6))
    m = Matcher(Dataset.from_graph(data))
    rows = {}
    for fc in (True, False):
        opts = MatchOptions(engine="vector", limit=10**9,
                            use_failure_cache=fc)
        cold = m.match_many(queries, opts, batch="auto")
        warm = m.match_many(queries, opts, batch="auto")
        assert _counts(cold) == _counts(warm)
        rows[fc] = (cold, warm)
    assert _counts(rows[True][0]) == _counts(rows[False][0])
    stats = {id(o.stats): o.stats for o in rows[True][1]}.values()
    assert sum(s.fail_hits for s in stats) > 0
    stats_off = {id(o.stats): o.stats for o in rows[False][1]}.values()
    assert all(s.fail_hits == s.fail_inserts == 0 for s in stats_off)


# --------------------------------------------------------------- sharded

@needs_devices
def test_sharded_parity():
    query, data = random_pair(7, qsize=6)
    m = Matcher(Dataset.from_graph(data))
    base = dict(engine="vector", limit=10**9, mesh=4)
    on = m.count(query, MatchOptions(use_failure_cache=True, **base))
    on2 = m.count(query, MatchOptions(use_failure_cache=True, **base))
    off = m.count(query, MatchOptions(use_failure_cache=False, **base))
    seq = m.count(query, MatchOptions(engine="vector", limit=10**9))
    assert on.count == on2.count == off.count == seq.count


@needs_devices
def test_sharded_superbatch_parity():
    data, queries = batch_workload(seed=9, n=260, n_queries=4, dup=2,
                                   qsizes=(5, 6))
    m = Matcher(Dataset.from_graph(data))
    outs = {}
    for fc in (True, False):
        opts = MatchOptions(engine="vector", limit=10**9, mesh=4,
                            use_failure_cache=fc)
        outs[fc] = m.match_many(queries, opts, batch="auto")
    assert _counts(outs[True]) == _counts(outs[False])


# ------------------------------------------------------------ adversarial

def _install_poison(m, query, opts, mutate):
    """Pre-poison the live buffers and install a hook that re-poisons after
    every superstep's fold-back, so no uncorrupted entry is ever visible to
    a lookup. Returns the hook-call counter; caller must clear the hook."""
    sched = _scheduler_of(m, query, opts)
    calls = {"n": 0}

    def hook(s):
        calls["n"] += 1
        mutate(s)

    mutate(sched)
    sched.fail_debug_hook = hook
    return sched, calls


def test_poisoned_keys_never_change_counts():
    """Corrupt every entry's key columns mid-run (hash/valid intact, so the
    hash probe still nominates the slot): the exact-key verify must reject
    it — zero hits, identical count."""
    query, data = random_pair(7, qsize=6)
    m = Matcher(Dataset.from_graph(data))
    opts = MatchOptions(engine="vector", limit=10**9,
                        use_failure_cache=True)
    clean = m.count(query, opts)                # populates the ring buffer
    off = m.count(query, MatchOptions(engine="vector", limit=10**9,
                                      use_failure_cache=False))

    def mutate(s):
        for si, rec in s._fail_buffers.items():
            buf = _unpack_fail(rec)
            s._fail_buffers[si] = _pack_fail({
                **buf, "keys": jnp.full_like(buf["keys"], -7777)})

    sched, calls = _install_poison(m, query, opts, mutate)
    try:
        poisoned = m.count(query, opts)
    finally:
        sched.fail_debug_hook = None
    assert calls["n"] > 0
    assert poisoned.count == clean.count == off.count
    assert poisoned.stats.fail_hits == 0        # every candidate rejected


def test_poisoned_hash_and_valid_never_change_counts():
    """Corrupt every entry's hash and force every slot valid (junk slots
    included): the probe can only nominate slots whose stored keys cannot
    equal any live row's keys, so the verify yields zero hits and the count
    is unchanged."""
    query, data = random_pair(7, qsize=6)
    m = Matcher(Dataset.from_graph(data))
    opts = MatchOptions(engine="vector", limit=10**9,
                        use_failure_cache=True)
    clean = m.count(query, opts)
    off = m.count(query, MatchOptions(engine="vector", limit=10**9,
                                      use_failure_cache=False))

    def mutate(s):
        for si, rec in s._fail_buffers.items():
            buf = _unpack_fail(rec)
            s._fail_buffers[si] = _pack_fail({
                **buf, "hash": jnp.full_like(buf["hash"], 777),
                "valid": jnp.ones_like(buf["valid"])})

    sched, calls = _install_poison(m, query, opts, mutate)
    try:
        poisoned = m.count(query, opts)
    finally:
        sched.fail_debug_hook = None
    assert calls["n"] > 0
    assert poisoned.count == clean.count == off.count
    assert poisoned.stats.fail_hits == 0


# --------------------------------------------------------------- options

def test_options_validation():
    with pytest.raises(ValueError, match="failure_cache_slots"):
        MatchOptions(failure_cache_slots=0)
    with pytest.raises(ValueError, match="failure_cache_slots"):
        MatchOptions(failure_cache_slots="lots")
    assert MatchOptions().use_failure_cache is True
    assert MatchOptions().failure_cache_slots == 64


# ------------------------------------------------------------- hypothesis
if HAS_HYPOTHESIS:
    from hypothesis import given, settings
    from strategies import failure_cache_regime

    @pytest.mark.tier2
    @settings(max_examples=12, deadline=None)
    @given(failure_cache_regime())
    def test_failure_cache_parity_property(regime):
        seed, qsize, slots, tile_rows, cer, dedup = regime
        query, data = random_pair(seed, qsize=qsize)
        if query is None:
            return
        m = Matcher(Dataset.from_graph(data))
        base = dict(engine="vector", tile_rows=tile_rows, limit=10**9,
                    use_cer_buffer=cer, use_dedup=dedup,
                    failure_cache_slots=slots)
        on = m.count(query, MatchOptions(use_failure_cache=True, **base))
        on2 = m.count(query, MatchOptions(use_failure_cache=True, **base))
        off = m.count(query, MatchOptions(use_failure_cache=False, **base))
        assert on.count == on2.count == off.count
