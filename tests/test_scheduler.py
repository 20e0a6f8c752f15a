"""Device-resident tile scheduler: supersteps, step accounting, CER buffer,
per-tile bucketed compat path, tile packing, and on-device leaf counting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from strategies import brother_workload

from repro.core.engine import VectorEngine, vector_match
from repro.core.graph import random_walk_query, synthetic_labeled_graph
from repro.core.oracle import nx_count
from repro.core.ref_engine import preprocess
from repro.core.scheduler import leaf_count_host, make_leaf_reduce


# ------------------------------------------------------------ step accounting
def test_fused_dispatch_identity():
    """device_steps counts jitted dispatches exactly once: every fused
    superstep (leaf reduction included) plus every pack merge."""
    data = synthetic_labeled_graph(120, 6.0, 4, seed=0, power_law=True)
    query = random_walk_query(data, 8, seed=31)
    res = vector_match(query, data, limit=10**9, tile_rows=16)
    st = res.stats
    assert st.supersteps > 0
    assert st.device_steps == st.supersteps + st.packed_tiles


@pytest.mark.parametrize("kwargs", [
    dict(),                                      # fused scheduler
    dict(use_cer_buffer=False),                  # compat stage-at-a-time loop
    dict(use_cer_buffer=False, use_dedup=False),  # compat without CER
])
def test_budget_not_double_charged(kwargs):
    """Regression for the pre-scheduler 2x charge: expansion re-enqueues and
    leaf tiles both bumped device_steps, so a budget equal to the measured
    dispatch count used to time out. Now max_steps == device_steps of a full
    run must complete."""
    data = synthetic_labeled_graph(80, 6.0, 2, seed=1, power_law=False)
    query = random_walk_query(data, 6, seed=8)
    full = vector_match(query, data, limit=10**9, tile_rows=32, **kwargs)
    steps = full.stats.device_steps
    assert steps > 1
    again = vector_match(query, data, limit=10**9, tile_rows=32,
                         max_steps=steps, **kwargs)
    assert not again.timed_out
    assert again.count == full.count
    capped = vector_match(query, data, limit=10**9, tile_rows=32,
                          max_steps=steps // 2, **kwargs)
    assert capped.timed_out


# ----------------------------------------------------------- CER bucketed path
def test_bucketed_compute_triggers_and_matches():
    """The compat path's per-tile bucketed CER: under all_black (the paper's
    CER-only configuration) the brother workload expands to 36 (a, b) rows
    keyed on 3 hub classes — 0 < n_unique <= rows // 2, so
    _bucket_compute_fn must fire, with count parity against both no-dedup
    and the oracle."""
    query, data = brother_workload()
    expect = nx_count(query, data)
    res = vector_match(query, data, limit=10**9, tile_rows=64,
                       encoding="all_black", use_cer_buffer=False)
    st = res.stats
    assert res.count == expect
    assert st.bucketed_tiles > 0
    assert 0 < st.dedup_unique <= st.dedup_keys_seen // 2
    plain = vector_match(query, data, limit=10**9, tile_rows=64,
                         encoding="all_black", use_dedup=False)
    assert plain.count == expect


def test_cer_buffer_cross_tile_hits_on_brother_workload():
    """Chunked expansion splits the 36 brother rows across sibling tiles;
    later chunks must be served from the ring buffer."""
    query, data = brother_workload()
    expect = nx_count(query, data)
    res = vector_match(query, data, limit=10**9, tile_rows=16,
                       encoding="all_black", pack_tiles=False)
    assert res.count == expect
    assert res.stats.cer_hits > 0
    # every brother class is computed at most once per chunk set
    assert res.stats.dedup_unique <= res.stats.dedup_keys_seen // 2


# ------------------------------------------------------------ CER ring buffer
@pytest.mark.parametrize("seed", [1, 4])
def test_cer_buffer_hits_and_parity(seed):
    data = synthetic_labeled_graph(120, 6.0, 4, seed=seed, power_law=True)
    query = random_walk_query(data, 8, seed=seed + 31)
    res = vector_match(query, data, limit=10**9, tile_rows=16)
    assert res.stats.cer_hits > 0
    assert res.stats.cer_misses > 0
    plain = vector_match(query, data, limit=10**9, tile_rows=16,
                         use_dedup=False)
    assert res.count == plain.count


def test_cer_buffer_warm_across_runs():
    """The ring buffer is engine-lifetime (values are pure functions of the
    read-set given the fixed tables): a second run on the same engine starts
    warm and must serve at least as many hits, with identical counts."""
    data = synthetic_labeled_graph(120, 6.0, 4, seed=4, power_law=True)
    query = random_walk_query(data, 8, seed=35)
    cs, an = preprocess(query, data)
    eng = VectorEngine(cs, an, tile_rows=16)
    first = eng.run(limit=10**9)
    second = eng.run(limit=10**9)
    assert second.count == first.count
    assert second.stats.cer_hits >= first.stats.cer_hits
    assert second.stats.cer_misses <= first.stats.cer_misses


# --------------------------------------------------------------- tile packing
def test_tile_packing_parity():
    """Ladder supersteps consume sub-capacity frontiers in-device, so packing
    engages only for overflowing frontiers with few live rows — a dense
    workload with a tiny tile forces that regime."""
    data = synthetic_labeled_graph(200, 8.0, 3, seed=4, power_law=True)
    query = random_walk_query(data, 7, seed=35)
    packed = vector_match(query, data, limit=10**9, tile_rows=8)
    assert packed.stats.packed_tiles > 0
    loose = vector_match(query, data, limit=10**9, tile_rows=8,
                         pack_tiles=False)
    assert packed.count == loose.count
    # packing merges sub-capacity siblings -> no more supersteps than loose
    assert packed.stats.supersteps <= loose.stats.supersteps


# ---------------------------------------------------------- on-device leaves
def _device_leaf(singles, groups, terms, alive):
    red = make_leaf_reduce(singles, groups)
    with jax.enable_x64(True):
        cnt, ovf = jax.jit(red)(jnp.asarray(terms, jnp.int32),
                                jnp.asarray(alive, bool))
    return int(jax.device_get(cnt)), bool(jax.device_get(ovf))


def test_leaf_reduce_matches_host():
    rng = np.random.default_rng(0)
    singles, groups = [7], [[1, 2], [3, 4, 5]]   # 1 + 3 + 7 = 11 terms
    terms = rng.integers(0, 40, size=(64, 11)).astype(np.int32)
    # keep inclusion-exclusion terms consistent: p(a&b) <= min(pa, pb) etc.
    terms[:, 3] = np.minimum(terms[:, 1], terms[:, 2])
    for k in (7, 8, 9, 10):
        terms[:, k] = np.minimum.reduce([terms[:, 4], terms[:, 5],
                                         terms[:, 6]])
    alive = rng.random(64) < 0.8
    want = leaf_count_host(singles, groups, terms, alive)
    got, ovf = _device_leaf(singles, groups, terms, alive)
    assert not ovf
    assert got == want


def test_leaf_reduce_overflow_falls_back_exact():
    """Per-row products past 2**63 must trip the device overflow flag; the
    host big-int path stays exact."""
    singles = [0, 1, 2, 3, 4]
    terms = np.full((2, 5), 8192, dtype=np.int32)      # 8192**5 = 2**65
    alive = np.array([True, True])
    _, ovf = _device_leaf(singles, [], terms, alive)
    assert ovf
    exact = leaf_count_host(singles, [], terms, alive)
    assert exact == 2 * 8192 ** 5


def test_leaf_overflow_engine_integration(monkeypatch):
    """Force the conservative overflow bound to trip on a real workload: the
    fused scheduler must fall back to the host path and still count exactly."""
    import repro.core.scheduler as sched
    data = synthetic_labeled_graph(60, 5.0, 3, seed=2, power_law=False)
    query = random_walk_query(data, 5, seed=12)
    expect = nx_count(query, data)
    baseline = vector_match(query, data, limit=10**9, tile_rows=64)
    assert baseline.count == expect and baseline.stats.leaf_overflows == 0
    monkeypatch.setattr(sched, "OVERFLOW_LIMIT", 0.5)
    forced = vector_match(query, data, limit=10**9, tile_rows=64)
    assert forced.count == expect
    assert forced.stats.leaf_overflows > 0


# ----------------------------------------------------------- intersect modes
def test_intersect_mode_parity():
    data = synthetic_labeled_graph(60, 5.0, 3, seed=3, power_law=False)
    query = random_walk_query(data, 5, seed=13)
    a = vector_match(query, data, limit=10**9, tile_rows=64, intersect="jnp")
    b = vector_match(query, data, limit=10**9, tile_rows=64,
                     intersect="pallas")
    assert a.count == b.count


def test_intersect_mode_validation():
    data = synthetic_labeled_graph(40, 4.0, 2, seed=0, power_law=False)
    query = random_walk_query(data, 3, seed=1)
    with pytest.raises(ValueError):
        vector_match(query, data, intersect="nope")


# ------------------------------------------------------- overlap accounting
def test_readback_accounting_under_overlap():
    """One device_steps per dispatch must still hold under overlap, and the
    deferred readbacks obey readbacks <= supersteps with every superstep
    accounted for: readbacks + overlapped_supersteps == supersteps.
    (Regression: the pre-overlap accounting assumed one readback per
    superstep, so coalescing would have silently undercounted syncs.)"""
    data = synthetic_labeled_graph(80, 6.0, 2, seed=1, power_law=False)
    query = random_walk_query(data, 6, seed=8)
    for overlap in (True, False):
        res = vector_match(query, data, limit=10**9, tile_rows=16,
                           overlap=overlap)
        st = res.stats
        assert st.device_steps == st.supersteps + st.packed_tiles
        assert 0 < st.readbacks <= st.supersteps
        assert st.readbacks + st.overlapped_supersteps == st.supersteps
        if not overlap:
            # the synchronous path syncs every dispatch individually
            assert st.readbacks == st.supersteps
            assert st.overlapped_supersteps == 0


def test_compat_loop_has_no_readback_counters():
    """The stage-at-a-time compat loop (use_cer_buffer=False) predates the
    fused superstep readback protocol; its overlap counters stay zero."""
    data = synthetic_labeled_graph(60, 5.0, 2, seed=3, power_law=False)
    query = random_walk_query(data, 5, seed=13)
    res = vector_match(query, data, limit=10**9, tile_rows=32,
                       use_cer_buffer=False)
    assert res.stats.readbacks == 0
    assert res.stats.overlapped_supersteps == 0
