"""Pallas kernels (interpret=True) vs pure-jnp oracles: shape/dtype sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.bitmap_intersect import (bitmap_intersect_pallas,
                                            fused_expand_intersect_pallas)
from repro.kernels.flash_decode import flash_decode_pallas


# -------------------------------------------------------- bitmap_intersect
@pytest.mark.parametrize("k", [1, 2, 3, 4])
@pytest.mark.parametrize("t_rows,w", [(1, 1), (7, 3), (64, 8), (33, 17)])
def test_bitmap_intersect_sweep(k, t_rows, w):
    rng = np.random.default_rng(k * 1000 + t_rows + w)
    tables = tuple(
        jnp.asarray(rng.integers(0, 2**32, size=(int(rng.integers(4, 40)), w),
                                 dtype=np.uint32))
        for _ in range(k))
    idxs = jnp.asarray(np.stack(
        [rng.integers(0, tbl.shape[0], t_rows) for tbl in tables], 1
    ).astype(np.int32))
    r_ref, pop_ref = ref.bitmap_intersect_ref(tables, idxs)
    r_pal, pop_pal = bitmap_intersect_pallas(tables, idxs)
    np.testing.assert_array_equal(np.asarray(r_pal), np.asarray(r_ref))
    np.testing.assert_array_equal(np.asarray(pop_pal), np.asarray(pop_ref))


@pytest.mark.parametrize("w", [1, 127, 128, 129, 256])
def test_bitmap_intersect_word_blocking(w):
    """W on both sides of the 128-lane padding: the zero pad words must
    AND and popcount to nothing."""
    rng = np.random.default_rng(w)
    tables = tuple(jnp.asarray(rng.integers(0, 2**32, size=(16, w),
                                            dtype=np.uint32)) for _ in range(2))
    idxs = jnp.asarray(rng.integers(0, 16, size=(12, 2)).astype(np.int32))
    r_ref, pop_ref = ref.bitmap_intersect_ref(tables, idxs)
    r, pop = bitmap_intersect_pallas(tables, idxs)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r_ref))
    np.testing.assert_array_equal(np.asarray(pop), np.asarray(pop_ref))


@pytest.mark.parametrize("fused", [False, True])
def test_intersect_out_of_range_indices_clamp_like_jnp(fused):
    """Negative and too-large indices resolve as a jnp gather does
    (negative counts from the end, then clip to [0, S-1]); on the chip an
    unclamped row DMA would read outside the table."""
    rng = np.random.default_rng(4)
    tables = tuple(jnp.asarray(rng.integers(0, 2**32, size=(s, 3),
                                            dtype=np.uint32)) for s in (5, 9))
    bad = np.array([-1, -5, -9, -40, 0, 4, 5, 8, 9, 1000], np.int32)
    if fused:
        idx = jnp.asarray(np.stack([bad[::-1], bad], 1))
        rows = jnp.asarray(np.array([-1, 0, 3, 9, 10, 50, -20, 2, 7, 1],
                                    np.int32))
        args = (tables, idx, rows, jnp.asarray(bad))
        r_ref, pop_ref = ref.fused_expand_intersect_ref(*args, slots=(2, 0))
        r, pop = fused_expand_intersect_pallas(*args, slots=(2, 0))
    else:
        idxs = jnp.asarray(np.stack([bad, bad[::-1]], 1))
        r_ref, pop_ref = ref.bitmap_intersect_ref(tables, idxs)
        r, pop = bitmap_intersect_pallas(tables, idxs)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r_ref))
    np.testing.assert_array_equal(np.asarray(pop), np.asarray(pop_ref))


# ----------------------------------------------- fused expand + intersect
def _fused_case(k, t_rows, t_in, w, seed, *, fill=None, k0=None):
    """Synthetic (tables, idx, rows, bitpos, slots) for the fused kernel:
    k0 parent columns (default k-1) plus the bitpos slot, mixed slot map."""
    rng = np.random.default_rng(seed)
    k0 = max(k - 1, 1) if k0 is None else k0
    s_max = 33                                    # rows per table
    if fill is None:
        tables = tuple(
            jnp.asarray(rng.integers(0, 2**32, size=(s_max, w),
                                     dtype=np.uint32))
            for _ in range(k))
    else:                                         # all-zero / all-one edges
        tables = tuple(jnp.full((s_max, w), np.uint32(fill))
                       for _ in range(k))
    idx = jnp.asarray(rng.integers(0, s_max, size=(t_in, k0))
                      .astype(np.int32))
    rows = jnp.asarray(rng.integers(0, t_in, size=t_rows).astype(np.int32))
    bitpos = jnp.asarray(rng.integers(0, s_max, size=t_rows)
                         .astype(np.int32))
    slots = tuple(rng.permutation(k0 + 1)[:k].astype(int).tolist())
    return tables, idx, rows, bitpos, slots


@pytest.mark.parametrize("k0", [3, 8, 23])
@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("t_rows,w", [(1, 1), (16, 5), (33, 40)])
def test_fused_expand_intersect_width_sweep(k, t_rows, w, k0):
    """Fused expand+intersect+popcount vs the two-step oracle across word
    counts, row counts off the 8-row grid step, and parent tiles of
    several widths (the flattened idx stride the kernel resolves with)."""
    tables, idx, rows, bitpos, slots = _fused_case(k, t_rows, 24, w,
                                                   seed=k * 77 + t_rows + w,
                                                   k0=k0)
    r_ref, pop_ref = ref.fused_expand_intersect_ref(tables, idx, rows,
                                                    bitpos, slots=slots)
    r_pal, pop_pal = fused_expand_intersect_pallas(
        tables, idx, rows, bitpos, slots=slots)
    np.testing.assert_array_equal(np.asarray(r_pal), np.asarray(r_ref))
    np.testing.assert_array_equal(np.asarray(pop_pal), np.asarray(pop_ref))


@pytest.mark.parametrize("fill", [0x00000000, 0xFFFFFFFF])
def test_fused_expand_intersect_bitmap_edges(fill):
    """All-zero and all-one bitmaps: popcount must be exactly 0 / 32·W on
    every row regardless of the selection pattern."""
    tables, idx, rows, bitpos, slots = _fused_case(2, 16, 8, 7, seed=5,
                                                   fill=fill)
    r, pop = fused_expand_intersect_pallas(tables, idx, rows, bitpos,
                                           slots=slots)
    want = 0 if fill == 0 else 32 * 7
    np.testing.assert_array_equal(np.asarray(pop).ravel(),
                                  np.full(16, want))
    r_ref, _ = ref.fused_expand_intersect_ref(tables, idx, rows, bitpos,
                                              slots=slots)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r_ref))


def test_fused_expand_intersect_no_parent_columns():
    """K0 = 0 (parent tile has no index columns): every slot must be the
    bitpos slot and the dummy idx pad is never dereferenced."""
    rng = np.random.default_rng(9)
    tables = (jnp.asarray(rng.integers(0, 2**32, size=(20, 3),
                                       dtype=np.uint32)),)
    idx = jnp.zeros((6, 0), jnp.int32)
    rows = jnp.asarray(rng.integers(0, 6, size=10).astype(np.int32))
    bitpos = jnp.asarray(rng.integers(0, 20, size=10).astype(np.int32))
    r_ref, pop_ref = ref.fused_expand_intersect_ref(tables, idx, rows,
                                                    bitpos, slots=(0,))
    r, pop = fused_expand_intersect_pallas(tables, idx, rows, bitpos,
                                           slots=(0,))
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r_ref))
    np.testing.assert_array_equal(np.asarray(pop), np.asarray(pop_ref))


@pytest.mark.skipif(not ops.on_tpu(), reason="compiled Pallas needs a TPU")
@pytest.mark.parametrize("w", [1, 152, 660])
def test_fused_expand_intersect_compiled_matches_interpret(w):
    """On TPU the compiled kernel must agree with interpret mode (which the
    CPU sweeps above pin to the oracle)."""
    tables, idx, rows, bitpos, slots = _fused_case(2, 32, 16, w, seed=3)
    r_i, p_i = fused_expand_intersect_pallas(
        tables, idx, rows, bitpos, slots=slots, interpret=True)
    r_c, p_c = fused_expand_intersect_pallas(
        tables, idx, rows, bitpos, slots=slots, interpret=False)
    np.testing.assert_array_equal(np.asarray(r_c), np.asarray(r_i))
    np.testing.assert_array_equal(np.asarray(p_c), np.asarray(p_i))


def test_fused_ops_dispatch_and_two_step_reference():
    """ops.fused_expand_intersect(use_pallas=False) is the two-step
    make_intersect_fn reference over the materialized child columns —
    the kernel must match it bit-for-bit."""
    tables, idx, rows, bitpos, slots = _fused_case(3, 16, 8, 9, seed=11)
    # two-step reference: materialize child columns, then the existing
    # intersect path (jnp oracle of make_intersect_fn)
    cols = jnp.concatenate([idx[rows], bitpos[:, None]], axis=1)
    idxs = jnp.stack([cols[:, s] for s in slots], axis=1)
    two_step = ops.make_intersect_fn(use_pallas=False)
    r_ref, pop_ref = two_step(tables, idxs)
    for kw in (dict(use_pallas=False), dict(use_pallas=True, interpret=True)):
        r, pop = ops.fused_expand_intersect(tables, idx, rows, bitpos,
                                            slots=slots, **kw)
        np.testing.assert_array_equal(np.asarray(r), np.asarray(r_ref))
        np.testing.assert_array_equal(np.asarray(pop).ravel(),
                                      np.asarray(pop_ref).ravel())


def test_engine_with_fused_intersect_matches_oracle():
    """End-to-end: intersect="fused" routes the boundary expansion through
    the fused kernel with counts identical to the jnp engine and the
    oracle."""
    from repro.core import random_walk_query, synthetic_labeled_graph
    from repro.core.engine import vector_match
    from repro.core.oracle import nx_count

    data = synthetic_labeled_graph(60, 5.0, 3, seed=2, power_law=False)
    query = random_walk_query(data, 5, seed=12)
    expect = nx_count(query, data)
    res = vector_match(query, data, limit=10**9, tile_rows=64,
                       intersect="fused")
    assert res.count == expect


def test_engine_with_pallas_intersect_matches_oracle():
    """End-to-end: vectorized engine with the Pallas kernel plugged in."""
    from repro.core import random_walk_query, synthetic_labeled_graph
    from repro.core.engine import vector_match
    from repro.core.oracle import nx_count

    data = synthetic_labeled_graph(60, 5.0, 3, seed=2, power_law=False)
    query = random_walk_query(data, 5, seed=12)
    expect = nx_count(query, data)
    fn = ops.make_intersect_fn(use_pallas=True, interpret=True)
    res = vector_match(query, data, limit=10**9, tile_rows=64, intersect_fn=fn)
    assert res.count == expect


# ------------------------------------------------------------ flash_decode
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,hkv,s,d", [
    (1, 4, 4, 32, 16), (2, 8, 2, 64, 32), (3, 12, 2, 100, 64), (2, 6, 1, 17, 8),
])
def test_flash_decode_sweep(b, h, hkv, s, d, dtype):
    rng = np.random.default_rng(b * 100 + h + s)
    q = jnp.asarray(rng.standard_normal((b, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), dtype)
    lengths = jnp.asarray(rng.integers(1, s + 1, size=(b,)).astype(np.int32))
    want = ref.flash_decode_ref(q, k, v, lengths)
    got = flash_decode_pallas(q, k, v, lengths, block_s=16)
    rtol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=rtol,
                               atol=rtol)


def test_flash_decode_full_length_default():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((2, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 48, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 48, 2, 16)), jnp.float32)
    want = ref.flash_decode_ref(q, k, v)
    got = flash_decode_pallas(q, k, v, block_s=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_ops_dispatch():
    rng = np.random.default_rng(1)
    tables = (jnp.asarray(rng.integers(0, 2**32, size=(8, 2), dtype=np.uint32)),)
    idxs = jnp.asarray(rng.integers(0, 8, size=(4, 1)).astype(np.int32))
    r0, p0 = ops.bitmap_intersect(tables, idxs, use_pallas=False)
    r1, p1 = ops.bitmap_intersect(tables, idxs, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(r0), np.asarray(r1))
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
