"""Compile the device path's kernels for a described TPU v5e, no chip needed.

Interpret mode runs a Pallas kernel on the CPU without asking whether the
chip's compiler accepts its blocks, so the CPU sweeps in test_kernels.py
cannot see a layout the chip refuses. Here both intersect kernels and the
int64 leaf reductions are lowered and compiled by the TPU compiler for one
chip of a described `v5e:2x2` at the shapes of the full-scale Table-2
queries: table widths W of 1 to 660 words, k of 1 to 3 backward
neighbours, 4,861 table rows, 256-row tiles and parent tiles of 23 index
columns. Nothing runs, so results are checked by the interpret-mode tests.

The topology is described inside a module fixture only: describing it
loads the TPU library, which one process at a time may hold.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.scheduler import make_leaf_reduce, make_leaf_reduce_batched
from repro.kernels.bitmap_intersect import (KERNEL_NAME,
                                            bitmap_intersect_pallas,
                                            fused_expand_intersect_pallas)

TABLE_ROWS = 4861          # largest single table of the full-scale queries
TILE_ROWS = 256            # MatchOptions.tile_rows default
PARENT_COLS = 23           # index columns of a 24-vertex query's parent tile
WIDTHS = (1, 124, 152, 660)
KS = (1, 2, 3)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described chip is written to the cache but
    cannot be read back without that chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                           # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("w", WIDTHS)
def test_bitmap_intersect_compiles_for_v5e(one_chip, w, k):
    tables = tuple(_spec(one_chip, (TABLE_ROWS - j, w), jnp.uint32)
                   for j in range(k))
    idxs = _spec(one_chip, (TILE_ROWS, k), jnp.int32)
    compiled = bitmap_intersect_pallas.lower(
        tables, idxs, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("x64", [False, True])
@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("w", WIDTHS)
def test_fused_expand_intersect_compiles_for_v5e(one_chip, w, k, x64):
    """The fused kernel runs inside the scheduler's supersteps, which are
    traced under x64 for the leaf reduction: compile it both ways."""
    tables = tuple(_spec(one_chip, (TABLE_ROWS - j, w), jnp.uint32)
                   for j in range(k))
    idx = _spec(one_chip, (TILE_ROWS, PARENT_COLS), jnp.int32)
    sel = _spec(one_chip, (TILE_ROWS,), jnp.int32)
    # the bitpos slot first, then parent columns spread over the tile
    slots = (PARENT_COLS,) + tuple(range(0, PARENT_COLS, 9))[:k - 1]
    with jax.enable_x64(x64):
        compiled = fused_expand_intersect_pallas.lower(
            tables, idx, sel, sel, slots=slots, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_expand_intersect_without_parent_columns_compiles(one_chip):
    tables = (_spec(one_chip, (TABLE_ROWS, 152), jnp.uint32),)
    idx = _spec(one_chip, (TILE_ROWS, 0), jnp.int32)
    sel = _spec(one_chip, (TILE_ROWS,), jnp.int32)
    fused_expand_intersect_pallas.lower(tables, idx, sel, sel, slots=(0,),
                                        interpret=False).compile()


@pytest.mark.parametrize("fused", [False, True])
def test_kernel_carries_its_name_for_v5e(one_chip, fused):
    """Both entry points lower to one custom call named `cemr_gather_and`,
    the name its op carries in a device trace."""
    tables = tuple(_spec(one_chip, (TABLE_ROWS - j, 124), jnp.uint32)
                   for j in range(2))
    if fused:
        idx = _spec(one_chip, (TILE_ROWS, PARENT_COLS), jnp.int32)
        sel = _spec(one_chip, (TILE_ROWS,), jnp.int32)
        lowered = fused_expand_intersect_pallas.lower(
            tables, idx, sel, sel, slots=(PARENT_COLS, 0), interpret=False)
    else:
        idxs = _spec(one_chip, (TILE_ROWS, 2), jnp.int32)
        lowered = bitmap_intersect_pallas.lower(tables, idxs,
                                                interpret=False)
    calls = [line for line in lowered.compile().as_text().splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert re.match(rf"\s*%{KERNEL_NAME}(\.\d+)? = ", calls[0]), calls[0]


@pytest.mark.parametrize("batched", [False, True])
def test_leaf_reduce_compiles_for_v5e(one_chip, batched):
    """int64 counts and the float64 overflow bound, as the leaf supersteps
    trace them: three singles, a pair group and a triple group."""
    singles, groups = [0, 1, 2], [[3, 4], [5, 6, 7]]
    n_terms = len(singles) + 3 + 7
    terms = _spec(one_chip, (TILE_ROWS, n_terms), jnp.int32)
    alive = _spec(one_chip, (TILE_ROWS,), jnp.bool_)
    with jax.enable_x64(True):
        if batched:
            red = make_leaf_reduce_batched(singles, groups, n_queries=32)
            qid = _spec(one_chip, (TILE_ROWS,), jnp.int32)
            compiled = jax.jit(red).lower(terms, alive, qid).compile()
        else:
            red = make_leaf_reduce(singles, groups)
            compiled = jax.jit(red).lower(terms, alive).compile()
    assert compiled.memory_analysis() is not None
