"""Pallas TPU kernel: batched candidate-bitmap intersection (+ fused popcount).

The CEMR enumeration hot loop (Algorithm 3 line 5 / engine._compute_fn):

    R[t, :] = AND_j  table_j[idx[t, j], :]        (k gathered rows per tile row)
    pop[t]  = popcount(R[t, :])

Layout: each (S_j, W) uint32 table is zero-padded to W_pad, the next multiple
of 128 lanes, and viewed as (S_j, 1, W_pad). XLA lays that view out in
(1, 128) tiles, so one table row is one contiguous, tile-aligned DMA. The
tables stay in HBM and the row indices are scalar-prefetched into SMEM. Grid
step i owns `ROWS_PER_STEP` frontier rows: it starts one row DMA per (row,
table) into VMEM scratch, waits for all of them, ANDs the k rows and writes an
(8, W_pad) block of R plus its (8, 1) popcount column. Zero padding words AND
and popcount to nothing, so the result is cut back to (T, W) unchanged.

A BlockSpec gather of single (1, W) rows, the obvious Pallas form, does not
lower for the chip: Mosaic needs the last two block dimensions divisible by
(8, 128) or equal to the array's, and a one-row slice of an (8, 128)-tiled
table is refused. Interpret mode accepts it, so only a compile for the chip
(tests/test_tpu_compile.py) shows the difference.

Indices follow jnp gather semantics: a negative index counts from the end,
and whatever is still out of range clamps to [0, S-1]. The kernel is then
bit-identical to `kernels/ref.py` for every index, including the clamped
selections the engine leaves in dead rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bitmap_intersect_pallas", "fused_expand_intersect_pallas",
           "pad_copy_bytes", "ROWS_PER_STEP", "LANES", "KERNEL_NAME"]

ROWS_PER_STEP = 8     # frontier rows per grid step: one 8-sublane output tile
LANES = 128           # W is padded to a multiple of the lane width
KERNEL_NAME = "cemr_gather_and"   # the kernel's name in HLO and device traces


def pad_copy_bytes(shapes) -> int:
    """Bytes of the zero-padded table copies one kernel call makes for
    tables of these (S, W) shapes: S * W_pad * 4 for each table whose W is
    not a multiple of LANES (`jnp.pad` writes the whole padded table), 0
    for the others."""
    total = 0
    for s, w in shapes:
        w_pad = pl.cdiv(w, LANES) * LANES
        if w_pad > w:
            total += s * w_pad * 4
    return total


def _clamp_index(i, n: int):
    """jnp gather semantics for one scalar index into n rows."""
    i = jnp.where(i < 0, i + n, i)
    return jnp.minimum(jnp.maximum(i, 0), n - 1)


def _gather_and_kernel(resolve, n_rows: tuple, *refs):
    """Shared body of both entry points. `resolve(prefetch_refs, t, j)`
    returns the (unclamped) row of table j for frontier row t."""
    k = len(n_rows)
    n_pf = len(refs) - k - 4
    pf, tables = refs[:n_pf], refs[n_pf:n_pf + k]
    r_ref, pop_ref, buf, sem = refs[n_pf + k:]
    base = pl.program_id(0) * ROWS_PER_STEP

    def row_copy(t, j):
        row = _clamp_index(resolve(pf, base + t, j), n_rows[j])
        return pltpu.make_async_copy(tables[j].at[pl.ds(row, 1)],
                                     buf.at[j, pl.ds(t, 1)], sem.at[0])

    copies = [row_copy(t, j) for t in range(ROWS_PER_STEP) for j in range(k)]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()
    r = buf[0]
    for j in range(1, k):
        r = r & buf[j]
    r = r.reshape(ROWS_PER_STEP, r.shape[-1])
    r_ref[...] = r
    pop_ref[...] = jax.lax.population_count(r).astype(jnp.int32).sum(
        axis=1, keepdims=True, dtype=jnp.int32)


def _gather_and(tables: tuple, prefetch: tuple, t_rows: int, resolve,
                interpret: bool):
    """Run the kernel over `t_rows` frontier rows; every prefetch array
    must already cover `_pad_rows(t_rows)` rows where `resolve` reads it."""
    k = len(tables)
    w = tables[0].shape[1]
    assert all(tbl.shape[1] == w for tbl in tables)
    w_pad = pl.cdiv(w, LANES) * LANES
    t_pad = _pad_rows(t_rows)
    rows3 = tuple(jnp.pad(tbl, ((0, 0), (0, w_pad - w)))[:, None, :]
                  for tbl in tables)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=(t_pad // ROWS_PER_STEP,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)] * k,
        out_specs=[pl.BlockSpec((ROWS_PER_STEP, w_pad), lambda i, *_: (i, 0)),
                   pl.BlockSpec((ROWS_PER_STEP, 1), lambda i, *_: (i, 0))],
        scratch_shapes=[pltpu.VMEM((k, ROWS_PER_STEP, 1, w_pad), jnp.uint32),
                        pltpu.SemaphoreType.DMA((1,))])
    kernel = functools.partial(_gather_and_kernel, resolve,
                               tuple(tbl.shape[0] for tbl in tables))
    # the kernel is all 32-bit; traced under the caller's x64 (the leaf
    # supersteps) its scalar indices would become i64, which Mosaic refuses
    with jax.enable_x64(False), jax.named_scope(KERNEL_NAME):
        r, pop = pl.pallas_call(
            kernel, grid_spec=gs,
            out_shape=(jax.ShapeDtypeStruct((t_pad, w_pad), jnp.uint32),
                       jax.ShapeDtypeStruct((t_pad, 1), jnp.int32)),
            interpret=interpret, name=KERNEL_NAME)(*prefetch, *rows3)
    return r[:t_rows, :w], pop[:t_rows]


def _pad_rows(t_rows: int) -> int:
    return pl.cdiv(t_rows, ROWS_PER_STEP) * ROWS_PER_STEP


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmap_intersect_pallas(tables: tuple, idxs: jnp.ndarray, *,
                            interpret: bool = True):
    """AND k gathered bitmap rows per frontier row.

    tables: tuple of (S_j, W) uint32 arrays (one per backward neighbor)
    idxs:   (T, k) int32 row indices into each table
    Returns (R (T, W) uint32, pop (T, 1) int32).
    """
    t_rows, k = idxs.shape
    assert k == len(tables)
    flat = jnp.pad(idxs.astype(jnp.int32),
                   ((0, _pad_rows(t_rows) - t_rows), (0, 0))).reshape(-1)

    def resolve(pf, t, j):
        return pf[0][t * k + j]

    return _gather_and(tuple(tables), (flat,), t_rows, resolve, interpret)


@functools.partial(jax.jit, static_argnames=("slots", "interpret"))
def fused_expand_intersect_pallas(tables: tuple, idx: jnp.ndarray,
                                  rows: jnp.ndarray, bitpos: jnp.ndarray, *,
                                  slots: tuple, interpret: bool = True):
    """Fused frontier expansion + k-way bitmap AND + popcount.

    Consumes the bit selection from `core.bitops.expand_select` directly:
    instead of first materializing the child tile's gathered index columns
    (``concat(idx[rows], bitpos)``), each row DMA resolves its table row
    through the scalar-prefetched (rows, bitpos, idx) triple — slot
    ``s < K0`` reads parent column ``idx[rows[t], s]``, slot ``s == K0``
    reads the freshly selected bit position ``bitpos[t]``. The AND and
    per-row popcount are the body of `bitmap_intersect_pallas`.

    tables: k × (S_j, W) uint32 adjacency bitmaps
    idx:    (Tin, K0) int32 parent tile index columns (K0 may be 0)
    rows:   (T,) int32 source row of each selected bit
    bitpos: (T,) int32 bit position (candidate index) of each selected bit
    slots:  k static ints in [0, K0], one per table
    Returns (R (T, W) uint32, pop (T, 1) int32). Invalid / dead rows are
    NOT masked here: (R, pop) must stay a pure function of the key columns
    so CER cache entries built from it remain sound (clamped selections
    are valid keys); the engine's finish_compute masks downstream.
    """
    assert len(slots) == len(tables)
    t_rows = rows.shape[0]
    t_in, k0 = idx.shape
    pad = _pad_rows(t_rows) - t_rows
    rows_p = jnp.pad(rows.astype(jnp.int32), (0, pad))
    bitpos_p = jnp.pad(bitpos.astype(jnp.int32), (0, pad))
    # K0 == 0: every slot is the bitpos slot and idx is never read, but the
    # prefetch operand must be non-empty
    flat_idx = (idx.astype(jnp.int32).reshape(-1) if k0
                else jnp.zeros((1,), jnp.int32))

    def resolve(pf, t, j):
        rows_ref, bitpos_ref, idx_ref = pf
        if slots[j] == k0:
            return bitpos_ref[t]
        return idx_ref[_clamp_index(rows_ref[t], t_in) * k0 + slots[j]]

    return _gather_and(tuple(tables), (rows_p, bitpos_p, flat_idx), t_rows,
                       resolve, interpret)
