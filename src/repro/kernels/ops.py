"""jit'd dispatch wrappers around the Pallas kernels.

Every op has a pure-jnp oracle in ref.py. Dispatch is backend-aware:
`use_pallas=True` routes to the kernel, and `interpret=None` (the default)
resolves automatically — compiled on TPU, interpret-mode elsewhere — so the
same call site is the fast path on TPU and a correctness path on CPU. The
vectorized CEMR engine and the LM serve path consume these through
`make_intersect_fn` / `decode_attention`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .bitmap_intersect import (bitmap_intersect_pallas,
                               fused_expand_intersect_pallas)
from .flash_decode import flash_decode_pallas

__all__ = ["bitmap_intersect", "flash_decode", "fused_expand_intersect",
           "make_intersect_fn", "make_fused_expand_intersect_fn",
           "decode_attention",
           "default_interpret", "on_tpu"]


def on_tpu() -> bool:
    """True when the default JAX backend is a TPU."""
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Pallas interpret-mode default: compiled on TPU, interpreted on CPU/GPU
    hosts (where Mosaic cannot lower the kernel)."""
    return not on_tpu()


def bitmap_intersect(tables, idxs, *, use_pallas: bool = False,
                     interpret: bool | None = None):
    tables = tuple(tables)
    if use_pallas:
        if interpret is None:
            interpret = default_interpret()
        return bitmap_intersect_pallas(tables, idxs, interpret=interpret)
    return ref.bitmap_intersect_ref(tables, idxs)


def flash_decode(q, k, v, lengths=None, *, use_pallas: bool = False,
                 interpret: bool | None = None, block_s: int = 128):
    if use_pallas:
        if interpret is None:
            interpret = default_interpret()
        return flash_decode_pallas(q, k, v, lengths, block_s=block_s,
                                   interpret=interpret)
    return ref.flash_decode_ref(q, k, v, lengths)


def fused_expand_intersect(tables, idx, rows, bitpos, *, slots,
                           use_pallas: bool = True,
                           interpret: bool | None = None):
    """Fused frontier expansion + intersection + popcount (or its two-step
    jnp oracle)."""
    tables = tuple(tables)
    slots = tuple(slots)
    if not use_pallas:
        return ref.fused_expand_intersect_ref(tables, idx, rows, bitpos,
                                              slots=slots)
    if interpret is None:
        interpret = default_interpret()
    return fused_expand_intersect_pallas(tables, idx, rows, bitpos,
                                         slots=slots, interpret=interpret)


def make_fused_expand_intersect_fn(*, use_pallas: bool = True,
                                   interpret: bool | None = None):
    """Adapter for core.engine._make_expand_fused: takes the backward-pair
    tables, parent index columns, the (rows, bitpos) bit selection and the
    static slot map; returns ``(R, pop)`` with pop flattened to (T,)."""

    def fn(tables, idx, rows, bitpos, slots):
        r, pop = fused_expand_intersect(tables, idx, rows, bitpos,
                                        slots=tuple(slots),
                                        use_pallas=use_pallas,
                                        interpret=interpret)
        return r, pop.reshape(-1)

    return fn


def make_intersect_fn(*, use_pallas: bool = True, interpret: bool | None = None):
    """Adapter for core.engine.VectorEngine(intersect_fn=...): takes the list
    of gathered tables + (T, k) indices and returns ``(R, pop)`` — the ANDed
    bitmap *and* the kernel's fused per-row popcount ((T,) int32), so the
    engine's contained-vertex prune never re-reduces R. `fn.pallas` says
    whether it calls the Pallas kernel (the engine counts the kernel's
    padded table copies by it)."""

    def fn(tables, idxs):
        r, pop = bitmap_intersect(tables, idxs, use_pallas=use_pallas,
                                  interpret=interpret)
        return r, pop.reshape(-1)

    fn.pallas = use_pallas
    return fn


def decode_attention(q, k, v, lengths=None, *, use_pallas: bool = False,
                     interpret: bool | None = None):
    """(B, H, D) single-token attention over a (B, S, Hkv, D) KV cache."""
    return flash_decode(q, k, v, lengths, use_pallas=use_pallas,
                        interpret=interpret)
