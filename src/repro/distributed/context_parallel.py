"""Explicit sequence-sharded decode attention (shard_map + LSE combine).

The baseline decode path leaves the cache-sequence partitioning to GSPMD
(policy rules shard the KV cache's S dim over `model` and let SPMD insert
the reductions). This module is the *explicit* formulation — each model
shard runs flash-decode over its local cache block and the partial
(m, l, o) triplets combine with the log-sum-exp identity:

    o = Σ_i exp(m_i − m*) · l_i · o_i  /  Σ_i exp(m_i − m*) · l_i

Two reasons to have it explicit: (a) the collectives are exactly two tiny
psums of (B, H[, D]) — independent of S — which pins the long_500k
collective term to its floor; (b) on real hardware it composes with the
flash_decode Pallas kernel per shard (the kernel streams only the local
cache block). Validated against the single-device oracle in
tests/test_distributed_exec.py / test_context_parallel.py.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref as kref

__all__ = ["sharded_decode_attention"]


def _local_partials(q, k, v, lengths, shard_offset, scale):
    """Per-shard flash-decode partials. q (B,H,D); k/v (B,S_loc,N,D);
    positions [shard_offset, shard_offset + S_loc) are valid if < lengths.
    Returns (m (B,H), l (B,H), o (B,H,D)) with o un-normalized."""
    b, h, d = q.shape
    s_loc, n = k.shape[1], k.shape[2]
    g = h // n
    qg = q.reshape(b, n, g, d).astype(jnp.float32)
    scores = jnp.einsum("bngd,bsnd->bngs", qg,
                        k.astype(jnp.float32)) * scale
    pos = shard_offset + jnp.arange(s_loc)
    valid = pos[None, None, None, :] < lengths[:, None, None, None]
    scores = jnp.where(valid, scores, -jnp.inf)
    m = scores.max(-1)                                  # (B,N,G)
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(valid, p, 0.0)                        # m=-inf rows → 0
    l = p.sum(-1)
    o = jnp.einsum("bngs,bsnd->bngd", p, v.astype(jnp.float32))
    safe_m = jnp.where(jnp.isfinite(m), m, -1e30)
    return (safe_m.reshape(b, h), l.reshape(b, h), o.reshape(b, h, d))


def sharded_decode_attention(q, k, v, lengths, mesh, *, axis: str = "model"):
    """q (B,H,D) replicated over `axis`; k/v (B,S,N,D) sharded on S over
    `axis`; lengths (B,). Returns (B,H,D), numerically equal to full
    attention over the whole cache."""
    b, h, d = q.shape
    s = k.shape[1]
    n_shards = mesh.shape[axis]
    s_loc = s // n_shards
    scale = 1.0 / math.sqrt(d)

    def body(q, k, v, lengths):
        idx = jax.lax.axis_index(axis)
        m, l, o = _local_partials(q, k, v, lengths, idx * s_loc, scale)
        m_star = jax.lax.pmax(m, axis)                  # (B,H)
        w = jnp.exp(m - m_star) * l                     # (B,H)
        denom = jax.lax.psum(w, axis)
        numer = jax.lax.psum(jnp.exp(m - m_star)[..., None] * o, axis)
        return (numer / jnp.maximum(denom, 1e-30)[..., None]).astype(q.dtype)

    rest = tuple(a for a in mesh.axis_names if a != axis)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, axis, None, None), P(None, axis, None, None),
                  P()),
        out_specs=P(), check_vma=False)(q, k, v, lengths)
