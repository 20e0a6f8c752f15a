"""Gradient compression for the DP all-reduce: int8 quantization with
error feedback (residual carry), expressed as a shard_map collective so it
composes with pjit training.

At 1000-node scale the DP gradient all-reduce is the dominant fixed
collective; int8 + EF cuts its bytes 4× with negligible quality loss
(1-bit/8-bit SGD literature). Used opt-in by the trainer (compress_grads=True).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["quantize_int8", "dequantize_int8", "compressed_psum",
           "ef_compress_update"]


def quantize_int8(x):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


def ef_compress_update(grad, residual):
    """Error-feedback compression of one gradient leaf: returns the
    dequantized (communicated) gradient and the new residual."""
    target = grad.astype(jnp.float32) + residual
    q, scale = quantize_int8(target)
    deq = dequantize_int8(q, scale)
    return deq, target - deq


def compressed_psum(x, axis_name: str):
    """int8 all-reduce: quantize locally, all-gather the (q, scale) pairs,
    dequantize+sum — 4× fewer interconnect bytes than f32 psum for the
    payload. (all_gather of int8 + per-shard scales; the sum happens locally
    so precision loss is one quantization, not log(n).)"""
    q, scale = quantize_int8(x)
    qs = jax.lax.all_gather(q, axis_name)            # (n, ...)
    ss = jax.lax.all_gather(scale, axis_name)        # (n,)
    return jnp.tensordot(ss, qs.astype(jnp.float32), axes=(0, 0))


def compressed_allreduce_tree(grads, mesh, axes=("data",)):
    """Apply compressed_psum leafwise over a replicated-gradient pytree via
    shard_map (used when gradients are data-parallel partial sums)."""
    axis = axes[0]

    def one(g):
        def f(gl):
            return compressed_psum(gl, axis)
        return jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)(g)

    return jax.tree.map(one, grads)
