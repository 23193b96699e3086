"""int8-compressed gradient all-reduce over the DCN ('pod') axis.

At 2+ pods the data-center network between pods is the thin pipe; the
standard trick is to compress the cross-pod gradient reduction.  We
implement an int8 blockwise-quantized psum with shard_map:

    q8(g) -> psum(int32 accum of q, fp32 psum of scales is NOT valid;
    instead each shard contributes q*s locally dequantized after an
    all_gather of the (q, s) pairs over the small pod axis)

For a pod axis of size 2 (assignment mesh) the all_gather of quantized
payloads moves 4x fewer bytes than an fp32 ring all-reduce and 2x fewer
than bf16, at ~0.4% relative error (see tests/test_compress.py).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

QBLOCK = 256


def _q8_flat(x):
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % QBLOCK
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    blocks = flat.reshape(-1, QBLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale, pad


def compressed_psum_mean(x: jax.Array, axis_name: str) -> jax.Array:
    """Mean-reduce ``x`` across ``axis_name`` with int8 payloads.

    Must be called inside shard_map with ``axis_name`` in scope.  Each
    participant quantizes its contribution, all-gathers the (q, scales)
    pair, dequantizes and averages locally.
    """
    q, s, pad = _q8_flat(x)
    qs = jax.lax.all_gather(q, axis_name)        # (n, nblocks, QBLOCK) int8
    ss = jax.lax.all_gather(s, axis_name)        # (n, nblocks, 1) fp32
    deq = (qs.astype(jnp.float32) * ss).mean(axis=0).reshape(-1)
    n = x.size
    return deq[:n].reshape(x.shape).astype(x.dtype)


def compressed_allreduce_stacked(mesh, x: jax.Array, axis_name: str = "pod"
                                 ) -> jax.Array:
    """Mean-reduce per-pod contributions with int8 payloads.

    ``x`` has a leading dim equal to the pod-axis size (one local gradient
    per pod), sharded over ``axis_name``.  Returns the mean contribution
    (shape ``x.shape[1:]``), numerically within q8 error of ``x.mean(0)``.
    """
    def per_shard(xs):                       # xs: (1, ...) local slice
        return compressed_psum_mean(xs[0], axis_name)[None]

    nd = x.ndim
    spec = P(axis_name, *([None] * (nd - 1)))
    f = jax.shard_map(per_shard, mesh=mesh, in_specs=spec, out_specs=spec,
                      check_vma=False)
    return f(x)[0]
