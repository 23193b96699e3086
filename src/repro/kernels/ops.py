"""jit'd public wrappers around the Pallas kernels.

``interpret=None`` (the default) compiles every kernel on an accelerator
and interprets it on the CPU backend (see :func:`repro.kernels.
resolve_interpret`); pass a bool only to override that.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import flash_attention as _fa
from . import preprocess as _pre
from . import quantize as _q

BLOCK = _q.BLOCK


# -- quantize ----------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize(x: jax.Array, *, interpret: Optional[bool] = None):
    """Any-shape tensor -> (q (n,BLOCK) int8, scales (n,1) f32, meta).

    meta = (shape, pad) needed by :func:`dequantize`."""
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % BLOCK
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    blocks = flat.reshape(-1, BLOCK)
    q, s = _q.quantize_blocks(blocks, interpret=interpret)
    return q, s


def dequantize(q: jax.Array, s: jax.Array, shape, dtype=jnp.float32,
               *, interpret: Optional[bool] = None) -> jax.Array:
    flat = _q.dequantize_blocks(q, s, interpret=interpret).reshape(-1)
    n = 1
    for d in shape:
        n *= d
    return flat[:n].reshape(shape).astype(dtype)


# -- preprocess -----------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("interpret",))
def normalize_images_nhwc(x: jax.Array, mean: jax.Array, std: jax.Array,
                          *, interpret: Optional[bool] = None) -> jax.Array:
    """x: (B, H, W, C) uint8 -> normalized (B, H, W, C) f32 (fused kernel)."""
    B, H, W, C = x.shape
    xc = jnp.transpose(x, (0, 3, 1, 2)).reshape(B, C, H * W)
    out = _pre.normalize_images(xc, mean, std, interpret=interpret)
    return jnp.transpose(out.reshape(B, C, H, W), (0, 2, 3, 1))


# (B, H, W, C) u8/u16/f32 -> (B, out_h, out_w, C) f32 in [0,1]; the kernel's
# own entry point is already jitted (the input pipeline calls it directly)
resize_convert_nhwc = _pre.resize_convert_images


# -- flash attention ---------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array,
                         *, causal: bool = True, bq: int = _fa.DEFAULT_BQ,
                         bk: int = _fa.DEFAULT_BK,
                         interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Sq, H, hd), k/v: (B, Skv, Hkv, hd) GQA -> (B, Sq, H, hd)."""
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    group = H // Hkv
    # broadcast KV heads for GQA, flatten (B, H)
    kb = jnp.repeat(k, group, axis=2)
    vb = jnp.repeat(v, group, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = kb.transpose(0, 2, 1, 3).reshape(B * H, Skv, hd)
    vf = vb.transpose(0, 2, 1, 3).reshape(B * H, Skv, hd)
    o = _fa.flash_attention(qf, kf, vf, causal=causal, bq=bq, bk=bk,
                            interpret=interpret)
    return o.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
