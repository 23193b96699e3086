"""Blockwise int8 quantize/dequantize — Pallas TPU kernel.

The transform behind three framework features: int8 optimizer states,
int8 checkpoint payloads (smaller bursts through the burst buffer), and the
compressed DCN gradient all-reduce.

Layout: values are viewed as (n_blocks, BLOCK) with BLOCK=256 lanes (two
128-lane registers), absmax-scaled per block to int8:

    scale = absmax(block) / 127 ;  q = round(x / scale)

Tiling: each grid step processes a (ROWS_PER_TILE, 256) VMEM tile — 8
sublanes x 256 lanes of fp32 in, int8 out + (ROWS_PER_TILE, 1) scales.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

BLOCK = 256
ROWS_PER_TILE = 256


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                 # (rows, BLOCK)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax == 0, 1.0, absmax / 127.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    q_ref[...] = q
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def quantize_blocks(x: jax.Array, *, interpret: Optional[bool] = None):
    """x: (n_blocks, BLOCK) fp32/bf16 -> (q int8, scales fp32 (n_blocks,1))."""
    n, b = x.shape
    assert b == BLOCK, f"expected block dim {BLOCK}, got {b}"
    rows = min(ROWS_PER_TILE, n)
    grid = (pl.cdiv(n, rows),)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, BLOCK), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(x)


def dequantize_blocks(q: jax.Array, s: jax.Array, *,
                      interpret: Optional[bool] = None):
    """(q int8 (n,BLOCK), scales (n,1)) -> fp32 (n, BLOCK)."""
    n, b = q.shape
    assert b == BLOCK
    rows = min(ROWS_PER_TILE, n)
    grid = (pl.cdiv(n, rows),)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, BLOCK), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(q, s)
