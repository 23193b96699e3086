"""Blocked (flash) causal attention — Pallas TPU kernel.

Beyond-paper perf layer: the jnp chunked-attention baseline materializes a
(q_chunk, kv_chunk) logits block in HBM-visible buffers between scan steps;
this kernel keeps the whole online-softmax state in VMEM.

Grid: (batch*heads, Sq / BQ).  Each step loops over KV blocks up to the
causal frontier with ``jax.lax.fori_loop``, carrying (acc, m, l) in VMEM.
Block sizes: BQ x BK = 512 x 512 on hd<=128 keeps q/k/v/acc tiles
(4 x 512 x 128 x 4B = 1 MiB) comfortably inside the ~16 MiB VMEM budget.

The ops.py wrapper handles GQA by broadcasting KV heads and flattens
(B, H) into the leading grid dim.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

DEFAULT_BQ = 512
DEFAULT_BK = 512
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, sm_scale, bq, bk, causal):
    # q_ref: (bq, hd); k_ref/v_ref: (Skv, hd) full rows for this (b,h)
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * sm_scale
    Skv = k_ref.shape[0]
    hd = q.shape[-1]

    n_kv = Skv // bk
    if causal:
        # only blocks whose start <= last q position
        last_q = (qi + 1) * bq - 1
        n_live = jnp.minimum(n_kv, (last_q // bk) + 1)
    else:
        n_live = n_kv

    def body(ki, carry):
        acc, m, l = carry
        k = k_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)
        v = v_ref[pl.ds(ki * bk, bk), :].astype(jnp.float32)
        s = q @ k.T                                       # (bq, bk)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=1, keepdims=True)
        acc_new = acc * alpha + p @ v
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, hd), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_live, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,          # (BH, Sq, hd)
    k: jax.Array,          # (BH, Skv, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: Optional[bool] = None,
) -> jax.Array:
    BH, Sq, hd = q.shape
    Skv = k.shape[1]
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, "seq must divide block size"
    sm_scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, bq=bq, bk=bk, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(BH, Sq // bq),
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, Skv, hd), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, Skv, hd), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
