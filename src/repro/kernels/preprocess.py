"""Fused image preprocess — Pallas TPU kernels (input-pipeline hot spot).

The paper's mapped function ends with convert_image_dtype + normalization on
the CPU.  On a TPU pod the natural split (DESIGN.md hardware-adaptation) is:
host decodes, device does the arithmetic.  Two kernels:

* :func:`normalize_images` fuses uint8->f32 cast, [0,1] scaling, and
  per-channel (x - mean)/std in one VMEM pass.
* :func:`resize_convert_images` fuses bilinear resize AND dtype conversion
  for a whole uniform-size batch: resize is expressed as two small
  interpolation matmuls (``Ry @ X @ Rx^T``), which maps onto the MXU
  instead of the gather units, and the [0,1] conversion scale is folded
  into ``Ry`` so it costs nothing.  :func:`resize_convert` dispatches
  between this kernel and the batched numpy LUT-gather fallback
  (:func:`repro.core.records.resize_batch`) on CPU-only hosts.

TPU layout: NHWC with C=3 would put 3 values on the 128-wide lane axis,
which Mosaic cannot reshape for a matmul.  Normalize moves channels to the
sublane dim, (B, C, H*W): each grid step handles one image's (C, PIX_TILE)
tile, and mean/std live in small (C, 1) refs.  Resize works on per-channel
planes, (B*C, H, W): each grid step is two plain 2-D matmuls with W on the
lanes.  Mosaic has no direct uint8 -> float32 cast, so integer pixels widen
through int32 first.
"""
from __future__ import annotations

import functools
from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import resolve_interpret

PIX_TILE = 2048


def _to_f32(x: jax.Array) -> jax.Array:
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(jnp.int32)
    return x.astype(jnp.float32)


def _normalize_kernel(x_ref, mean_ref, std_ref, o_ref):
    x = _to_f32(x_ref[...]) * (1.0 / 255.0)              # (1, C, T)
    mean = mean_ref[...][None, :, :]                     # (1, C, 1)
    std = std_ref[...][None, :, :]
    o_ref[...] = (x - mean) / std


def normalize_images(x: jax.Array, mean: jax.Array, std: jax.Array,
                     *, interpret: Optional[bool] = None) -> jax.Array:
    """x: (B, C, P) uint8, mean/std: (C,) -> (B, C, P) float32."""
    B, C, P = x.shape
    tile = min(PIX_TILE, P)
    grid = (B, pl.cdiv(P, tile))
    return pl.pallas_call(
        _normalize_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, C, tile), lambda b, i: (b, 0, i)),
            pl.BlockSpec((C, 1), lambda b, i: (0, 0)),
            pl.BlockSpec((C, 1), lambda b, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C, tile), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((B, C, P), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x, mean.reshape(C, 1), std.reshape(C, 1))


# ---------------------------------------------------------------------------
# Batched bilinear resize + dtype convert
# ---------------------------------------------------------------------------
from ..core.records import CONVERT_SCALE as _CONVERT_SCALE  # noqa: E402

# f32 matmuls at full precision: the default single bf16 pass would round
# the interpolation weights far outside the numpy path's tolerance
_HIGHEST = jax.lax.Precision.HIGHEST


@lru_cache(maxsize=64)
def _interp_matrix(n_in: int, n_out: int, scale: float = 1.0) -> np.ndarray:
    """(n_out, n_in) bilinear interpolation matrix, same sample positions as
    ``records.bilinear_lut`` (align-corners linspace); row i holds the two
    corner weights of output sample i, pre-multiplied by ``scale``."""
    pos = np.linspace(0, n_in - 1, n_out, dtype=np.float32)
    lo = np.floor(pos).astype(np.int64)
    hi = np.minimum(lo + 1, n_in - 1)
    frac = pos - lo.astype(np.float32)
    m = np.zeros((n_out, n_in), np.float32)
    rows = np.arange(n_out)
    np.add.at(m, (rows, lo), (1.0 - frac) * scale)
    np.add.at(m, (rows, hi), frac * scale)
    return m


def _resize_convert_kernel(x_ref, ry_ref, rxt_ref, o_ref):
    x = _to_f32(x_ref[...])                                  # (H, W) one plane
    t = jnp.dot(ry_ref[...], x, precision=_HIGHEST,          # (OH, W)
                preferred_element_type=jnp.float32)
    o_ref[...] = jnp.dot(t, rxt_ref[...], precision=_HIGHEST,  # (OH, OW)
                         preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("out_h", "out_w", "interpret"))
def resize_convert_images(x: jax.Array, out_h: int, out_w: int,
                          *, interpret: Optional[bool] = None) -> jax.Array:
    """Batched device-side resize+convert: (B,H,W,C) u8/u16/f32 ->
    (B,out_h,out_w,C) f32 in [0,1].

    One grid step per channel plane; both interpolation matmuls run on the
    MXU with the dtype-conversion scale folded into the row matrix.
    Requires a uniform-size batch (H, W shared) — the sharded-corpus
    writers emit one with ``hw_jitter=0``.
    """
    B, H, W, C = x.shape
    scale = float(_CONVERT_SCALE.get(np.dtype(x.dtype), 1.0))
    ry = jnp.asarray(_interp_matrix(H, out_h, scale))        # (OH, H)
    rxt = jnp.asarray(_interp_matrix(W, out_w).T)            # (W, OW)
    planes = jnp.transpose(x, (0, 3, 1, 2)).reshape(B * C, H, W)
    out = pl.pallas_call(
        _resize_convert_kernel,
        grid=(B * C,),
        in_specs=[
            pl.BlockSpec((None, H, W), lambda p: (p, 0, 0)),
            pl.BlockSpec((out_h, H), lambda p: (0, 0)),
            pl.BlockSpec((W, out_w), lambda p: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, out_h, out_w), lambda p: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * C, out_h, out_w), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(planes, ry, rxt)
    return jnp.transpose(out.reshape(B, C, out_h, out_w), (0, 2, 3, 1))


def resize_convert_batch_np(x: np.ndarray, out_h: int, out_w: int) -> np.ndarray:
    """Numpy fallback: batched LUT-gather resize with the conversion scale
    folded into the final pass (bit-compatible with the per-image host path)."""
    from ..core import records

    x = np.asarray(x)
    scale = _CONVERT_SCALE.get(x.dtype)
    if scale is None:
        return records.resize_batch(x.astype(np.float32), out_h, out_w)
    return records.resize_batch(x, out_h, out_w, scale=scale)


def resize_convert(x, out_h: int, out_w: int, *, backend: str = "auto"):
    """Dispatch batched resize+convert: ``"pallas"`` (device kernel, compiled
    on an accelerator, interpreted on the CPU backend), ``"numpy"`` (host
    LUT gather), or ``"auto"`` (kernel only when a real accelerator backend
    is present)."""
    if backend == "auto":
        backend = "numpy" if jax.default_backend() == "cpu" else "pallas"
    if backend == "numpy":
        return resize_convert_batch_np(np.asarray(x), out_h, out_w)
    if backend == "pallas":
        return resize_convert_images(jnp.asarray(x), out_h, out_w)
    raise ValueError(f"unknown backend {backend!r}; options: auto/numpy/pallas")
