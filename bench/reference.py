"""Plain reference of what the timed path computes.

Nothing here imports the program.  The input side decodes a record's pixels
from the corpus this benchmark wrote and resizes them by the definition of
an align-corners bilinear resize, in float64.  The model side is AlexNet's
training step in straightforward ``jax.numpy`` at ``Precision.HIGHEST``:
five convolutions (SAME padding, ReLU, 2x2/2 max-pools after the first,
second and fifth), three fully connected layers, mean softmax cross-entropy
and plain SGD.

``fp8=True`` computes the same step with every convolution and matmul
operand rounded to float8 e4m3 under a per-tensor scale, in the forward and
the backward pass alike, with float32 accumulation: the control, one step
below the bfloat16 operands of the TPU's default precision that the
configurations state.  ``rows`` keeps only the first ``rows`` examples of the
batch (the faults of a step that drops half the batch, or a data-parallel
step that leaves out the exchange and updates with one chip's quarter).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import flops

HIGHEST = lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _round_fp8(x):
    """``x`` rounded to float8 e4m3, scaled so its largest magnitude maps to
    the format's largest."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, E4M3_MAX / amax, 1.0)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(x.dtype) / scale


@jax.custom_vjp
def fp8_operand(x):
    """An operand in float8; its gradient passes through unchanged."""
    return _round_fp8(x)


fp8_operand.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def fp8_cotangent(y):
    """Identity forward; the gradient arriving at ``y`` rounded to float8, so
    the backward matmuls take float8 operands too."""
    return y


fp8_cotangent.defvjp(lambda y: (y, None), lambda _, g: (_round_fp8(g),))


def _mm(fn, x, w, fp8: bool):
    if not fp8:
        return fn(x, w)
    return fp8_cotangent(fn(fp8_operand(x), fp8_operand(w)))


def _taps(n_in: int, n_out: int):
    pos = np.arange(n_out) * (n_in - 1) / (n_out - 1)
    lo = np.floor(pos).astype(np.int64)
    hi = np.minimum(lo + 1, n_in - 1)
    return lo, hi, pos - lo


def _matrix(n_in: int, n_out: int) -> np.ndarray:
    """``(n_out, n_in)``: the resize along one axis as a matrix."""
    lo, hi, f = _taps(n_in, n_out)
    m = np.zeros((n_out, n_in))
    np.add.at(m, (np.arange(n_out), lo), 1 - f)
    np.add.at(m, (np.arange(n_out), hi), f)
    return m


def resize_as_matmuls(pixels: np.ndarray, out_h: int, out_w: int,
                      precision) -> np.ndarray:
    """The same resize of a ``(b, h, w, c)`` uint8 batch, as two float32
    matmuls on the device at ``precision``."""
    _, h, w, _ = pixels.shape
    ry = jnp.asarray(_matrix(h, out_h) / 255.0, jnp.float32)
    rx = jnp.asarray(_matrix(w, out_w), jnp.float32)
    x = jnp.asarray(pixels, jnp.float32)
    t = jnp.einsum("oh,bhwc->bowc", ry, x, precision=precision)
    return np.asarray(jnp.einsum("pw,bowc->bopc", rx, t,
                                 precision=precision))


def resize(pixels: np.ndarray, out_h: int, out_w: int) -> np.ndarray:
    """``(h, w, c)`` uint8 -> ``(out_h, out_w, c)`` float32 in [0, 1]:
    output row ``i`` samples input row ``i * (h - 1) / (out_h - 1)``,
    interpolated linearly between its two neighbours (columns alike)."""
    h, w, _ = pixels.shape
    x = pixels.astype(np.float64) / 255.0
    ylo, yhi, fy = _taps(h, out_h)
    xlo, xhi, fx = _taps(w, out_w)
    rows = x[ylo] * (1 - fy)[:, None, None] + x[yhi] * fy[:, None, None]
    out = rows[:, xlo] * (1 - fx)[None, :, None] + rows[:, xhi] * fx[None, :, None]
    return out.astype(np.float32)


def forward(params, images, model: dict, fp8: bool = False):
    x = images
    for i, (k, s) in enumerate(zip(flops.KERNEL_HW, flops.CONV_STRIDES)):
        p = params[f"conv{i}"]
        conv = functools.partial(
            lax.conv_general_dilated, window_strides=(s, s), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
        x = jax.nn.relu(_mm(conv, x, p["w"], fp8) + p["b"])
        if i in flops.POOL_AFTER:
            x = lax.reduce_window(x, -jnp.inf, lax.max,
                                  (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    for i in range(3):
        p = params[f"fc{i}"]
        dot = functools.partial(jnp.dot, precision=HIGHEST)
        x = _mm(dot, x, p["w"], fp8) + p["b"]
        if i < 2:
            x = jax.nn.relu(x)
    return x


def loss_fn(params, images, labels, model: dict, fp8: bool = False):
    logits = forward(params, images, model, fp8)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


@functools.partial(jax.jit, static_argnames=("model_key", "fp8", "rows"))
def _step(params, images, labels, lr, *, model_key, fp8, rows):
    model = dict(model_key)
    if rows is not None:
        images, labels = images[:rows], labels[:rows]
    loss, grads = jax.value_and_grad(loss_fn)(params, images, labels, model,
                                              fp8)
    new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    return loss, grads, new


def step(params, images, labels, model: dict, *, fp8: bool = False,
         rows: Optional[int] = None):
    """One SGD step at ``model["lr"]``: ``(loss, grads, new_params)``."""
    key = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                       for k, v in model.items()))
    with jax.default_matmul_precision("highest"):
        return _step(params, jnp.asarray(images), jnp.asarray(labels),
                     jnp.float32(model["lr"]), model_key=key, fp8=fp8,
                     rows=rows)
