"""AlexNet — the paper's mini-application network (§III-B, ~200 lines in TF).

5 conv (ReLU) + 3 maxpool + 3 FC, softmax-xent loss, plain SGD — exactly
the paper's workload shape: per-batch compute long enough that the
prefetcher can hide the input pipeline behind it.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array


def init_params(rng, cfg) -> Dict[str, Any]:
    keys = iter(jax.random.split(rng, 16))
    f = cfg.filters
    c_in = cfg.channels
    params: Dict[str, Any] = {}
    kernel_hw = [11, 5, 3, 3, 3]
    for i, (kout, khw) in enumerate(zip(f, kernel_hw)):
        shape = (khw, khw, c_in, kout)
        fan_in = khw * khw * c_in
        params[f"conv{i}"] = dict(
            w=(jax.random.normal(next(keys), shape, jnp.float32)
               * math.sqrt(2.0 / fan_in)),
            b=jnp.zeros((kout,), jnp.float32),
        )
        c_in = kout
    # flatten size: in_hw /4 (conv0 stride) then three /2 maxpools
    hw = cfg.in_hw // 4
    for _ in range(3):
        hw = hw // 2
    flat = hw * hw * f[-1]
    dims = [flat, *cfg.fc, cfg.n_classes]
    for i in range(3):
        params[f"fc{i}"] = dict(
            w=(jax.random.normal(next(keys), (dims[i], dims[i + 1]), jnp.float32)
               * math.sqrt(2.0 / dims[i])),
            b=jnp.zeros((dims[i + 1],), jnp.float32),
        )
    return params


def forward(params: Dict[str, Any], images: Array, cfg) -> Array:
    """images: (B, H, W, C) float32 -> logits (B, n_classes)."""
    x = images
    strides = [4, 1, 1, 1, 1]
    pool_after = {0, 1, 4}
    for i in range(5):
        p = params[f"conv{i}"]
        x = lax.conv_general_dilated(
            x, p["w"], window_strides=(strides[i], strides[i]),
            padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ) + p["b"]
        x = jax.nn.relu(x)
        if i in pool_after:
            x = lax.reduce_window(
                x, -jnp.inf, lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
            )
    x = x.reshape(x.shape[0], -1)
    for i in range(3):
        p = params[f"fc{i}"]
        x = x @ p["w"] + p["b"]
        if i < 2:
            x = jax.nn.relu(x)
    return x


def loss_fn(params, images: Array, labels: Array, cfg) -> Array:
    logits = forward(params, images, cfg)
    logp = jax.nn.log_softmax(logits)
    onehot = jax.nn.one_hot(labels, cfg.n_classes)
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


def make_train_step(cfg):
    """The mini-app's jitted SGD step at ``cfg.lr``:
    ``({"params", "step"}, (images, labels)) -> (state, {"loss"})``."""

    @jax.jit
    def train_step(state, batch):
        images, labels = batch
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, images, labels, cfg))(state["params"])
        params = jax.tree.map(lambda p, g: p - cfg.lr * g,
                              state["params"], grads)
        return {"params": params, "step": state["step"] + 1}, {"loss": loss}

    return train_step
