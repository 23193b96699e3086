"""Weights made on the device from the seed, in one jitted call.

The layout is the one the program's AlexNet step consumes (``conv0`` ..
``conv4``, ``fc0`` .. ``fc2``, each ``{"w", "b"}``, float32); the values come
from this file alone, so the reference can make the same weights again from
the seed without taking any array from the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import flops


def key_data(seed: int, stream: int) -> np.ndarray:
    """Threefry key data for ``(seed, stream)``; any non-negative seed,
    including ones beyond 32 bits."""
    return np.random.SeedSequence([seed, stream]).generate_state(2, np.uint32)


def _init(key_bits, model: dict) -> dict:
    key = jax.random.wrap_key_data(key_bits)
    layers = flops.alexnet_layers(model)
    keys = jax.random.split(key, len(layers))
    c_in = model["channels"]
    params = {}
    for i, (c_out, k) in enumerate(zip(model["filters"], flops.KERNEL_HW)):
        fan_in = k * k * c_in
        params[f"conv{i}"] = {
            "w": jax.random.normal(keys[i], (k, k, c_in, c_out), jnp.float32)
            * math.sqrt(2.0 / fan_in),
            "b": jnp.zeros((c_out,), jnp.float32)}
        c_in = c_out
    hw = model["in_hw"]
    for s in flops.CONV_STRIDES:
        hw = -(-hw // s)
    hw //= 2 ** len(flops.POOL_AFTER)
    dims = [hw * hw * c_in, *model["fc"], model["n_classes"]]
    for i in range(3):
        params[f"fc{i}"] = {
            "w": jax.random.normal(keys[5 + i], (dims[i], dims[i + 1]),
                                   jnp.float32) * math.sqrt(2.0 / dims[i]),
            "b": jnp.zeros((dims[i + 1],), jnp.float32)}
    return params


def make_params(seed: int, model: dict) -> dict:
    """AlexNet parameters for ``model`` on the default device."""
    static = {k: tuple(v) if isinstance(v, list) else v
              for k, v in model.items()}
    fn = jax.jit(lambda bits: _init(bits, static))
    return fn(jnp.asarray(key_data(seed, 1)))
