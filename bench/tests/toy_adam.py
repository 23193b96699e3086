"""A toy architecture with an Adam state, for the harness's tests: the token
MLP of ``toy_mlp.py`` (its corpus, input, loss, weights and input check),
trained by the program's Adam (``repro.train.optimizer.adam_update``) in a
step that donates its state, as a step whose state fills the chip has to.
The first gradient is read from the first moment after step 1; the
reference is Adam written out in plain ``jax.numpy`` over ``toy_mlp``'s
reference loss.

``bench/tests/smoke.py`` writes this file into its smoke tree as
``bench/arch/toy_adam.py``; the real benchmark has no such architecture.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import check
from bench.tests.toy_mlp import (build_corpus, check_batches,  # noqa: F401
                                 control_input, counts, epoch_factory,
                                 loss_fn, make_params, ref_loss)
from repro.train.optimizer import OptConfig, adam_update, init_opt_state


def _opt(model: dict) -> OptConfig:
    return OptConfig(lr=model["lr"], b1=model["b1"], b2=model["b2"],
                     eps=model["eps"], grad_clip=0.0)


def make_state(seed: int, model: dict, devices: list) -> dict:
    with jax.default_device(devices[0]):
        params = make_params(seed, model)
        return {"params": params, "opt": init_opt_state(params, _opt(model)),
                "step": jnp.int32(0)}


def make_train_step(model: dict, devices: list):
    opt = _opt(model)

    @functools.partial(jax.jit, donate_argnums=0)
    def train_step(state, tokens):
        loss, g = jax.value_and_grad(loss_fn)(state["params"], tokens)
        params, moments = adam_update(g, state["opt"], state["params"],
                                      state["step"], opt)
        return ({"params": params, "opt": moments, "step": state["step"] + 1},
                {"loss": loss})

    return train_step


def first_gradient(p0, state1, model):
    """``m1 / (1 - b1)``: Adam's first moment after one step from zero."""
    m1 = check.to_host({k: s["m"] for k, s in state1["opt"].items()})
    b1 = model["b1"]
    return check.Leafwise(lambda m: m / (1 - b1), m1)


def reference_steps(seed: int, model: dict, ref, device, *, lower=False,
                    rows=None):
    """``lower``: bfloat16 operands, one step below float32."""
    dtype = jnp.bfloat16 if lower else jnp.float32
    lr, b1, b2, eps = model["lr"], model["b1"], model["b2"], model["eps"]
    grad = jax.jit(jax.value_and_grad(ref_loss), static_argnums=(2, 3))
    with jax.default_device(device), jax.default_matmul_precision("highest"):
        params = make_params(seed, model)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        p0, losses, g1 = check.to_host(params), [], None
        for t, tokens in enumerate(ref, 1):
            loss, g = grad(params, jnp.asarray(tokens[:rows]), model["vocab"],
                           dtype)
            m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
            v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
            params = jax.tree.map(
                lambda p, m, v: p - lr * (m / (1 - b1 ** t))
                / (jnp.sqrt(v / (1 - b2 ** t)) + eps), params, m, v)
            losses.append(float(loss))
            if t == 1:
                g1 = check.to_host(g)
        return losses, p0, g1, check.to_host(params)
