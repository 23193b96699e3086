"""A toy architecture for the harness's tests, with a record format of its
own: fixed-length int32 token records, raw in shards on native storage, and
an MLP that predicts each token from the one before it (next-token
cross-entropy, plain SGD).  Its reference is the same loss written out in
plain ``jax.numpy`` float32 at ``HIGHEST``, by another route (one-hot
matmuls in place of gathers).

``bench/tests/smoke.py`` writes this file into its smoke tree as
``bench/arch/toy_mlp.py``, beside the real ``alexnet.py``: the harness runs
it from that file alone.  The real benchmark has no such architecture.
"""
from __future__ import annotations

from collections import namedtuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import check
from bench.weights import key_data
from repro.core import make_storage

Corpus = namedtuple("Corpus", "storage paths tokens")


def build_corpus(cfg: dict, seed: int, root: str) -> Corpus:
    """``cfg["n_records"]`` records of ``seq + 1`` tokens uniform over the
    vocabulary, ``cfg["records_per_shard"]`` to a shard."""
    model, per = cfg["model"], cfg["records_per_shard"]
    rng = np.random.default_rng([seed, 0])
    tokens = rng.integers(0, model["vocab"],
                          (cfg["n_records"], model["seq"] + 1), np.int32)
    storage = make_storage("native", root)
    paths = []
    for s in range(0, len(tokens), per):
        path = f"tokens_{s // per:05d}.i32"
        storage.write_file(path, tokens[s:s + per].astype("<i4").tobytes())
        paths.append(path)
    return Corpus(storage, paths, tokens)


def epoch_factory(corpus: Corpus, cfg: dict, traffic: dict, batch: int):
    """Epoch ``ep``: every shard read back, the records shuffled by ``ep``,
    in whole batches of ``(batch, seq + 1)`` tokens on the device."""
    width = cfg["model"]["seq"] + 1

    def epoch(ep):
        rows = np.concatenate([
            np.frombuffer(corpus.storage.read_file(p), "<i4").reshape(-1,
                                                                    width)
            for p in corpus.paths])
        rows = rows[np.random.default_rng([ep, 1]).permutation(len(rows))]
        for s in range(0, len(rows) - batch + 1, batch):
            yield jnp.asarray(rows[s:s + batch], jnp.int32)

    return epoch


def _init(bits, vocab: int, hidden: int) -> dict:
    k = jax.random.split(jax.random.wrap_key_data(bits), 3)
    return {"embed": jax.random.normal(k[0], (vocab, hidden), jnp.float32),
            "w1": jax.random.normal(k[1], (hidden, hidden), jnp.float32)
            / np.sqrt(hidden),
            "b1": jnp.zeros((hidden,), jnp.float32),
            "out": jax.random.normal(k[2], (hidden, vocab), jnp.float32)
            / np.sqrt(hidden),
            "bo": jnp.zeros((vocab,), jnp.float32)}


def make_params(seed: int, model: dict) -> dict:
    return jax.jit(_init, static_argnums=(1, 2))(
        jnp.asarray(key_data(seed, 1)), model["vocab"], model["hidden"])


def make_state(seed: int, model: dict, devices: list) -> dict:
    with jax.default_device(devices[0]):
        return {"params": make_params(seed, model), "step": jnp.int32(0)}


def loss_fn(p, tokens):
    x, y = tokens[:, :-1], tokens[:, 1:]
    h = jnp.tanh(p["embed"][x] @ p["w1"] + p["b1"])
    logp = jax.nn.log_softmax(h @ p["out"] + p["bo"])
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))


def make_train_step(model: dict, devices: list):
    lr = model["lr"]

    @jax.jit
    def train_step(state, tokens):
        loss, g = jax.value_and_grad(loss_fn)(state["params"], tokens)
        params = jax.tree.map(lambda p, d: p - lr * d, state["params"], g)
        return {"params": params, "step": state["step"] + 1}, {"loss": loss}

    return train_step


def first_gradient(p0, state1, model):
    lr = model["lr"]
    return check.Leafwise(lambda a, b: (a - b) / lr, p0,
                          check.to_host(state1["params"]))


def counts(cfg: dict, traffic: dict, batch: int) -> dict:
    m = cfg["model"]
    macs = m["seq"] * (m["hidden"] ** 2 + m["hidden"] * m["vocab"])
    return {"train_flops_per_sample": 6 * macs}


def check_batches(corpus: Corpus, batches, model: dict):
    """``rows_wrong``: rows that are not a record of the corpus, or repeat
    one; the reference's batches are the generator's own records."""
    index = {row.tobytes(): i for i, row in enumerate(corpus.tokens)}
    seen, wrong, ref = set(), 0, []
    for tokens in batches:
        rows = []
        for row in np.asarray(tokens, np.int32):
            i = index.get(row.tobytes())
            if i is None or i in seen:
                wrong += 1
            seen.add(i)
            rows.append(corpus.tokens[0 if i is None else i])
        ref.append(np.stack(rows))
    return {"rows_wrong": wrong}, ref


def ref_loss(p, tokens, vocab: int, dtype):
    x = jax.nn.one_hot(tokens[:, :-1], vocab, dtype=jnp.float32)
    y = jax.nn.one_hot(tokens[:, 1:], vocab, dtype=jnp.float32)

    def mm(a, b):
        return jnp.matmul(a.astype(dtype), b.astype(dtype),
                          preferred_element_type=jnp.float32)

    h = jnp.tanh(mm(mm(x, p["embed"]), p["w1"]) + p["b1"])
    z = mm(h, p["out"]) + p["bo"]
    logp = z - jax.scipy.special.logsumexp(z, axis=-1, keepdims=True)
    return -jnp.sum(logp * y) / (y.shape[0] * y.shape[1])


def reference_steps(seed: int, model: dict, ref, device, *, lower=False,
                    rows=None):
    """``lower``: bfloat16 operands, one step below float32."""
    dtype = jnp.bfloat16 if lower else jnp.float32
    grad = jax.jit(jax.value_and_grad(ref_loss), static_argnums=(2, 3))
    with jax.default_device(device), jax.default_matmul_precision("highest"):
        params = make_params(seed, model)
        p0, losses, g1 = check.to_host(params), [], None
        for k, tokens in enumerate(ref):
            loss, g = grad(params, jnp.asarray(tokens[:rows]), model["vocab"],
                           dtype)
            params = jax.tree.map(lambda p, d: p - model["lr"] * d, params, g)
            losses.append(float(loss))
            if k == 0:
                g1 = check.to_host(g)
        return losses, p0, g1, check.to_host(params)


def control_input(corpus, ref, batches, model) -> dict:
    return {}                   # the records reach the step as they are
