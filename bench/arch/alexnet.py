"""AlexNet on an image corpus, the paper's mini-app: what a run needs that
depends on the model and on its data.

The harness (``bench/loop.py``, ``bench/harness.py``, ``bench/check.py``,
``bench/control.py``) names no architecture.  It finds this file by the
configuration's ``model.arch`` (``bench/spec.py``) and calls the functions
below; another architecture is another file beside this one with the same
functions.

* the corpus: ``bench/corpus.py``'s image shards on native storage;
* the input: the program's ``sharded_image_pipeline`` over them, with the
  traffic file's parameters (``"batched_preprocess"`` and the rest);
* the state and the step: ``bench/weights.py``'s weights, plain SGD, and the
  program's ``repro.models.alexnet.make_train_step``;
* the counts: ``bench/flops.py``;
* the input's check (``rows_wrong``, ``pixel_gap``), the plain reference
  (``bench/reference.py``) and the controls.
"""
from __future__ import annotations

from collections import namedtuple
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import check, flops, reference, weights
from bench.corpus import Corpus, build_corpus, corner_index, corner_key
from repro.configs.alexnet_mini import AlexNetConfig
from repro.core import sharded_image_pipeline
from repro.models import alexnet

__all__ = ["build_corpus", "epoch_factory", "make_state", "make_train_step",
           "first_gradient", "counts", "check_batches", "reference_steps",
           "control_input", "batch_shapes", "lower_reference"]

# the reference's own batches of the records the program's rows came from,
# and the record index of each row (-1 where none matched)
Reference = namedtuple("Reference", "images labels records")


def program_config(model: dict) -> AlexNetConfig:
    return AlexNetConfig(name="bench", in_hw=model["in_hw"],
                         channels=model["channels"],
                         n_classes=model["n_classes"],
                         filters=tuple(model["filters"]),
                         fc=tuple(model["fc"]), lr=model["lr"])


def epoch_factory(corpus: Corpus, cfg: dict, traffic: dict, batch: int):
    """``ep -> `` epoch ``ep``'s batches of ``(images, labels)``."""
    hw = cfg["model"]["in_hw"]

    def epoch(ep):
        return sharded_image_pipeline(
            corpus.storage, corpus.paths, corpus.labels_per_shard,
            batch_size=batch, cycle_length=traffic["cycle_length"],
            block_length=traffic["block_length"],
            num_parallel_calls=traffic["num_parallel_calls"],
            prefetch=traffic["prefetch"], out_hw=(hw, hw),
            batched_preprocess=traffic["batched_preprocess"], seed=ep,
            repeat=False)

    return epoch


def make_state(seed: int, model: dict, devices: list) -> dict:
    """The step's first state, ``{"params", "step"}``, made on the cell's
    one device from the seed."""
    with jax.default_device(devices[0]):
        return {"params": weights.make_params(seed, model),
                "step": jnp.int32(0)}


def make_train_step(model: dict, devices: list):
    return alexnet.make_train_step(program_config(model))


def first_gradient(p0: Dict[str, np.ndarray], state1: dict,
                   model: dict) -> check.Leafwise:
    """The first gradient as plain SGD got it, ``(p0 - p1) / lr``, from the
    host copy of the parameters before the first step and the state after
    it, on the device: read before the next step could donate it."""
    lr = model["lr"]
    return check.Leafwise(lambda a, b: (a - b) / lr, p0,
                          check.to_host(state1["params"]))


def counts(cfg: dict, traffic: dict, batch: int) -> dict:
    """The training FLOPs of one image, and the device resize's FLOPs and
    bytes for one batch, from shapes."""
    model, hw = cfg["model"], cfg["image_hw"]
    shape = (batch, hw, hw, model["channels"], model["in_hw"], model["in_hw"])
    return {"train_flops_per_sample": flops.alexnet_train_flops(model),
            "resize": {"flops": flops.resize_flops(*shape),
                       "bytes": flops.resize_bytes(*shape)},
            "uses_resize_kernel": traffic["batched_preprocess"] == "pallas"}


def check_batches(corpus: Corpus, batches: Sequence, model: dict):
    """Identify each row of the program's batches by its corner pixels, and
    build the reference's own batches of the same records.  Returns
    ``({"rows_wrong", "pixel_gap"}, Reference)``: rows that are not a
    record of the corpus, repeat a row or carry another label than the
    record's, and the widest gap between a delivered pixel and the
    reference's decode and resize of the same record."""
    hw = model["in_hw"]
    index = corner_index(corpus)
    seen, wrong, gap = set(), 0, 0.0
    ref_images, ref_labels, records = [], [], []
    for images, labels in batches:
        images, labels = np.asarray(images), np.asarray(labels)
        corners = np.rint(images[:, [0, -1]][:, :, [0, -1]] * 255.0)
        rows = []
        for b in range(images.shape[0]):
            i = index.get(corner_key(np.clip(corners[b], 0, 255)
                                     .astype(np.uint8)))
            if i is None or i in seen or labels[b] != corpus.labels[i]:
                wrong += 1
            records.append(-1 if i is None else i)
            if i is None:
                rows.append(np.zeros(images.shape[1:], np.float32))
                ref_labels.append(0)
                continue
            seen.add(i)
            rows.append(reference.resize(corpus.pixels(i), hw, hw))
            ref_labels.append(int(corpus.labels[i]))
        ref = np.stack(rows)
        gap = max(gap, float(np.max(np.abs(images.astype(np.float64) - ref))))
        ref_images.append(ref)
    n = len(batches)
    return ({"rows_wrong": wrong, "pixel_gap": gap},
            Reference(ref_images,
                      np.asarray(ref_labels, np.int32).reshape(n, -1),
                      np.asarray(records).reshape(n, -1)))


def reference_steps(seed: int, model: dict, ref: Reference, device, *,
                    lower: bool = False, rows=None):
    """The reference from the seed's weights through its batches:
    ``(losses, p0, g1, pn)`` on the host.  ``lower``: with float8 e4m3
    operands, one step below the configuration's bfloat16 passes."""
    with jax.default_device(device):
        params = weights.make_params(seed, model)
        p0 = check.to_host(params)
        losses: List[float] = []
        g1 = None
        for k, (x, y) in enumerate(zip(ref.images, ref.labels)):
            loss, grads, params = reference.step(params, x, y, model,
                                                 fp8=lower, rows=rows)
            losses.append(float(loss))
            if k == 0:
                g1 = check.to_host(grads)
        return losses, p0, g1, check.to_host(params)


def control_input(corpus: Corpus, ref: Reference, batches: Sequence,
                  model: dict) -> Dict[str, dict]:
    """The input's control, the resize's matmuls at ``Precision.HIGH``
    (three passes) for the ``HIGHEST`` the configuration states, and its
    fault, one pixel of the first batch raised by one grey level."""
    hw = model["in_hw"]
    pixels = [np.stack([corpus.pixels(i) for i in rows])
              for rows in ref.records]
    lower = [reference.resize_as_matmuls(x, hw, hw, lax.Precision.HIGH)
             for x in pixels]
    control = max(float(np.max(np.abs(x - r.astype(np.float64))))
                  for x, r in zip(lower, ref.images))
    altered = [tuple(np.array(x) for x in b) for b in batches[:1]]
    altered[0][0][0, 5, 5, 0] += 1.0 / 255.0
    return {"control": {"pixel_gap": control},
            "altered_pixel": {
                "pixel_gap": check_batches(corpus, altered,
                                           model)[0]["pixel_gap"]}}


def batch_shapes(cfg: dict):
    """One batch as the step takes it, as shapes."""
    model, b = cfg["model"], cfg["batch"]
    hw = model["in_hw"]
    return (jax.ShapeDtypeStruct((b, hw, hw, model["channels"]), jnp.float32),
            jax.ShapeDtypeStruct((b,), jnp.int32))


def lower_reference(params, batch, model: dict):
    """The reference's step lowered for ``params`` and ``batch``, which may
    be shapes placed on a device that is described and not attached."""
    images, labels = batch
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=labels.sharding)
    key = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                       for k, v in model.items()))
    with jax.default_matmul_precision("highest"):
        return reference._step.lower(params, images, labels, lr,
                                     model_key=key, fp8=False, rows=None)
