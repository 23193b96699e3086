"""How ``correct`` is decided: what the timed path produced, against the
plain reference (:mod:`bench.reference`), number by number.

The numbers, each compared with its limit (``value <= limit``):

* ``rows_wrong``: rows of the first batches that are not a record of the
  corpus, repeat a row, or carry another label than the record's (exact, 0);
* ``pixel_gap``: the widest gap between a pixel the input pipeline delivered
  and the reference's decode and resize of the same record;
* ``loss_gap``: over the first steps, the widest relative gap between the
  program's loss and the reference's, the reference stepping from the same
  weights over its own decode of the same records;
* ``grad_gap``: the first gradient as the optimizer got it, worked out from
  the state after one step (``(p0 - p1) / lr``), against the reference's, by
  the worst leaf: ``| |g| - |g_ref| | / max(|g_ref|, median leaf |g_ref|)``;
* ``delta_gap``: the same for the parameters' change over the first steps;
* ``restore_wrong``: leaves of a resumed state that are not bit-identical to
  the state saved at the preemption (exact, 0);
* ``position_wrong``: resumes whose first batch is not the batch the
  preempted stream would have delivered next, so a sample was skipped or
  replayed (exact, 0).

Leaves whose reference gradient is under a thousandth of the median leaf's
move by round-off alone and are left out of ``grad_gap`` and ``delta_gap``.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from . import reference, weights
from .corpus import Corpus, corner_index, corner_key

EXACT = ("rows_wrong", "restore_wrong", "position_wrong")
LEAF_FLOOR = 1e-3


def to_host(tree) -> Dict[str, np.ndarray]:
    """``{path: float64 array}`` of a parameter tree."""
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
            for p, v in flat}


def reference_batches(corpus: Corpus, batches: Sequence, hw: int):
    """Identify each row of the program's batches by its corner pixels, and
    build the reference's own batches of the same records.  Returns
    ``(ref_images, ref_labels, rows_wrong, pixel_gap, records)``, the last
    the record index of each row (-1 where none matched)."""
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
    return (ref_images, np.asarray(ref_labels, np.int32).reshape(n, -1),
            wrong, gap, np.asarray(records).reshape(n, -1))


def reference_steps(seed: int, model: dict, images: List[np.ndarray],
                    labels: np.ndarray, device, *, fp8: bool = False,
                    rows: Optional[int] = None):
    """The reference from the seed's weights through ``len(images)`` steps:
    ``(losses, p0, g1, p1, pn)`` on the host."""
    with jax.default_device(device):
        params = weights.make_params(seed, model)
        p0 = to_host(params)
        losses, g1, p1 = [], None, None
        for k, (x, y) in enumerate(zip(images, labels)):
            loss, grads, params = reference.step(params, x, y, model,
                                                 fp8=fp8, rows=rows)
            losses.append(float(loss))
            if k == 0:
                g1, p1 = to_host(grads), to_host(params)
        return losses, p0, g1, p1, to_host(params)


def leaf_gaps(prog: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
              keep: List[str]) -> Dict[str, float]:
    """Each kept leaf's gap of norms, against the larger of its reference
    norm and the median leaf's."""
    norms = {k: float(np.linalg.norm(ref[k])) for k in keep}
    med = statistics.median(norms.values())
    return {k: abs(float(np.linalg.norm(prog[k])) - norms[k])
            / max(norms[k], med) for k in keep}


def kept_leaves(g_ref: Dict[str, np.ndarray]) -> List[str]:
    norms = {k: float(np.linalg.norm(v)) for k, v in g_ref.items()}
    med = statistics.median(norms.values())
    return [k for k, v in norms.items() if v >= LEAF_FLOOR * med]


def training_gaps(losses, p0, p1, pn, ref_losses, ref_p0, ref_g1, ref_pn,
                  lr: float) -> Dict[str, float]:
    """``loss_gap``, ``grad_gap`` and ``delta_gap`` of one trajectory (the
    program's, the control's or a fault's) against the reference's."""
    keep = kept_leaves(ref_g1)
    g1 = {k: (p0[k] - p1[k]) / lr for k in keep}
    delta = {k: pn[k] - p0[k] for k in keep}
    ref_delta = {k: ref_pn[k] - ref_p0[k] for k in keep}
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(losses,
                                                            ref_losses)),
        "grad_gap": max(leaf_gaps(g1, ref_g1, keep).values()),
        "delta_gap": max(leaf_gaps(delta, ref_delta, keep).values()),
    }


def state_mismatches(saved, restored) -> int:
    """Leaves of ``restored`` that differ from ``saved`` in structure, dtype
    or any bit."""
    s_flat, s_def = jax.tree.flatten(jax.device_get(saved))
    r_flat, r_def = jax.tree.flatten(restored)
    if s_def != r_def:
        return max(len(s_flat), 1)
    return sum(1 for a, b in zip(s_flat, r_flat)
               if np.asarray(a).dtype != np.asarray(b).dtype
               or not np.array_equal(np.asarray(a), np.asarray(b)))


def batches_differ(a, b) -> bool:
    return any(not np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """``(correct, {name: {"value", "limit"}})``; an exact number's limit is
    0, and a number with no limit fails."""
    out, ok = {}, True
    for name, value in numbers.items():
        limit = 0 if name in EXACT else limits.get(name)
        passed = limit is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(passed)
        out[name] = {"value": value, "limit": limit}
    return ok, out
