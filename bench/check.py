"""How ``correct`` is decided: what the timed path produced, against the
plain reference of the cell's architecture, number by number.

The numbers, each compared with its limit (``value <= limit``):

* the input's, from the architecture's ``check_batches``
  (``bench/arch/<arch>.py``): ``rows_wrong``, rows that are not a record of
  the corpus or repeat one (exact, 0), and the module's own;
* ``loss_gap``: over the first steps, the widest relative gap between the
  program's loss and the reference's, the reference stepping from the same
  weights over its own batches of the same records;
* ``grad_gap``: the first gradient as the optimizer got it, worked out from
  the states before and after the first step (the architecture's
  ``first_gradient``), against the reference's, by the worst leaf:
  ``| |g| - |g_ref| | / max(|g_ref|, median leaf |g_ref|)``;
* ``delta_gap``: the same for the parameters' change over the first steps;
* ``restore_wrong``: leaves of a resumed state that are not bit-identical to
  the state saved at the preemption (exact, 0);
* ``position_wrong``: resumes whose first batch is not the batch the
  preempted stream would have delivered next, so a sample was skipped or
  replayed (exact, 0).

Leaves whose reference gradient is under a thousandth of the median leaf's
move by round-off alone and are left out of ``grad_gap`` and ``delta_gap``.

Trees come to the host in each leaf's own dtype and are widened to float64
one leaf at a time, where a norm or a difference is taken, so the check of
a state that fills the chip holds no whole tree in float64.
"""
from __future__ import annotations

import statistics
from collections.abc import Mapping
from typing import Callable, Dict, List

import jax
import numpy as np

EXACT = ("rows_wrong", "restore_wrong", "position_wrong")
LEAF_FLOOR = 1e-3


def to_host(tree) -> Dict[str, np.ndarray]:
    """``{path: array}`` of a tree on the host, each leaf in its own
    dtype."""
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(p): np.asarray(v) for p, v in flat}


class Leafwise(Mapping):
    """``{path: fn(*leaves)}`` over trees of the same paths, each leaf
    widened to float64 and ``fn`` applied only when the leaf is read."""

    def __init__(self, fn: Callable, *trees: Dict[str, np.ndarray]):
        self.fn, self.trees = fn, trees

    def __getitem__(self, k: str) -> np.ndarray:
        return self.fn(*(np.asarray(t[k], np.float64) for t in self.trees))

    def __iter__(self):
        return iter(self.trees[0])

    def __len__(self) -> int:
        return len(self.trees[0])


def change(p0, pn) -> Leafwise:
    """``pn - p0``, leaf by leaf in float64."""
    return Leafwise(np.subtract, pn, p0)


def norm(x) -> float:
    return float(np.linalg.norm(np.asarray(x, np.float64)))


def leaf_gaps(prog: Mapping, ref: Mapping, keep: List[str]) -> Dict[str, float]:
    """Each kept leaf's gap of norms, against the larger of its reference
    norm and the median leaf's."""
    norms = {k: norm(ref[k]) for k in keep}
    med = statistics.median(norms.values())
    return {k: abs(norm(prog[k]) - norms[k]) / max(norms[k], med)
            for k in keep}


def kept_leaves(g_ref: Mapping) -> List[str]:
    norms = {k: norm(v) for k, v in g_ref.items()}
    med = statistics.median(norms.values())
    return [k for k, v in norms.items() if v >= LEAF_FLOOR * med]


def training_gaps(prog, ref) -> Dict[str, float]:
    """``loss_gap``, ``grad_gap`` and ``delta_gap`` of one trajectory (the
    program's, the control's or a fault's) against the reference's.  Each is
    ``(losses, p0, g1, pn)``: the first steps' losses, the parameters before
    them, the first gradient as the optimizer got it, and the parameters
    after them, on the host."""
    losses, p0, g1, pn = prog
    ref_losses, ref_p0, ref_g1, ref_pn = ref
    keep = kept_leaves(ref_g1)
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(losses,
                                                            ref_losses)),
        "grad_gap": max(leaf_gaps(g1, ref_g1, keep).values()),
        "delta_gap": max(leaf_gaps(change(p0, pn), change(ref_p0, ref_pn),
                                   keep).values()),
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
