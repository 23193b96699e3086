"""The check's numbers on host trees in each leaf's own dtype, widened leaf
by leaf, against the float64 whole-tree formula they replace."""
import statistics

import numpy as np
import pytest

from bench import check

LR = 1e-4


# -- the float64 whole-tree formula, as the check computed it before --------
def wide(tree):
    return {k: np.asarray(v, np.float64) for k, v in tree.items()}


def old_leaf_gaps(prog, ref, keep):
    norms = {k: float(np.linalg.norm(ref[k])) for k in keep}
    med = statistics.median(norms.values())
    return {k: abs(float(np.linalg.norm(prog[k])) - norms[k])
            / max(norms[k], med) for k in keep}


def old_kept_leaves(g_ref):
    norms = {k: float(np.linalg.norm(v)) for k, v in g_ref.items()}
    med = statistics.median(norms.values())
    return [k for k, v in norms.items() if v >= check.LEAF_FLOOR * med]


def old_training_gaps(prog, ref):
    losses, p0, g1, pn = prog
    ref_losses, ref_p0, ref_g1, ref_pn = ref
    keep = old_kept_leaves(ref_g1)
    delta = {k: pn[k] - p0[k] for k in keep}
    ref_delta = {k: ref_pn[k] - ref_p0[k] for k in keep}
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(losses,
                                                            ref_losses)),
        "grad_gap": max(old_leaf_gaps(g1, ref_g1, keep).values()),
        "delta_gap": max(old_leaf_gaps(delta, ref_delta, keep).values()),
    }


# -- random float32 trajectories ---------------------------------------------
SHAPES = {"['a']['w']": (64, 48), "['a']['b']": (48,), "['c']": (7, 5, 3),
          "['big']": (1000,), "['still']": (16,)}


def tree(rng, scale=1.0):
    return {k: (scale * rng.standard_normal(s)).astype(np.float32)
            for k, s in SHAPES.items()}


def trajectory(rng):
    """``(losses, p0, p1, pn)``: ``['big']`` sits at 1e3 and moves by ~1e-4,
    so ``pn - p0`` cancels all but the last digits of float32, and
    ``['still']`` has a gradient far under the floor."""
    p0 = tree(rng)
    p0["['big']"] += np.float32(1e3)
    g = tree(rng)
    g["['still']"] *= np.float32(1e-9)
    p1 = {k: (p0[k] - np.float32(LR) * g[k]).astype(np.float32) for k in p0}
    pn = {k: (p1[k] + tree(rng, 1e-4)[k]).astype(np.float32) for k in p0}
    pn["['big']"] = (p0["['big']"] + np.float32(1e-4)).astype(np.float32)
    return list(rng.uniform(1, 5, 3)), p0, p1, pn


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 7])
def test_training_gaps_equal_the_float64_formula(seed):
    rng = np.random.default_rng(seed)
    losses, p0, p1, pn = trajectory(rng)
    ref_losses, rp0, rp1, rpn = trajectory(rng)
    g1 = check.Leafwise(lambda a, b: (a - b) / LR, p0, p1)
    rg1 = tree(rng)
    rg1["['still']"] *= np.float32(1e-9)
    new = check.training_gaps((losses, p0, g1, pn), (ref_losses, rp0, rg1,
                                                     rpn))
    w0, w1 = wide(p0), wide(p1)
    old_g1 = {k: (w0[k] - w1[k]) / LR for k in w0}
    old = old_training_gaps((losses, w0, old_g1, wide(pn)),
                            (ref_losses, wide(rp0), wide(rg1), wide(rpn)))
    assert new == old
    assert "['still']" not in check.kept_leaves(rg1)
    # the cancelling leaf: its float32 difference would read otherwise
    big = "['big']"
    assert check.change(p0, pn)[big].dtype == np.float64
    np.testing.assert_array_equal(check.change(p0, pn)[big],
                                  wide(pn)[big] - wide(p0)[big])


@pytest.mark.parametrize("seed", [3, 4])
def test_leaf_gaps_equal_the_float64_formula(seed):
    rng = np.random.default_rng(seed)
    prog, ref = tree(rng), tree(rng)
    keep = sorted(SHAPES)
    assert check.leaf_gaps(prog, ref, keep) == old_leaf_gaps(
        wide(prog), wide(ref), keep)


def test_to_host_keeps_each_leafs_dtype():
    import jax.numpy as jnp
    host = check.to_host({"a": jnp.ones(3, jnp.float32),
                          "b": {"c": jnp.ones(2, jnp.bfloat16)},
                          "n": jnp.int32(4)})
    assert {k: v.dtype.name for k, v in host.items()} == {
        "['a']": "float32", "['b']['c']": "bfloat16", "['n']": "int32"}
    assert all(type(v) is np.ndarray for v in host.values())
