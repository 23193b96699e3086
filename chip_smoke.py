"""Chip smoke test: the paper's AlexNet mini-application on one TPU chip.

    python chip_smoke.py [--seed N]         # one chip, the main path
    python chip_smoke.py --chips 4          # the data-parallel mesh phase only

Drives the library's normal training path at full width — ``Trainer`` over
``sharded_image_pipeline`` with the compiled Pallas resize+convert kernel,
checkpointing through ``CheckpointManager(engine="asyncbb")`` — with random
weights and a random corpus, both made from ``--seed``:

1. build 512 uniform 256x256 RGB images in 16 shards on native storage;
2. check the kernel's first batch against the host numpy path;
3. train ``CONFIG`` AlexNet for 6 steps, saving every 2;
4. resume in a fresh ``Trainer`` (params bit-identical, iterator position
   restored) and take 2 more steps.

``--chips 4`` instead steps AlexNet data-parallel on a 4-device mesh, checks
its losses against the same batches stepped on one device, and restores the
saved state sharded onto the mesh.

The few numbers printed on the way are smoke readings, not a benchmark.  The
last line of stdout is ``{"ok": true, "device": {...}}``.  Without a TPU the
script exits non-zero before doing any work; any failed check exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs import ALEXNET  # noqa: E402
from repro.core import (CheckpointManager, ResumableIterator,  # noqa: E402
                        make_storage, prefetch_to_device, records,
                        sharded_image_pipeline)
from repro.kernels import resolve_interpret  # noqa: E402
from repro.kernels.preprocess import resize_convert_batch_np  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import alexnet as A  # noqa: E402
from repro.train.trainer import Trainer  # noqa: E402

N_IMAGES = 512
IMAGES_PER_SHARD = 32
IMAGE_HW = 256
BATCH = 32
TRAIN_STEPS = 6
RESUMED_STEPS = 2
CKPT_EVERY = 2
MESH_STEPS = 3
# kernel vs numpy resize+convert, values in [0, 1]: the tolerance
# tests/test_kernels.py holds the kernel to
RESIZE_ATOL = 1e-5
# data-parallel vs one-device losses: the same math, summed in another order
MESH_LOSS_RTOL = 1e-3
CKPT_PREFIX = "ckpt/alexnet"


def reading(name: str, value) -> None:
    print(f"smoke reading (not a benchmark): {name} = {value}", flush=True)


def require_tpu() -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); nothing was run")
    return devices


def build_corpus(root: str, seed: int, *, n_images: int = N_IMAGES,
                 images_per_shard: int = IMAGES_PER_SHARD,
                 hw: int = IMAGE_HW, n_classes: int = ALEXNET.n_classes):
    """Uniform-size sharded image corpus: ``(storage, paths, labels)``."""
    storage = make_storage("native", os.path.join(root, "corpus"))
    paths, labels = records.write_sharded_image_dataset(
        storage, n_images, images_per_shard, mean_hw=(hw, hw), hw_jitter=0,
        n_classes=n_classes, seed=seed)
    return storage, paths, labels


def pipeline_factory(corpus, cfg, batch_size: int, backend: str = "pallas"):
    """``epoch -> Dataset`` over the corpus, shuffled by the epoch number."""
    storage, paths, labels = corpus

    def epoch(ep):
        return sharded_image_pipeline(
            storage, paths, labels, batch_size=batch_size,
            out_hw=(cfg.in_hw, cfg.in_hw), batched_preprocess=backend,
            seed=ep, repeat=False)

    return epoch


def init_state(cfg, seed: int):
    params = A.init_params(jax.random.PRNGKey(seed), cfg)
    return {"params": params, "step": jnp.int32(0)}


def make_manager(root: str, engine: str = "asyncbb") -> CheckpointManager:
    slow = make_storage("native", os.path.join(root, "slow"))
    fast = (make_storage("native", os.path.join(root, "fast"))
            if engine in ("bb", "asyncbb") else None)
    return CheckpointManager(slow, CKPT_PREFIX, engine=engine,
                             fast_storage=fast)


def assert_trees_equal(got, want) -> None:
    """Same structure, dtypes and bits in every leaf."""
    if jax.tree.structure(got) != jax.tree.structure(want):
        raise AssertionError("state structure differs")
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        if g.dtype != w.dtype or not np.array_equal(g, w):
            raise AssertionError("state leaves are not bit-identical")


def check_history(history, first_step: int, n_steps: int) -> list:
    """The run took exactly ``n_steps`` steps, numbered on from
    ``first_step``, with finite losses; returns the losses."""
    steps = [h["step"] for h in history]
    want = list(range(first_step + 1, first_step + n_steps + 1))
    if steps != want:
        raise AssertionError(f"ran steps {steps}, expected {want}")
    losses = [h["loss"] for h in history]
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss in {losses}")
    return losses


def check_first_batch(corpus, cfg, batch_size: int = BATCH) -> float:
    """First batch of epoch 0 through the Pallas kernel vs the host numpy
    path (:func:`resize_convert_batch_np`); returns the max abs error."""
    with iter(pipeline_factory(corpus, cfg, batch_size, "pallas")(0)) as it:
        images, labels = next(it)
    with iter(pipeline_factory(corpus, cfg, batch_size, "numpy")(0)) as it:
        want_images, want_labels = next(it)
    shape = (batch_size, cfg.in_hw, cfg.in_hw, cfg.channels)
    if images.shape != shape or images.dtype != jnp.float32:
        raise AssertionError(f"kernel batch {images.shape} {images.dtype}, "
                             f"expected {shape} float32")
    if not np.array_equal(np.asarray(labels), want_labels):
        raise AssertionError("kernel and numpy pipelines disagree on labels")
    err = float(np.max(np.abs(np.asarray(images) - want_images)))
    if not err <= RESIZE_ATOL:
        raise AssertionError(f"kernel batch differs from the numpy path by "
                             f"{err} > {RESIZE_ATOL}")
    return err


def train(corpus, cfg, ckpt_root: str, train_step, *, seed: int,
          batch_size: int = BATCH, n_steps: int = TRAIN_STEPS) -> Trainer:
    """Fresh run of ``n_steps`` steps with a save every ``CKPT_EVERY``."""
    data = ResumableIterator(pipeline_factory(corpus, cfg, batch_size))
    mgr = make_manager(ckpt_root)
    tr = Trainer(train_step, init_state(cfg, seed), data, checkpointer=mgr,
                 ckpt_every=CKPT_EVERY, resume=False)
    try:
        check_history(tr.run(n_steps), 0, n_steps)
        tr.wait_for_checkpoints()
    finally:
        tr.close()
        mgr.close()
    return tr


def resume(corpus, cfg, ckpt_root: str, train_step, first: Trainer, *,
           seed: int, batch_size: int = BATCH,
           n_steps: int = RESUMED_STEPS) -> Trainer:
    """Restart as a new process would: restore ``first``'s last save and
    position, check both, then take ``n_steps`` more steps."""
    data = ResumableIterator(pipeline_factory(corpus, cfg, batch_size))
    mgr = make_manager(ckpt_root)
    skeleton = jax.eval_shape(lambda: init_state(cfg, seed))
    tr = Trainer(train_step, skeleton, data, checkpointer=mgr,
                 ckpt_every=CKPT_EVERY, resume=True)
    try:
        if tr.recovered_step != first.step:
            raise AssertionError(f"resumed at {tr.recovered_step}, "
                                 f"expected {first.step}")
        assert_trees_equal(tr.state, first.state)
        if data.state() != first.data_iter.state():
            raise AssertionError(f"iterator at {data.state()}, expected "
                                 f"{first.data_iter.state()}")
        check_history(tr.run(n_steps), first.step, n_steps)
        tr.wait_for_checkpoints()
    finally:
        tr.close()
        mgr.close()
    return tr


def _placed_on(batches, devices):
    """Pass batches through, failing on any leaf not spread over all of
    ``devices``."""
    for batch in batches:
        for leaf in jax.tree.leaves(batch):
            if leaf.sharding.device_set != devices:
                raise AssertionError(f"batch leaf on {leaf.sharding}")
        yield batch


def four_chips(corpus, cfg, ckpt_root: str, devices, *, seed: int,
               batch_size: int = BATCH, n_steps: int = MESH_STEPS) -> dict:
    """Data-parallel AlexNet on a ``"data"`` mesh over ``devices``:
    ``n_steps`` steps against the same global batches on ``devices[0]``,
    then the manager's save restored sharded onto the mesh."""
    from jax.sharding import (AxisType, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    mesh = jax.make_mesh((len(devices),), ("data",), devices=devices,
                         axis_types=(AxisType.Auto,))
    mesh_devices = set(devices)
    with iter(pipeline_factory(corpus, cfg, batch_size, "numpy")(0)) as it:
        batches = [next(it) for _ in range(n_steps)]
    train_step = A.make_train_step(cfg)
    state = init_state(cfg, seed)

    one = SingleDeviceSharding(devices[0])
    ref = Trainer(train_step, jax.device_put(state, one),
                  prefetch_to_device(iter(batches), sharding=one),
                  resume=False)
    ref_losses = check_history(ref.run(n_steps), 0, n_steps)

    mgr = make_manager(ckpt_root, engine="direct")
    dp = Trainer(train_step,
                 jax.device_put(state, NamedSharding(mesh, P())),
                 _placed_on(prefetch_to_device(
                     iter(batches), sharding=NamedSharding(mesh, P("data"))),
                     mesh_devices),
                 checkpointer=mgr, ckpt_every=n_steps, resume=False)
    try:
        losses = check_history(dp.run(n_steps), 0, n_steps)
        dp.wait_for_checkpoints()
        for leaf in jax.tree.leaves(dp.state):
            if leaf.sharding.device_set != mesh_devices:
                raise AssertionError(f"state leaf on {leaf.sharding}")
        np.testing.assert_allclose(losses, ref_losses, rtol=MESH_LOSS_RTOL)

        def spec(leaf):  # shard the last axis where it divides evenly
            if leaf.ndim and leaf.shape[-1] % len(devices) == 0:
                return P(*([None] * (leaf.ndim - 1)), "data")
            return P()

        shardings = jax.tree.map(
            lambda leaf: NamedSharding(mesh, spec(leaf)), dp.state)
        restored = mgr.restore_sharded(dp.state, shardings)
        for leaf, sh in zip(jax.tree.leaves(restored),
                            jax.tree.leaves(shardings)):
            if leaf.sharding != sh:
                raise AssertionError(f"restored leaf on {leaf.sharding}, "
                                     f"expected {sh}")
        assert_trees_equal(restored, dp.state)
    finally:
        mgr.close()
    return {"losses": losses, "reference_losses": ref_losses}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the corpus and the weights (default 0)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel mesh phase")
    args = ap.parse_args(argv)

    devices = require_tpu()
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX found "
                         f"{len(devices)} device(s)")
    if resolve_interpret(None):
        raise SystemExit("chip_smoke: Pallas kernels would be interpreted")
    reading("device_kind", devices[0].device_kind)
    reading("compile cache", use_compile_cache())
    cfg = ALEXNET

    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as root:
        corpus = build_corpus(root, args.seed)
        ckpt_root = os.path.join(root, "ckpt")
        if args.chips == 4:
            out = four_chips(corpus, cfg, ckpt_root, devices[:4],
                             seed=args.seed)
            reading("data-parallel losses", out["losses"])
            reading("one-device losses", out["reference_losses"])
        else:
            reading("first batch max |kernel - numpy|",
                    check_first_batch(corpus, cfg))
            train_step = A.make_train_step(cfg)
            first = train(corpus, cfg, ckpt_root, train_step, seed=args.seed)
            resumed = resume(corpus, cfg, ckpt_root, train_step, first,
                             seed=args.seed)
            compute = first.timer.compute_s
            steady = statistics.median(compute[1:])
            reading("first step s (trace + compile + run)", compute[0])
            reading("compile s (first step - median later step)",
                    compute[0] - steady)
            reading("step s after the first", compute[1:])
            reading("data wait s per step", first.timer.data_wait_s)
            reading("blocked s per save", first.timer.checkpoint_s)
            reading("steps taken, resumed at, taken after resume",
                    (len(first.history), resumed.recovered_step,
                     len(resumed.history)))
            reading("losses", [h["loss"] for h in first.history
                               + resumed.history])

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
