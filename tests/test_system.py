"""End-to-end behaviour: the paper's full workload on CPU smoke scale.

Pipeline -> AlexNet training -> checkpointing through a burst buffer ->
restart — i.e. the complete mini-application of §III, miniaturized.
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALEXNET_SMOKE as ACFG
from repro.core import (
    CheckpointManager, Dataset, IOTracer, image_pipeline, make_storage,
)
from repro.core import records
from repro.core.microbench import run_microbench, thread_scaling_sweep
from repro.models import alexnet as A
from repro.train.trainer import Trainer


@pytest.fixture(scope="module")
def corpus():
    with tempfile.TemporaryDirectory() as d:
        st = make_storage("native", d)
        paths, labels = records.write_image_dataset(
            st, 48, mean_hw=(24, 24), n_classes=ACFG.n_classes, seed=3)
        yield st, paths, labels


class TestMicrobench:
    def test_reports_sane_bandwidth(self, corpus):
        st, paths, _ = corpus
        r = run_microbench(st, paths, threads=2, batch_size=8, out_hw=(16, 16))
        assert r.n_images == 48 and r.images_per_s > 0 and r.mb_per_s > 0

    def test_read_only_faster_than_preprocess(self, corpus):
        st, paths, _ = corpus
        rp = run_microbench(st, paths, threads=2, batch_size=8,
                            out_hw=(64, 64), preprocess=True)
        rr = run_microbench(st, paths, threads=2, batch_size=8,
                            preprocess=False)
        assert rr.images_per_s > rp.images_per_s  # paper Fig. 5 vs Fig. 4


class TestEndToEnd:
    def test_alexnet_train_with_pipeline_and_burst_buffer(self, corpus):
        st, paths, labels = corpus
        ds = image_pipeline(
            st, paths, labels, batch_size=8, num_parallel_calls=2,
            out_hw=(ACFG.in_hw, ACFG.in_hw), prefetch=1, repeat=True, seed=0)

        params = A.init_params(jax.random.PRNGKey(0), ACFG)
        state = {"params": params, "step": jnp.int32(0)}

        @jax.jit
        def train_step(state, batch):
            imgs, lbls = batch
            loss, g = jax.value_and_grad(
                lambda p: A.loss_fn(p, imgs, lbls, ACFG))(state["params"])
            new_p = jax.tree.map(lambda p, gg: p - 1e-3 * gg,
                                 state["params"], g)
            return {"params": new_p, "step": state["step"] + 1}, {"loss": loss}

        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            fast = make_storage("optane", d1, time_scale=0.02)
            slow = make_storage("hdd", d2, time_scale=0.02)
            bb = CheckpointManager(slow, "ckpt/alexnet", engine="bb",
                                   fast_storage=fast)
            tr = Trainer(train_step, state, iter(ds), checkpointer=bb,
                         ckpt_every=3)
            hist = tr.run(6)
            bb.wait()
            assert len(hist) == 6
            assert all(np.isfinite(h["loss"]) for h in hist)
            # both checkpoints landed on the slow tier
            from repro.core.checkpoint import CheckpointSaver
            assert CheckpointSaver(slow, "ckpt/alexnet").all_steps() == [3, 6]
            bb.close()

            # restart picks up where we left off
            state2 = {"params": A.init_params(jax.random.PRNGKey(1), ACFG),
                      "step": jnp.int32(0)}
            bb2 = CheckpointManager(slow, "ckpt/alexnet", engine="bb",
                                    fast_storage=fast)
            tr2 = Trainer(train_step, state2, iter(ds), checkpointer=bb2)
            assert tr2.step == 6
            bb2.close()
