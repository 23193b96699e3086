"""Trainer loop: restart, preemption, straggler monitor, ckpt integration."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.recovery import CheckpointManager
from repro.train.trainer import Trainer


def toy_setup():
    """A tiny quadratic 'model' so steps are fast and deterministic."""
    state = {"params": {"w": jnp.array([4.0, -2.0])}, "step": jnp.int32(0)}

    def train_step(state, batch):
        def loss(p):
            return jnp.sum((p["w"] - batch) ** 2)
        g = jax.grad(loss)(state["params"])
        new = {
            "params": {"w": state["params"]["w"] - 0.1 * g["w"]},
            "step": state["step"] + 1,
        }
        return new, {"loss": loss(state["params"])}

    def data():
        while True:
            yield jnp.zeros(2)

    return state, train_step, data()


class TestTrainerLoop:
    def test_runs_and_records(self):
        state, step_fn, data = toy_setup()
        tr = Trainer(step_fn, state, data)
        hist = tr.run(5)
        assert len(hist) == 5
        assert hist[-1]["loss"] < hist[0]["loss"]
        assert tr.step == 5
        rep = tr.report()
        assert rep["steps"] == 5 and "data_wait_frac" in rep

    def test_checkpoint_every_k(self, tmp_storage):
        state, step_fn, data = toy_setup()
        ck = CheckpointManager(tmp_storage, "ckpt/m", keep_last=10)
        tr = Trainer(step_fn, state, data, checkpointer=ck, ckpt_every=2)
        tr.run(6)
        assert ck.saver.all_steps() == [2, 4, 6]

    def test_restart_resumes_from_checkpoint(self, tmp_storage):
        state, step_fn, data = toy_setup()
        ck = CheckpointManager(tmp_storage, "ckpt/m")
        tr = Trainer(step_fn, state, data, checkpointer=ck, ckpt_every=3)
        tr.run(3)
        w_after_3 = np.asarray(jax.device_get(tr.state["params"]["w"]))

        # "crash" and restart from a fresh initial state
        state2, step_fn2, data2 = toy_setup()
        ck2 = CheckpointManager(tmp_storage, "ckpt/m")
        tr2 = Trainer(step_fn2, state2, data2, checkpointer=ck2, resume=True)
        assert tr2.step == 3
        np.testing.assert_allclose(
            np.asarray(jax.device_get(tr2.state["params"]["w"])), w_after_3)

    def test_preemption_checkpoints_and_stops(self, tmp_storage):
        state, step_fn, data = toy_setup()
        ck = CheckpointManager(tmp_storage, "ckpt/m")
        tr = Trainer(step_fn, state, data, checkpointer=ck)
        tr.request_stop()
        tr.run(100)
        assert tr.step == 1          # stopped at first boundary
        assert ck.latest_step() == 1  # preemption checkpoint written

    def test_straggler_monitor_flags_slow_input(self):
        import time

        state, step_fn, _ = toy_setup()

        def slow_data():
            while True:
                time.sleep(0.03)
                yield jnp.zeros(2)

        tr = Trainer(step_fn, state, slow_data(), straggler_threshold=0.2)
        tr.run(5)
        rep = tr.report()
        assert rep["straggler_suspect"], rep
