"""The async two-tier engine (``asyncbb``): snapshot-only blocking, both
tiers land.

Acceptance criteria covered here:

* ``save()`` blocks for the host snapshot only — on the simulated
  optane/hdd pair the training-thread blocked seconds are ≤ 0.5x the plain
  burst buffer's (which pays the full fast-tier write);
* both tiers end up with every checkpoint, bit-identical, and the handle
  settles exactly when the *fast* tier has committed (the step is then
  restorable — the preemption-save contract);
* drain bookkeeping (``_pending``/``_drained``) stays bounded over long
  runs; error reporting is exactly-once across ``wait()``/``close()``;
* trainer integration: the step loop never blocks past the snapshot, and a
  preemption save is fast-tier durable before ``run()`` returns.
"""
import threading
import time

import numpy as np
import pytest

from repro.core.checkpoint import CheckpointSaver
from repro.core.engine import CheckpointEngine, SaveHandle
from repro.core.faults import FaultInjected, FaultyStorage
from repro.core.recovery import CheckpointManager


def asyncbb(fast, slow, prefix, **kw):
    return CheckpointEngine((fast, slow), prefix, snapshot="async", **kw)


def big_tree(mb=2, seed=0):
    rng = np.random.default_rng(seed)
    return {"w": rng.normal(size=(mb * 1024 * 256,)).astype(np.float32)}


class TestAsyncBurstBuffer:
    def test_roundtrip_both_tiers(self, fast_slow_storage):
        fast, slow = fast_slow_storage
        abb = CheckpointManager(slow, "ckpt/m", engine="asyncbb",
                                fast_storage=fast, n_shards=2,
                                drain_streams=4, drain_chunk=256 << 10)
        t = big_tree(1)
        h = abb.save(7, t)
        assert isinstance(h, SaveHandle) and h.step == 7
        r = h.result()   # fast tier committed
        assert r.step == 7 and r.n_bytes > 0
        assert abb.fast_saver.latest_step() == 7  # restorable already
        abb.wait()       # slow tier drained
        for saver in (CheckpointSaver(fast, "ckpt/m"),
                      CheckpointSaver(slow, "ckpt/m")):
            out = saver.restore_pytree(t)
            np.testing.assert_array_equal(out["w"], t["w"])
        out = abb.restore_pytree(t)
        np.testing.assert_array_equal(out["w"], t["w"])
        abb.close()

    def test_blocked_half_of_plain_burst_buffer(self, fast_slow_storage):
        """The tentpole number: bb pays the fast-tier write; asyncbb pays
        the snapshot only."""
        fast, slow = fast_slow_storage
        t = big_tree(8)
        bb = CheckpointEngine((fast, slow), "bb/m")
        bb.save(1, t)
        bb_blocked = bb.blocked_s[0]
        bb.wait()
        bb.close()

        abb = asyncbb(fast, slow, "abb/m")
        h = abb.save(1, t)
        abb_blocked = abb.blocked_s[0]
        h.result()
        abb.wait()
        abb.close()
        assert abb_blocked < bb_blocked * 0.5, (
            f"asyncbb blocked {abb_blocked:.3f}s !< "
            f"bb blocked {bb_blocked:.3f}s * 0.5")

    def test_saves_commit_in_order_on_both_tiers(self, fast_slow_storage):
        fast, slow = fast_slow_storage
        abb = asyncbb(fast, slow, "ckpt/m", max_pending=2)
        t = big_tree(1)
        for s in (10, 20, 30):
            abb.save(s, t)
        abb.wait()
        assert CheckpointSaver(fast, "ckpt/m").latest_step() == 30
        assert CheckpointSaver(slow, "ckpt/m").all_steps() == [10, 20, 30]
        abb.close()

    def test_fast_tier_cleanup_and_bounded_bookkeeping(self,
                                                       fast_slow_storage):
        """Satellite regression: ``_pending``/``_drained`` must not grow
        with the number of saves, and old staged steps are evicted."""
        fast, slow = fast_slow_storage
        abb = CheckpointManager(slow, "ckpt/m", engine="asyncbb",
                                fast_storage=fast, keep_last=8)
        t = big_tree(1)
        for s in range(1, 7):
            abb.save(s, t)
        abb.wait()
        engine = abb.engine
        with engine._pending_lock:
            assert engine._pending == [] and engine._drained == set()
        files = fast.listdir("ckpt")
        assert not any(f.startswith("m-1.data") for f in files)
        assert any(f.startswith("m-6.data") for f in files)
        abb.close()

    def test_backpressure_bounds_inflight_snapshots(self, fast_slow_storage):
        fast, slow = fast_slow_storage
        abb = asyncbb(fast, slow, "ckpt/m", max_pending=1)
        t = big_tree(4)
        abb.save(1, t)          # occupies the single slot while staging
        t0 = time.monotonic()
        abb.save(2, t)          # must wait for save 1 to finish staging
        second_blocked = time.monotonic() - t0
        # the second save's blocked time includes (most of) save 1's stage
        assert second_blocked > abb.blocked_s[0] * 2
        abb.wait()
        abb.close()

    def test_stage_error_reported_once(self, tmp_storage):
        import tempfile

        faulty_fast = FaultyStorage(tmp_storage)
        with tempfile.TemporaryDirectory() as d2:
            from repro.core.storage import NativeStorage

            slow = NativeStorage(d2)
            abb = asyncbb(faulty_fast, slow, "ckpt/m")
            t = big_tree(1)
            abb.save(1, t)
            abb.wait()
            faulty_fast.fail_after(0)
            h = abb.save(2, t)
            assert isinstance(h.exception(), FaultInjected)
            with pytest.raises(FaultInjected):
                abb.wait()   # observed via the handle, but wait still owes it
            faulty_fast.heal()
            abb.save(3, t)
            abb.wait()       # stale step-2 error must not resurface
            assert CheckpointSaver(slow, "ckpt/m").latest_step() == 3
            abb.close()      # already-delivered error: close stays quiet

    def test_drain_error_surfaces_through_wait(self, tmp_storage):
        import tempfile

        with tempfile.TemporaryDirectory() as d2:
            from repro.core.storage import NativeStorage

            faulty_slow = FaultyStorage(NativeStorage(d2))
            abb = asyncbb(tmp_storage, faulty_slow, "ckpt/m")
            t = big_tree(1)
            abb.save(1, t)
            abb.wait()
            faulty_slow.fail_after(0)
            h = abb.save(2, t)
            assert h.result().step == 2      # fast tier is fine
            with pytest.raises(FaultInjected):
                abb.wait()                   # the drain died
            faulty_slow.heal()
            # fast tier kept the step even though the slow tier lost it
            assert CheckpointSaver(tmp_storage, "ckpt/m").latest_step() == 2
            assert CheckpointSaver(faulty_slow, "ckpt/m").latest_step() == 1
            abb.close()

    def test_save_after_close_raises(self, fast_slow_storage):
        fast, slow = fast_slow_storage
        abb = asyncbb(fast, slow, "ckpt/m")
        abb.close()
        with pytest.raises(RuntimeError):
            abb.save(1, big_tree(1))


class TestTrainerIntegration:
    def _trainer(self, checkpointer):
        from repro.train.trainer import Trainer

        def train_step(st, batch):
            return {**st, "step": st["step"] + 1}, {"loss": 0.0}

        data = iter([np.zeros(2, np.float32)] * 64)
        return Trainer(
            train_step, {"w": np.ones(1024, np.float32), "step": np.int32(0)},
            data, checkpointer=checkpointer, ckpt_every=2, resume=False,
        )

    def test_step_loop_never_blocks_past_snapshot(self, fast_slow_storage):
        fast, slow = fast_slow_storage
        abb = asyncbb(fast, slow, "ckpt/m")
        tr = self._trainer(abb)
        tr.run(5)
        assert len(abb.blocked_s) == 2      # saves at steps 2 and 4
        assert all(b < 0.05 for b in tr.timer.checkpoint_s), (
            tr.timer.checkpoint_s)
        tr.wait_for_checkpoints()
        assert tr.report()["pending_async_saves"] == 0
        assert CheckpointSaver(slow, "ckpt/m").latest_step() == 4
        abb.close()

    def test_preemption_save_fast_tier_durable(self, fast_slow_storage):
        fast, slow = fast_slow_storage
        abb = asyncbb(fast, slow, "ckpt/m")
        tr = self._trainer(abb)
        tr.run(2)
        tr.request_stop()
        tr.run(3)   # stops at the boundary, blocking on the final save
        # handle.result() settles on fast-tier commit: restorable now
        assert CheckpointSaver(fast, "ckpt/m").latest_step() == tr.step
        tr.wait_for_checkpoints()
        assert CheckpointSaver(slow, "ckpt/m").latest_step() == tr.step
        abb.close()
