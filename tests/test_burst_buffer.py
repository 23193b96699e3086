"""Burst buffer (paper §III-C/V-C): drain completeness, non-blocking, restore."""
import time

import numpy as np

from repro.core.checkpoint import CheckpointSaver
from repro.core.engine import CheckpointEngine
from repro.core.recovery import CheckpointManager


def big_tree(mb=2):
    rng = np.random.default_rng(0)
    return {"w": rng.normal(size=(mb * 1024 * 256,)).astype(np.float32)}


class TestBurstBuffer:
    def test_drain_completeness(self, fast_slow_storage):
        fast, slow = fast_slow_storage
        bb = CheckpointManager(slow, "ckpt/m", engine="bb",
                               fast_storage=fast, keep_last=5)
        t = big_tree(1)
        for s in (10, 20, 30):
            bb.save(s, t)
        bb.wait()
        slow_saver = CheckpointSaver(slow, "ckpt/m")
        assert slow_saver.all_steps() == [10, 20, 30]
        out = slow_saver.restore_pytree(t, step=30)
        np.testing.assert_array_equal(out["w"], t["w"])
        bb.close()

    def test_training_blocked_only_on_fast_tier(self, fast_slow_storage):
        """The blocked time must track the fast tier, not the slow one."""
        fast, slow = fast_slow_storage
        t = big_tree(8)
        direct_slow = CheckpointEngine((slow,), "d/m")
        direct_slow.save(1, t)
        slow_block = direct_slow.blocked_s[0]

        bb = CheckpointEngine((fast, slow), "bb/m")
        bb.save(1, t)
        bb_block = bb.blocked_s[0]
        bb.wait()
        bb.close()
        assert bb_block < slow_block * 0.6, (
            f"burst buffer blocked {bb_block:.3f}s vs direct-slow {slow_block:.3f}s"
        )

    def test_restore_prefers_fast_tier(self, fast_slow_storage):
        fast, slow = fast_slow_storage
        bb = CheckpointManager(slow, "ckpt/m", engine="bb", fast_storage=fast)
        t = big_tree(1)
        bb.save(7, t)
        bb.wait()
        out = bb.restore_pytree(t)
        np.testing.assert_array_equal(out["w"], t["w"])
        assert bb.latest_step() == 7
        bb.close()

    def test_restore_falls_back_to_slow(self, fast_slow_storage):
        fast, slow = fast_slow_storage
        bb = CheckpointManager(slow, "ckpt/m", engine="bb", fast_storage=fast)
        t = big_tree(1)
        bb.save(7, t)
        bb.wait()
        bb.close()
        # simulate losing the burst buffer (node-local NVM gone)
        fast.remove("ckpt")
        bb2 = CheckpointManager(slow, "ckpt/m", engine="bb",
                                fast_storage=fast)
        out = bb2.restore_pytree(t)
        np.testing.assert_array_equal(out["w"], t["w"])
        bb2.close()

    def test_fast_tier_cleanup(self, fast_slow_storage):
        """Old staged checkpoints are evicted from the small fast tier."""
        fast, slow = fast_slow_storage
        bb = CheckpointManager(slow, "ckpt/m", engine="bb",
                               fast_storage=fast, keep_last=5)
        t = big_tree(1)
        for s in (1, 2, 3):
            bb.save(s, t)
        bb.wait()
        assert bb.fast_saver.all_steps()  # marker intact
        files = fast.listdir("ckpt")
        # only the newest staged step retains data files
        assert not any(f.startswith("m-1.data") for f in files)
        assert any(f.startswith("m-3.data") for f in files)
        bb.close()


class TestDirect:
    def test_direct_interface(self, tmp_storage):
        d = CheckpointManager(tmp_storage, "ckpt/m", keep_last=2)
        t = big_tree(1)
        d.save(1, t)
        d.save(2, t)
        assert d.latest_step() == 2
        out = d.restore_pytree(t)
        np.testing.assert_array_equal(out["w"], t["w"])
        d.wait()  # no-op
        d.close()

    def test_close_discipline_matches_async_engines(self, tmp_storage):
        """PR-7 handle/close parity: close() is idempotent, save() after
        close() raises, and a save failure is delivered exactly once
        (inline) — never again via wait()/close()."""
        import pytest

        from repro.core.faults import FaultInjected, FaultyStorage

        faulty = FaultyStorage(tmp_storage)
        d = CheckpointManager(faulty, "ckpt/m")
        t = big_tree(1)
        d.save(1, t)
        faulty.fail_after(0)
        with pytest.raises(FaultInjected):  # delivered inline, once
            d.save(2, t)
        faulty.heal()
        d.wait()    # must NOT re-raise the already-delivered error
        d.close()   # likewise
        d.close()   # idempotent
        with pytest.raises(RuntimeError):
            d.save(3, t)
        assert d.latest_step() == 1  # failed save never committed
