"""Fault injection & crash consistency: the atomicity guarantees, proven.

Every checkpointer documents "a crash mid-save leaves the previous
checkpoint restorable" — these tests kill the storage at exact points
(before the commit marker, on the marker itself, during the drain) with
:class:`FaultyStorage` and assert the previous step survives on every path:
CheckpointSaver and the checkpoint engine's ``async``, ``bb`` (both tiers)
and ``asyncbb`` presets — the last under *every* write-op injection point
of its save/drain path, and under the torn-write and reordered-fsync crash
models, not just clean op-boundary kills.
"""
import tempfile
import threading
import time

import numpy as np
import pytest

from repro.core.checkpoint import CheckpointSaver
from repro.core.engine import CheckpointEngine
from repro.core.faults import FaultInjected, FaultyStorage
from repro.core.recovery import CheckpointManager
from repro.core.storage import NativeStorage


def tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": rng.normal(size=(64, 64)).astype(np.float32),
        "b": rng.normal(size=(64,)).astype(np.float32),
        "step": np.int32(seed),
    }


class TestFaultyStorage:
    def test_fail_after_counts_writes(self, tmp_storage):
        f = FaultyStorage(tmp_storage).fail_after(2)
        f.write_file("a", b"1")
        f.write_file("b", b"2")
        with pytest.raises(FaultInjected):
            f.write_file("c", b"3")
        assert tmp_storage.exists("a") and tmp_storage.exists("b")
        assert not tmp_storage.exists("c")  # fault fires before the write

    def test_sticky_failure_models_dead_device(self, tmp_storage):
        f = FaultyStorage(tmp_storage).fail_after(0)
        with pytest.raises(FaultInjected):
            f.write_file("a", b"1")
        with pytest.raises(FaultInjected):  # still dead
            f.write_file("b", b"2")
        f.heal()
        f.write_file("c", b"3")
        assert f.read_file("c") == b"3"

    def test_fail_on_path_substring(self, tmp_storage):
        f = FaultyStorage(tmp_storage).fail_on("marker")
        f.write_file("data-0", b"x")
        with pytest.raises(FaultInjected):
            f.write_file("the/marker", b"y")

    def test_read_faults(self, tmp_storage):
        tmp_storage.write_file("a", b"payload")
        f = FaultyStorage(tmp_storage).fail_after(0, ops=("read",))
        f.write_file("b", b"ok")  # writes unaffected
        with pytest.raises(FaultInjected):
            f.read_file("a")
        with pytest.raises(FaultInjected):
            f.read_range("a", 0, 3)


class TestSaverCrashConsistency:
    def test_crash_on_data_shard_keeps_previous(self, tmp_storage):
        faulty = FaultyStorage(tmp_storage)
        saver = CheckpointSaver(faulty, "ckpt/m", n_shards=2)
        t1 = tree(1)
        saver.save(1, t1)
        faulty.fail_after(0)  # first write of the next save dies
        with pytest.raises(FaultInjected):
            saver.save(2, tree(2))
        faulty.heal()
        assert saver.latest_step() == 1  # marker never moved
        out = saver.restore_pytree(t1)
        np.testing.assert_array_equal(out["w"], t1["w"])

    def test_crash_on_marker_write_keeps_previous(self, tmp_storage):
        faulty = FaultyStorage(tmp_storage)
        saver = CheckpointSaver(faulty, "ckpt/m")
        t1 = tree(1)
        saver.save(1, t1)
        faulty.fail_on("ckpt/checkpoint")  # kill exactly the commit
        with pytest.raises(FaultInjected):
            saver.save(2, tree(2))
        faulty.heal()
        # step-2 data landed but was never committed: previous still latest
        assert saver.latest_step() == 1
        out = saver.restore_pytree(t1)
        np.testing.assert_array_equal(out["w"], t1["w"])

    def test_crash_with_parallel_shard_writes(self, tmp_storage):
        """A failing shard aborts the whole save before the marker, even
        with the other shards written concurrently."""
        faulty = FaultyStorage(tmp_storage)
        saver = CheckpointSaver(faulty, "ckpt/m", n_shards=4, io_threads=4)
        t1 = tree(1)
        saver.save(1, t1)
        faulty.fail_after(2)  # third shard write of the next save dies
        with pytest.raises(FaultInjected):
            saver.save(2, tree(2))
        faulty.heal()
        assert saver.latest_step() == 1
        out = saver.restore_pytree(t1)
        np.testing.assert_array_equal(out["w"], t1["w"])


class TestAsyncCrashConsistency:
    def test_wait_surfaces_background_write_error(self, tmp_storage):
        faulty = FaultyStorage(tmp_storage)
        ac = CheckpointManager(faulty, "ckpt/m", engine="async")
        t1 = tree(1)
        ac.save(1, t1).result()
        faulty.fail_after(0)
        handle = ac.save(2, tree(2))  # snapshot succeeds; write will die
        assert isinstance(handle.exception(), FaultInjected)
        with pytest.raises(FaultInjected):
            ac.wait()
        faulty.heal()
        assert ac.latest_step() == 1
        out = ac.restore_pytree(t1)
        np.testing.assert_array_equal(out["w"], t1["w"])
        ac.close()

    def test_error_reported_once_not_resurfaced_forever(self, tmp_storage):
        """After a failed save is reported by wait(), a healed device and
        successful later saves must make wait() clean again."""
        faulty = FaultyStorage(tmp_storage)
        ac = CheckpointManager(faulty, "ckpt/m", engine="async")
        faulty.fail_after(0)
        ac.save(1, tree(1))
        with pytest.raises(FaultInjected):
            ac.wait()
        faulty.heal()
        ac.save(2, tree(2))
        ac.wait()  # must not re-raise the stale step-1 error
        assert ac.latest_step() == 2
        ac.close()


class TestBurstBufferCrashConsistency:
    def test_fast_tier_crash_mid_save_keeps_previous(self, fast_slow_storage):
        fast, slow = fast_slow_storage
        faulty_fast = FaultyStorage(fast)
        bb = CheckpointManager(slow, "ckpt/m", engine="bb",
                               fast_storage=faulty_fast)
        t1 = tree(1)
        bb.save(1, t1)
        bb.wait()
        faulty_fast.fail_after(0)
        with pytest.raises(FaultInjected):
            bb.save(2, tree(2))
        faulty_fast.heal()
        bb.wait()
        # both tiers still restore step 1
        out = bb.restore_pytree(t1)
        np.testing.assert_array_equal(out["w"], t1["w"])
        slow_saver = CheckpointSaver(slow, "ckpt/m")
        assert slow_saver.latest_step() == 1
        out = slow_saver.restore_pytree(t1)
        np.testing.assert_array_equal(out["w"], t1["w"])
        bb.close()

    def test_drain_error_surfaces_in_wait_and_slow_tier_consistent(
            self, fast_slow_storage):
        fast, slow = fast_slow_storage
        faulty_slow = FaultyStorage(slow)
        bb = CheckpointEngine((fast, faulty_slow), "ckpt/m")
        t1 = tree(1)
        bb.save(1, t1)
        bb.wait()
        faulty_slow.fail_after(0)  # the next drain's first slow write dies
        bb.save(2, tree(2))        # staging to fast succeeds
        with pytest.raises(FaultInjected):
            bb.wait()
        faulty_slow.heal()
        # slow tier: marker still at step 1, and step 1 restores
        slow_saver = CheckpointSaver(slow, "ckpt/m")
        assert slow_saver.latest_step() == 1
        out = slow_saver.restore_pytree(t1)
        np.testing.assert_array_equal(out["w"], t1["w"])
        # fast tier holds the newer staged step — nothing was lost
        assert CheckpointSaver(fast, "ckpt/m").latest_step() == 2
        bb.close()

    def test_drain_marker_crash_keeps_slow_consistent(self, fast_slow_storage):
        """Die exactly on the slow-tier commit marker: files of the new step
        are on the slow tier but it must still restore the previous step."""
        fast, slow = fast_slow_storage
        faulty_slow = FaultyStorage(slow)
        bb = CheckpointEngine((fast, faulty_slow), "ckpt/m")
        t1 = tree(1)
        bb.save(1, t1)
        bb.wait()
        faulty_slow.fail_on("ckpt/checkpoint")
        bb.save(2, tree(2))
        with pytest.raises(FaultInjected):
            bb.wait()
        faulty_slow.heal()
        slow_saver = CheckpointSaver(slow, "ckpt/m")
        assert slow_saver.latest_step() == 1
        out = slow_saver.restore_pytree(t1)
        np.testing.assert_array_equal(out["w"], t1["w"])
        bb.close()


class TestTornWriteModel:
    """The torn-write fault mode itself: a frac prefix really lands."""

    def test_partial_prefix_lands_then_device_dies(self, tmp_storage):
        f = FaultyStorage(tmp_storage).torn_write(0.5, n_ops=1)
        f.write_file("a", b"x" * 100)                # op 0: clean
        with pytest.raises(FaultInjected):
            f.write_file("b", b"y" * 100)            # op 1: torn
        assert tmp_storage.size("b") == 50           # half the buffer landed
        with pytest.raises(FaultInjected):
            f.write_file("c", b"z")                  # sticky: device is dead
        assert not tmp_storage.exists("c")
        f.heal()
        f.write_file("c", b"z")
        assert f.read_file("c") == b"z"

    def test_torn_targets_path_substring(self, tmp_storage):
        f = FaultyStorage(tmp_storage).torn_write(0.25, on="marker")
        f.write_file("data-0", b"d" * 8)             # non-matching: clean
        with pytest.raises(FaultInjected):
            f.write_file("the/marker", b"m" * 8)
        assert tmp_storage.size("the/marker") == 2

    def test_invalid_fraction_rejected(self, tmp_storage):
        f = FaultyStorage(tmp_storage)
        with pytest.raises(ValueError):
            f.torn_write(1.0)
        with pytest.raises(ValueError):
            f.torn_write(-0.1)


class TestReorderedFsyncModel:
    """The volatile-cache durability model: unsynced writes are not durable,
    and the *last-issued* one can survive a crash while earlier ones don't
    (durability reordering — the adversary of unsynced commit markers)."""

    def test_crash_rolls_back_unsynced_writes(self, tmp_storage):
        f = FaultyStorage(tmp_storage).reordered_fsync()
        f.write_file("a", b"old")
        f.fsync_dir(".")                  # barrier: "a"=old is durable
        f.write_file("a", b"new")         # volatile overwrite
        f.write_file("b", b"data")        # volatile create
        lost = f.crash(keep="none")
        assert sorted(lost) == ["a", "b"]
        assert tmp_storage.read_file("a") == b"old"  # pre-image restored
        assert not tmp_storage.exists("b")           # never durable

    def test_sync_write_is_a_barrier(self, tmp_storage):
        f = FaultyStorage(tmp_storage).reordered_fsync()
        f.write_file("a", b"1")
        f.write_file("barrier", b"2", sync=True)  # flushes "a" too (syncfs)
        f.write_file("c", b"3")
        lost = f.crash(keep="none")
        assert lost == ["c"]
        assert tmp_storage.read_file("a") == b"1"
        assert tmp_storage.read_file("barrier") == b"2"

    def test_keep_last_spares_newest_volatile_write(self, tmp_storage):
        f = FaultyStorage(tmp_storage).reordered_fsync()
        f.write_file("data", b"D")
        f.write_file("marker", b"M")      # issued last, hit the medium first
        lost = f.crash(keep="last")
        assert lost == ["data"]
        assert not tmp_storage.exists("data")
        assert tmp_storage.read_file("marker") == b"M"

    def test_rename_does_not_launder_volatility(self, tmp_storage):
        """tmp+rename of an unsynced file: the rename target inherits the
        volatility and rolls back to *its* pre-image (the old marker)."""
        tmp_storage.write_file("marker", b"OLD")
        f = FaultyStorage(tmp_storage).reordered_fsync()
        f.write_file("marker.tmp", b"NEW")    # volatile
        f.rename("marker.tmp", "marker")
        lost = f.crash(keep="none")
        assert lost == ["marker"]
        assert tmp_storage.read_file("marker") == b"OLD"

    def test_crash_requires_arming(self, tmp_storage):
        with pytest.raises(RuntimeError):
            FaultyStorage(tmp_storage).crash()


class TestTornWriteCrashConsistency:
    def test_saver_torn_marker_keeps_previous(self, tmp_storage):
        """A torn write on the marker path must not corrupt the commit: the
        tmp+rename protocol leaves the old marker bytes untouched (a plain
        truncate-and-rewrite of the marker would leave corrupt JSON and
        make *both* steps unreachable)."""
        faulty = FaultyStorage(tmp_storage)
        saver = CheckpointSaver(faulty, "ckpt/m")
        t1 = tree(1)
        saver.save(1, t1)
        faulty.torn_write(0.5, on="ckpt/checkpoint")
        with pytest.raises(FaultInjected):
            saver.save(2, tree(2))
        faulty.heal()
        assert saver.latest_step() == 1   # old marker parses, still JSON
        out = saver.restore_pytree(t1)
        np.testing.assert_array_equal(out["w"], t1["w"])

    def test_saver_torn_data_shard_keeps_previous(self, tmp_storage):
        faulty = FaultyStorage(tmp_storage)
        saver = CheckpointSaver(faulty, "ckpt/m", n_shards=2)
        t1 = tree(1)
        saver.save(1, t1)
        faulty.torn_write(0.7, n_ops=0)   # first shard write of next save
        with pytest.raises(FaultInjected):
            saver.save(2, tree(2))
        faulty.heal()
        assert saver.latest_step() == 1
        out = saver.restore_pytree(t1)
        np.testing.assert_array_equal(out["w"], t1["w"])

    def test_bb_torn_drain_keeps_slow_consistent(self, fast_slow_storage):
        """A drain range-write torn mid-buffer leaves a half-written file on
        the slow tier — the un-advanced marker must keep it invisible."""
        fast, slow = fast_slow_storage
        faulty_slow = FaultyStorage(slow)
        bb = CheckpointEngine((fast, faulty_slow), "ckpt/m",
                              drain_streams=2, drain_chunk=4096)
        t1 = tree(1)
        bb.save(1, t1)
        bb.wait()
        faulty_slow.torn_write(0.5, n_ops=1)
        bb.save(2, tree(2))
        with pytest.raises(FaultInjected):
            bb.wait()
        faulty_slow.heal()
        slow_saver = CheckpointSaver(slow, "ckpt/m")
        assert slow_saver.latest_step() == 1
        out = slow_saver.restore_pytree(t1)
        np.testing.assert_array_equal(out["w"], t1["w"])
        # fast tier unaffected
        assert CheckpointSaver(fast, "ckpt/m").latest_step() == 2
        bb.close()


class TestReorderedFsyncCrashConsistency:
    def test_drain_marker_is_a_durability_barrier(self, tmp_storage):
        """Regression for the unsynced slow-tier marker: the drain's data
        writes are volatile (``write_range(sync=False)``), so if the commit
        marker were published without a sync barrier, durability reordering
        could persist the *marker* while the data it commits rolls back —
        a marker pointing at garbage.  The marker write must therefore be
        ``sync=True`` (flushing everything issued before it) *before* the
        rename publishes it: after ``crash(keep="last")`` the drained step
        must restore bit-identically."""
        with tempfile.TemporaryDirectory() as d2:
            faulty_slow = FaultyStorage(NativeStorage(d2)).reordered_fsync()
            bb = CheckpointEngine((tmp_storage, faulty_slow), "ckpt/m",
                                  drain_streams=2, drain_chunk=4096)
            t1 = tree(1)
            bb.save(1, t1)
            bb.wait()
            bb.close()
            faulty_slow.crash(keep="last")  # power loss after drain "done"
            slow_saver = CheckpointSaver(faulty_slow, "ckpt/m")
            assert slow_saver.latest_step() == 1
            out = slow_saver.restore_pytree(t1)
            np.testing.assert_array_equal(out["w"], t1["w"])

    def test_asyncbb_survives_crash_after_every_save(self, tmp_storage):
        """Same property through the fused engine, across multiple saves."""
        with tempfile.TemporaryDirectory() as d2:
            faulty_slow = FaultyStorage(NativeStorage(d2)).reordered_fsync()
            abb = CheckpointEngine(
                (tmp_storage, faulty_slow), "ckpt/m", snapshot="async",
                drain_streams=2, drain_chunk=4096)
            trees = {s: tree(s) for s in (1, 2, 3)}
            for s in (1, 2, 3):
                abb.save(s, trees[s])
            abb.wait()
            abb.close()
            faulty_slow.crash(keep="last")
            slow_saver = CheckpointSaver(faulty_slow, "ckpt/m")
            latest = slow_saver.latest_step()
            assert latest == 3
            out = slow_saver.restore_pytree(trees[latest])
            np.testing.assert_array_equal(out["w"], trees[latest]["w"])


class TestAsyncBBInjectionSweep:
    """Torn-write injection at *every* write op of the async burst buffer's
    save/drain path, on each tier: whatever lands half-written, a restorable
    step must survive on every tier that has a marker."""

    PREFIX = "ckpt/m"

    def _make(self, fast_dir, slow_dir):
        fast = FaultyStorage(NativeStorage(fast_dir))
        slow = FaultyStorage(NativeStorage(slow_dir))
        abb = CheckpointEngine(
            (fast, slow), self.PREFIX, snapshot="async", n_shards=2,
            drain_streams=2, drain_chunk=4096)
        return fast, slow, abb

    def _count_step2_write_ops(self):
        """Clean run: how many write ops does saving step 2 issue per tier?"""
        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            fast, slow, abb = self._make(d1, d2)
            abb.save(1, tree(1))
            abb.wait()
            f0 = sum(1 for op, _, _ in fast.op_log if op.startswith("write")
                     or op == "append_file")
            s0 = sum(1 for op, _, _ in slow.op_log if op.startswith("write")
                     or op == "append_file")
            abb.save(2, tree(2))
            abb.wait()
            abb.close()
            f1 = sum(1 for op, _, _ in fast.op_log if op.startswith("write")
                     or op == "append_file")
            s1 = sum(1 for op, _, _ in slow.op_log if op.startswith("write")
                     or op == "append_file")
        return f1 - f0, s1 - s0

    def _assert_tier_restorable(self, storage, trees):
        """The tier's marker must point at a step that restores
        bit-identically to what was saved."""
        saver = CheckpointSaver(storage, self.PREFIX)
        step = saver.latest_step()
        assert step in trees, f"marker points at unknown step {step}"
        out = saver.restore_pytree(trees[step])
        np.testing.assert_array_equal(out["w"], trees[step]["w"])
        return step

    def test_every_injection_point(self):
        n_fast, n_slow = self._count_step2_write_ops()
        assert n_fast >= 4 and n_slow >= 4  # shards+index+meta+marker ranges
        trees = {1: tree(1), 2: tree(2)}

        for tier_name, n_ops in (("fast", n_fast), ("slow", n_slow)):
            for k in range(n_ops):
                with tempfile.TemporaryDirectory() as d1, \
                        tempfile.TemporaryDirectory() as d2:
                    fast, slow, abb = self._make(d1, d2)
                    abb.save(1, trees[1])
                    abb.wait()
                    target = fast if tier_name == "fast" else slow
                    target.torn_write(0.5, n_ops=k)
                    abb.save(2, trees[2])
                    with pytest.raises(FaultInjected):
                        abb.wait()
                    target.heal()
                    try:
                        abb.close()
                    except FaultInjected:
                        pass  # a second failure from the same cascade
                    ctx = f"tier={tier_name}, injection point {k}/{n_ops}"
                    # the un-injected fast tier always commits step 2; an
                    # injected tier must still restore *a* step (usually 1)
                    if tier_name == "fast":
                        self._assert_tier_restorable(fast, trees)
                        # stage died -> nothing was drained for step 2
                        assert CheckpointSaver(
                            slow, self.PREFIX).latest_step() == 1, ctx
                    else:
                        assert CheckpointSaver(
                            fast, self.PREFIX).latest_step() == 2, ctx
                        step = self._assert_tier_restorable(slow, trees)
                        assert step == 1, ctx  # marker never advanced


class TestHangModel:
    """The stuck-op fault: the op blocks (bytes land on release), nothing
    raises — the model drain watchdogs exist to detect."""

    def test_hang_blocks_then_released_op_completes(self, tmp_storage):
        f = FaultyStorage(tmp_storage).hang(n_ops=0)
        done = threading.Event()

        def writer():
            f.write_file("a", b"payload")  # wedges here
            done.set()

        t = threading.Thread(target=writer, daemon=True)
        t.start()
        deadline = time.monotonic() + 5.0
        while f.hung_now == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert f.hung_now == 1 and f.hung_ops == 1
        assert not done.is_set()
        assert not tmp_storage.exists("a")  # nothing raised, nothing landed
        f.release_hung()
        assert done.wait(5.0)
        assert tmp_storage.read_file("a") == b"payload"  # bytes land on release
        assert f.hung_now == 0

    def test_hang_duration_self_releases(self, tmp_storage):
        f = FaultyStorage(tmp_storage).hang(n_ops=0, duration=0.05)
        t0 = time.monotonic()
        f.write_file("a", b"x")
        assert time.monotonic() - t0 >= 0.05
        assert tmp_storage.read_file("a") == b"x"
        assert f.hung_ops == 1

    def test_hang_is_one_shot_unless_repeat(self, tmp_storage):
        f = FaultyStorage(tmp_storage).hang(n_ops=0, duration=0.02)
        f.write_file("a", b"1")
        t0 = time.monotonic()
        f.write_file("b", b"2")  # disarmed: no stall
        assert time.monotonic() - t0 < 0.02
        assert f.hung_ops == 1
        f.hang(n_ops=0, duration=0.02, repeat=True)
        f.write_file("c", b"3")
        f.write_file("d", b"4")
        assert f.hung_ops == 3  # both tripped while armed

    def test_hang_on_path_substring_and_op_counting(self, tmp_storage):
        f = FaultyStorage(tmp_storage).hang(on="marker", duration=0.05)
        t0 = time.monotonic()
        f.write_file("data-0", b"x")  # path doesn't match: no stall
        assert time.monotonic() - t0 < 0.05
        f.write_file("the/marker", b"y")
        assert time.monotonic() - t0 >= 0.05
        f.hang(n_ops=2, duration=0.03)
        t1 = time.monotonic()
        f.write_file("p", b"1")
        f.write_file("q", b"2")  # two ops let through
        assert time.monotonic() - t1 < 0.03
        f.write_file("r", b"3")  # the third trips
        assert time.monotonic() - t1 >= 0.03

    def test_heal_unwedges_and_disarms(self, tmp_storage):
        f = FaultyStorage(tmp_storage).hang(n_ops=0, repeat=True)
        done = threading.Event()

        def writer():
            f.write_file("a", b"1")
            done.set()

        threading.Thread(target=writer, daemon=True).start()
        deadline = time.monotonic() + 5.0
        while f.hung_now == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        f.heal()
        assert done.wait(5.0)
        t0 = time.monotonic()
        f.write_file("b", b"2")  # disarmed: immediate
        assert time.monotonic() - t0 < 0.05

    def test_invalid_duration_rejected(self, tmp_storage):
        with pytest.raises(ValueError):
            FaultyStorage(tmp_storage).hang(duration=-1.0)
