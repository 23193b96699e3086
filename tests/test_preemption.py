"""Preemption-safe checkpoint lifecycle (PR 10 tentpole).

* ``preempt(deadline_s)`` on every engine: stop new saves, cancel queued
  snapshots except the newest, promote that one to its durability tier
  within the deadline, and record what was abandoned.
* Drain watchdog: a drain stream wedged in a stuck slow-tier op (the
  :meth:`FaultyStorage.hang` model) is detected, aborted, its chunk
  re-queued on a fresh stream — and the save still completes; a chunk
  that stalls on every attempt surfaces :class:`DrainStallError`.
* Trainer integration: ``Trainer.preempt(deadline_s)`` rides the stop
  path, records the :class:`PreemptionReport`, and a restart resumes from
  the preempted step — including a step that was staged on the fast tier
  but never drained.
"""
import tempfile
import time

import numpy as np
import pytest

from repro.core.checkpoint import CheckpointSaver
from repro.core.engine import CheckpointEngine, DrainStallError
from repro.core.faults import FaultyStorage
from repro.core.recovery import (ABANDONED, COMMITTED, STAGED,
                                 CheckpointManager)
from repro.core.storage import NativeStorage

PREFIX = "ckpt/m"


def tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": rng.normal(size=(256,)).astype(np.float32),
            "step": np.int64(seed)}


def wait_until(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() >= deadline:
            return False
        time.sleep(0.005)
    return True


# ---------------------------------------------------------------------------
# engine-level preempt()
# ---------------------------------------------------------------------------
class TestDirectPreempt:
    def test_trivially_durable_and_rejects_later_saves(self, tmp_storage):
        ck = CheckpointEngine((tmp_storage,), PREFIX)
        ck.save(1, tree(1))
        rep = ck.preempt(deadline_s=1.0)
        assert rep.committed_step == 1
        assert rep.abandoned_steps == [] and rep.deadline_met
        with pytest.raises(RuntimeError):
            ck.save(2, tree(2))


class TestBurstBufferPreempt:
    def test_staged_steps_already_durable(self, tmp_storage):
        with tempfile.TemporaryDirectory() as d2:
            bb = CheckpointEngine((tmp_storage, NativeStorage(d2)), PREFIX)
            bb.save(1, tree(1))
            rep = bb.preempt(deadline_s=1.0)
            assert rep.committed_step == 1 and rep.deadline_met
            with pytest.raises(RuntimeError):
                bb.save(2, tree(2))
            bb.wait()
            bb.close()


class TestAsyncPreempt:
    def test_promotes_newest_cancels_older_queued(self, tmp_storage):
        faulty = FaultyStorage(tmp_storage)
        ac = CheckpointEngine((faulty,), PREFIX, snapshot="async",
                              max_pending=3)
        trees = {s: tree(s) for s in (1, 2, 3)}
        # step 1's first data write wedges for a while: steps 2 and 3 queue
        # behind it on the single writer thread
        faulty.hang(on=".data-", duration=0.3)
        h1 = ac.save(1, trees[1])
        h2 = ac.save(2, trees[2])
        h3 = ac.save(3, trees[3])
        assert wait_until(lambda: faulty.hung_now == 1)
        rep = ac.preempt(deadline_s=30.0)
        # 2 was queued-not-started -> cancelled; 3 promoted and committed;
        # 1 was already running -> ran to completion (not abandoned)
        assert rep.abandoned_steps == [2]
        assert rep.deadline_met
        assert rep.committed_step == 3
        assert h2.cancelled() and not h1.cancelled() and not h3.cancelled()
        assert rep.elapsed_s <= 30.0
        with pytest.raises(RuntimeError):
            ac.save(4, tree(4))
        ac.close()
        saver = CheckpointSaver(tmp_storage, PREFIX)
        out = saver.restore_pytree(trees[3])
        np.testing.assert_array_equal(out["w"], trees[3]["w"])

    def test_deadline_miss_reports_abandoned(self, tmp_storage):
        faulty = FaultyStorage(tmp_storage)
        ac = CheckpointEngine((faulty,), PREFIX, snapshot="async",
                              max_pending=2)
        faulty.hang(on=".data-")  # forever, until released
        h1 = ac.save(1, tree(1))
        assert wait_until(lambda: faulty.hung_now == 1)
        t0 = time.monotonic()
        rep = ac.preempt(deadline_s=0.2)
        elapsed = time.monotonic() - t0
        assert not rep.deadline_met
        assert rep.abandoned_steps == [1]
        assert rep.committed_step is None  # nothing ever landed
        assert 0.15 <= elapsed < 5.0  # waited the budget, not forever
        # the promoted save was left running, not killed: once the device
        # un-wedges it commits as normal and close() is clean
        faulty.heal()
        assert wait_until(h1.done)
        ac.close()
        assert CheckpointSaver(tmp_storage, PREFIX).latest_step() == 1

    def test_cancelled_save_releases_backpressure_slot(self, tmp_storage):
        faulty = FaultyStorage(tmp_storage)
        ac = CheckpointEngine((faulty,), PREFIX, snapshot="async",
                              max_pending=2)
        faulty.hang(on=".data-")
        ac.save(1, tree(1))
        assert wait_until(lambda: faulty.hung_now == 1)
        ac.save(2, tree(2))  # fills the second (and last) pending slot
        rep = ac.preempt(deadline_s=0.1)  # cancels 2, times out on... no:
        # newest is 2 -> 2 is promoted; nothing older is queued-unstarted
        # except none (1 is running).  2 can't start behind wedged 1 ->
        # deadline miss; its cancel-or-timeout must not deadlock the sema.
        assert not rep.deadline_met and 2 in rep.abandoned_steps
        faulty.heal()
        ac.close()


class TestAsyncBurstBufferPreempt:
    def test_promote_to_fast_tier_within_deadline(self):
        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            fast_inner, slow = NativeStorage(d1), NativeStorage(d2)
            fast = FaultyStorage(fast_inner)
            abb = CheckpointEngine((fast, slow), PREFIX, snapshot="async",
                                   max_pending=3)
            trees = {s: tree(s) for s in (1, 2, 3)}
            fast.hang(on=".data-", duration=0.3)  # wedge step 1's stage
            abb.save(1, trees[1])
            abb.save(2, trees[2])
            abb.save(3, trees[3])
            assert wait_until(lambda: fast.hung_now == 1)
            rep = abb.preempt(deadline_s=30.0)
            assert rep.abandoned_steps == [2]
            assert rep.deadline_met and rep.committed_step == 3
            # the promoted step is durable at the preemption (fast) tier
            # even if the node dies before any drain finishes
            fast_saver = CheckpointSaver(fast_inner, PREFIX)
            out = fast_saver.restore_pytree(trees[3], step=3)
            np.testing.assert_array_equal(out["w"], trees[3]["w"])
            abb.close()


# ---------------------------------------------------------------------------
# drain watchdog
# ---------------------------------------------------------------------------
class TestDrainWatchdog:
    """The watchdog, judged by its events, not by the wall clock.

    The stall timeout sits far above the transfer time of a healthy
    256-byte chunk, even on a host loaded with parallel test workers, so
    every stall counted is the one the test armed."""

    TIMEOUT = 1.0

    def _bb(self, fast, slow, prefix=PREFIX, **kw):
        kw.setdefault("drain_stall_timeout", self.TIMEOUT)
        kw.setdefault("drain_streams", 2)
        kw.setdefault("drain_chunk", 256)  # several chunks per shard
        return CheckpointEngine((fast, slow), prefix, **kw)

    def test_hung_stream_aborted_and_chunk_requeued(self, tmp_storage):
        with tempfile.TemporaryDirectory() as d2:
            slow_inner = NativeStorage(d2)
            slow = FaultyStorage(slow_inner)
            bb = self._bb(tmp_storage, slow)
            t = tree(1)
            # one data-chunk write wedges forever (one-shot: the re-queued
            # attempt on the replacement stream goes through)
            slow.hang(on=".data-")
            bb.save(1, t)
            bb.wait()
            # exactly the armed wedge: detected, its stream aborted, and
            # the re-queued chunk landed every byte
            assert bb.drain_stalls == 1 and bb.drain_aborts == 1
            assert slow.hung_ops == 1
            out = CheckpointSaver(slow_inner, PREFIX).restore_pytree(t)
            np.testing.assert_array_equal(out["w"], t["w"])
            slow.heal()  # un-park the leaked stream thread
            bb.close()

    def test_chunk_stalling_every_attempt_raises_drain_stall_error(
            self, tmp_storage):
        with tempfile.TemporaryDirectory() as d2:
            slow = FaultyStorage(NativeStorage(d2))
            bb = self._bb(tmp_storage, slow, drain_requeue_limit=1)
            slow.hang(on=".data-", repeat=True)  # every attempt wedges
            bb.save(1, tree(1))
            with pytest.raises(DrainStallError):
                bb.wait()
            assert bb.drain_stalls >= 2  # initial attempt + the re-queue
            slow.heal()
            bb.close()

    def test_healthy_drain_unaffected_by_watchdog(self, tmp_storage):
        with tempfile.TemporaryDirectory() as d2:
            slow = NativeStorage(d2)
            bb = self._bb(tmp_storage, slow)
            for s in (1, 2):
                bb.save(s, tree(s))
            bb.wait()
            assert bb.drain_stalls == 0 and bb.drain_aborts == 0
            assert CheckpointSaver(slow, PREFIX).latest_step() == 2
            bb.close()

    def test_watchdog_metrics_counters(self, tmp_storage):
        from repro import metrics

        prefix = "ckpt/watchdog-metrics"
        label = (("ckpt", prefix),)
        with tempfile.TemporaryDirectory() as d2:
            slow = FaultyStorage(NativeStorage(d2))
            bb = self._bb(tmp_storage, slow, prefix=prefix)
            slow.hang(on=".data-")
            # a registry of this test's own, the caller's restored after
            previous = metrics.get_registry()
            reg = metrics.set_registry(metrics.MetricsRegistry())
            try:
                bb.save(1, tree(1))
                bb.wait()
            finally:
                metrics.set_registry(previous)
            counters = reg.collect()["counters"]
            stalls = counters[metrics.render_name("ckpt.drain_stalls", label)]
            aborts = counters[metrics.render_name("ckpt.drain_aborts", label)]
            assert stalls == bb.drain_stalls == 1
            assert aborts == bb.drain_aborts == 1
            slow.heal()
            bb.close()


# ---------------------------------------------------------------------------
# fused manager + trainer integration
# ---------------------------------------------------------------------------
def make_stream_setup():
    """Deterministic fold state (same harness as test_recovery)."""
    consumed = []
    state = {"w": np.float64(0.0), "step": np.int64(0)}

    def step_fn(state, batch):
        b = np.float64(batch)
        consumed.append(float(b))
        return ({"w": state["w"] * np.float64(0.5) + b,
                 "step": state["step"] + np.int64(1)}, {"loss": b})

    return state, step_fn, consumed


def make_data_iter():
    from repro.core.dataset import Dataset, ResumableIterator

    return ResumableIterator(lambda ep: Dataset.from_tensor_slices(
        [np.float64(ep * 100 + i + 1) for i in range(8)]))


class TestTrainerPreemption:
    def _trainer(self, mgr, n_steps=0, **kw):
        from repro.train.trainer import Trainer

        state, step_fn, consumed = make_stream_setup()
        tr = Trainer(step_fn, state, make_data_iter(), checkpointer=mgr,
                     ckpt_every=2, **kw)
        return tr, consumed

    def test_preempt_records_report_and_restart_resumes(self):
        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            fast, slow = NativeStorage(d1), NativeStorage(d2)
            mgr = CheckpointManager(slow, PREFIX, engine="asyncbb",
                                    fast_storage=fast, keep_last=3)
            tr, consumed = self._trainer(mgr)
            tr.on_step = lambda step, m: (step == 3 and tr.preempt(10.0))
            tr.run(6)
            assert len(consumed) == 3  # stopped at the step-3 boundary
            rep = tr.preemption_report
            assert rep is not None and rep.deadline_met
            assert rep.committed_step == 3
            assert tr.report()["preemption"]["committed_step"] == 3
            mgr.wait()
            mgr.close()

            mgr2 = CheckpointManager(slow, PREFIX, engine="asyncbb",
                                     fast_storage=fast, keep_last=3)
            tr2, consumed2 = self._trainer(mgr2)
            assert tr2.recovered_step == 3
            tr2.run(3)
            # the resumed stream continues exactly where the preempted one
            # stopped: no sample skipped, none replayed
            assert consumed2 == [4.0, 5.0, 6.0]
            mgr2.wait()
            mgr2.close()

    def test_restart_from_staged_not_drained_step(self):
        """The preemption-restart contract: a step durable only on the
        fast tier (drain wedged at preemption time) must be restorable."""
        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            fast = NativeStorage(d1)
            slow = FaultyStorage(NativeStorage(d2))
            mgr = CheckpointManager(slow, PREFIX, engine="asyncbb",
                                    fast_storage=fast, keep_last=3)
            slow.hang(on=".data-", repeat=True)  # no drain ever commits
            tr, consumed = self._trainer(mgr)
            tr.on_step = lambda step, m: (step == 4 and tr.preempt(10.0))
            tr.run(8)
            rep = tr.preemption_report
            assert rep is not None and rep.committed_step == 4
            assert mgr.step_states()[4] == STAGED  # never COMMITTED
            assert mgr.latest_valid() == 4  # restorable via the fast tier

            mgr2 = CheckpointManager(slow, PREFIX, engine="asyncbb",
                                     fast_storage=fast, keep_last=3)
            tr2, consumed2 = self._trainer(mgr2)
            assert tr2.recovered_step == 4
            # one step (below ckpt_every): node 1's wedged drains are still
            # parked, so only its manager ever publishes to the slow tier
            tr2.run(1)
            assert consumed2 == [5.0]
            mgr2.close()
            slow.heal()   # un-wedge node 1's drains
            mgr.wait()    # they commit (and run deferred GC) cleanly
            assert mgr.step_states()[4] == COMMITTED
            mgr.close()

    def test_direct_engine_stop_path_still_works(self, tmp_storage):
        mgr = CheckpointManager(tmp_storage, PREFIX, keep_last=3)
        tr, consumed = self._trainer(mgr)
        tr.on_step = lambda step, m: (step == 3 and tr.request_stop())
        tr.run(6)
        rep = tr.preemption_report
        assert rep is not None and rep.committed_step == 3
        assert mgr.latest_valid() == 3
        mgr.close()

    def test_abandoned_steps_marked_in_lifecycle(self):
        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            fast = FaultyStorage(NativeStorage(d1))
            slow = NativeStorage(d2)
            mgr = CheckpointManager(slow, PREFIX, engine="asyncbb",
                                    fast_storage=fast, keep_last=5,
                                    max_pending=3)
            fast.hang(on=".data-", duration=0.3)
            for s in (1, 2, 3):
                mgr.save(s, tree(s))
            assert wait_until(lambda: fast.hung_now == 1)
            rep = mgr.preempt(10.0)
            assert rep.abandoned_steps == [2]
            assert mgr.abandoned_steps == [2]
            assert mgr.step_states()[2] == ABANDONED
            assert mgr.step_states()[3] in (STAGED, COMMITTED)
            mgr.close()
