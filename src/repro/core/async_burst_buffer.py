"""Async burst-buffer checkpointing: snapshot-only blocking + tiered drain.

The paper's burst buffer (§III-C, Fig. 9/10 — the 2.6x result) hides the
*slow-tier* cost of a checkpoint behind a fast tier, but training still
blocks for the full fast-tier write.  Its prefetcher result (§IV: complete
compute/input overlap) points at overlapping the write path entirely; this
module fuses the two engines so even the fast-tier stage leaves the
training thread:

1. **Snapshot** (blocking, :func:`repro.core.checkpoint.flatten_pytree`
   with ``copy=True``): the pytree is materialized in host memory —
   memory-bandwidth bound (GB/s), so training resumes after milliseconds.
2. **Stage** (background writer thread, in submission order): the normal
   sharded, atomic :meth:`CheckpointSaver.save_flat` to the *fast* tier
   (Optane in the paper), traced as ``STAGE_STAGE``.
3. **Drain** (background drain thread, inherited from
   :class:`BurstBufferCheckpointer`): every file of the staged step splits
   into ``drain_chunk`` ranges that stream to the *slow* tier on
   ``drain_streams`` threads (``read_range`` → pwrite-style
   ``write_range``), then the slow-tier commit marker is published durably
   (sync barrier + tmp/rename).

``save()`` returns an :class:`AsyncSaveHandle`; its ``result()`` settles
when the **fast tier** has committed (the step is then durable and
restorable — the contract a preemption save needs), while :meth:`wait`
additionally drains the slow tier.  ``max_pending`` bounds host memory the
same way :class:`AsyncCheckpointer` does.

Crash consistency is the same marker protocol at both tiers, proven in
``tests/test_faults.py`` under clean, torn-write and reordered-fsync fault
models at every injection point of the save/drain path.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, List, Optional

from .. import metrics, trace
from .async_checkpoint import AsyncSaveHandle, _any_error_delivered, \
    _cancel_and_promote
from .burst_buffer import BurstBufferCheckpointer
from .checkpoint import PreemptionReport, SaveResult, flatten_pytree


class AsyncBurstBufferCheckpointer(BurstBufferCheckpointer):
    """Burst-buffer checkpointer whose ``save()`` blocks only for the host
    snapshot.

    Same construction surface as :class:`BurstBufferCheckpointer` plus
    ``max_pending`` (host-memory backpressure: a ``save()`` issued while
    that many snapshots are still staging blocks until a slot frees; the
    blocked time is honestly recorded in ``blocked_s``).
    """

    def __init__(self, fast_storage, slow_storage,
                 prefix: str = "ckpt/model", *, max_pending: int = 2,
                 **kwargs):
        kwargs.pop("drain_async", None)  # the drain thread is mandatory here
        super().__init__(fast_storage, slow_storage, prefix,
                         drain_async=True, **kwargs)
        self._sema = threading.BoundedSemaphore(max(1, max_pending))
        self._stage_handles: List[AsyncSaveHandle] = []
        # One stager: steps stage (and therefore enqueue drains) in
        # submission order, so both tiers' markers advance monotonically.
        self._stager: Optional[ThreadPoolExecutor] = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="bb-stage"
        )

    # -- producer (training thread) -----------------------------------------
    def save(self, step: int, tree: Any,
             extra_meta: Optional[dict] = None) -> AsyncSaveHandle:
        if self._stager is None:
            raise RuntimeError("AsyncBurstBufferCheckpointer is closed")
        if self._preempted:
            raise RuntimeError(
                "save() on a preempted AsyncBurstBufferCheckpointer")
        m = metrics.enabled()
        t0 = time.monotonic()
        # backpressure: at most max_pending snapshots
        with trace.span(trace.STAGE_CKPT_BACKPRESSURE, "ckpt_backpressure"):
            self._sema.acquire()
        try:
            t_snap = time.monotonic()
            with trace.span(trace.STAGE_CKPT_SNAPSHOT,
                            f"snapshot:{self.prefix}-{step}") as sp:
                flat, treedef = flatten_pytree(tree, copy=True)
                sp.set_bytes(sum(a.nbytes for a in flat.values()))
            if m:
                metrics.observe("ckpt.snapshot_s",
                                time.monotonic() - t_snap, ckpt=self.prefix)
            fut = self._stager.submit(self._stage, step, flat, extra_meta,
                                      treedef, m)
            if m:
                metrics.add_gauge("ckpt.pending_saves", 1, ckpt=self.prefix)
        except BaseException:
            self._sema.release()
            raise
        blocked = time.monotonic() - t0
        self.blocked_s.append(blocked)
        if m:
            metrics.observe("ckpt.blocked_s", blocked, ckpt=self.prefix)
        handle = AsyncSaveHandle(step, fut, blocked, metrics_flag=m)
        self._stage_handles = [
            h for h in self._stage_handles
            if not h.done()
            or (not h._future.cancelled() and not h._reported
                and h._future.exception() is not None)
        ]
        self._stage_handles.append(handle)
        return handle

    # -- stager thread -------------------------------------------------------
    def _stage(self, step: int, flat, extra_meta, treedef,
               m: bool) -> SaveResult:
        """Fast-tier sharded save, then hand the files to the drain
        pipeline.  Runs on the single stager thread."""
        try:
            t0 = time.monotonic()
            with trace.span(trace.STAGE_STAGE,
                            f"stage:{self.prefix}-{step}") as sp:
                r = self.fast_saver.save_flat(step, flat, extra_meta,
                                              treedef=treedef)
                sp.set_bytes(r.n_bytes)
            if m:
                metrics.observe("ckpt.staged_s", time.monotonic() - t0,
                                ckpt=self.prefix)
                metrics.add_gauge("ckpt.drain_backlog_bytes", r.n_bytes,
                                  ckpt=self.prefix)
            if self.on_staged is not None:
                # fast-tier commit: the step is now preemption-durable
                self.on_staged(step)
            self._enqueue_drain(step, r, m)
            return r
        finally:
            self._sema.release()
            if m:  # symmetric with the save-time increment
                metrics.add_gauge("ckpt.pending_saves", -1, ckpt=self.prefix)

    # -- consumer-side API ---------------------------------------------------
    def pending(self) -> int:
        """Snapshots not yet committed to the fast tier."""
        return sum(1 for h in self._stage_handles if not h.done())

    def wait(self) -> None:
        """Block until every issued save has staged *and* drained; raise
        the first background error (stage or drain), report-once."""
        handles, self._stage_handles = self._stage_handles, []
        errors = []
        for h in handles:
            e = h._drain_error()  # blocks until this stage settles
            if e is not None:
                errors.append(e)
        # only now is the drain queue fully fed (stages enqueue drains)
        self._q.join()
        errors.extend(self._take_errors())
        if errors:
            raise errors[0]

    def preempt(self, deadline_s: Optional[float] = None) -> PreemptionReport:
        """Graceful shutdown within a budget: stop accepting saves, cancel
        queued-but-unstarted stages except the newest, and wait up to
        ``deadline_s`` for that newest snapshot to commit on the **fast
        tier** (the preemption-durability point — slow-tier drains of
        already-staged steps keep running in the background and are never
        abandoned)."""
        t0 = time.monotonic()
        self._preempted = True
        abandoned, met = _cancel_and_promote(
            list(self._stage_handles), self._sema, self.prefix, deadline_s,
            t0)
        return PreemptionReport(self.latest_step(), abandoned, deadline_s,
                                time.monotonic() - t0, met)

    def close(self) -> None:
        """Drain the stager, stop the drain thread, surface the first
        never-delivered background error from either phase (quiet if a
        failure already reached the caller — same contract as
        :meth:`AsyncCheckpointer.close`)."""
        errors: List[BaseException] = []
        if self._stager is not None:
            self._stager.shutdown(wait=True)
            self._stager = None
        handles, self._stage_handles = self._stage_handles, []
        if not _any_error_delivered(handles):
            errors.extend(e for e in (h._unreported_error() for h in handles)
                          if e is not None)
        try:
            super().close()  # joins the drain thread, raises drain errors
        except BaseException as e:
            errors.append(e)
        if errors:
            raise errors[0]
