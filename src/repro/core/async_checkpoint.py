"""Async snapshot checkpointing: training blocks for the snapshot only.

The paper's burst buffer (§III-C, Fig. 9/10) hides the *slow-tier* cost of a
checkpoint behind a fast tier, but training still blocks for the full
fast-tier write.  Its prefetcher result (§IV: complete compute/input overlap)
points at the stronger play, which this module implements for the write path:

1. **Snapshot** (blocking, :func:`repro.core.checkpoint.flatten_pytree` with
   ``copy=True``): the pytree is materialized in host memory — device arrays
   via ``jax.device_get``, numpy leaves by copy.  This is memory-bandwidth
   bound (GB/s), not storage-bound (MB/s), so the training thread resumes
   after milliseconds.
2. **Write** (background): a dedicated writer thread runs the normal
   sharded, atomic :meth:`CheckpointSaver.save_flat` — with the N data
   shards themselves written concurrently on the saver's ``io_threads``
   pool (the write-side analogue of the paper's 2.3x/7.8x read
   thread-scaling).

``save()`` returns an :class:`AsyncSaveHandle` (future-like: ``done()`` /
``result()`` / ``exception()``).  The commit protocol is unchanged — data,
index and meta land before the ``checkpoint`` marker — so a crash at any
point leaves the previous checkpoint restorable (see ``tests/test_faults.py``
for the fault-injected proof).

``max_pending`` bounds host-memory use: a ``save()`` issued while that many
snapshots are still being written blocks until a slot frees (the blocked
time is honestly recorded in ``blocked_s``).

Every phase is trace-attributed (``STAGE_CKPT_SNAPSHOT`` on the training
thread, ``STAGE_CKPT_WRITE`` on the writer thread), so a
:mod:`repro.trace` report shows checkpoint writes overlapping compute —
see ``benchmarks/fig10_async_ckpt.py``.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeout
from typing import Any, Callable, List, Optional

from .. import metrics, trace
from .checkpoint import CheckpointSaver, PreemptionReport, SaveResult, \
    flatten_pytree


class AsyncSaveHandle:
    """Future-like handle for one in-flight checkpoint save.

    Error bookkeeping distinguishes two degrees of "the caller knows":

    * *observed* — the caller saw the error through :meth:`result` or
      :meth:`exception`.  ``close()`` then stays quiet, but a draining
      ``wait()`` still raises it (wait's contract: surface every failed
      save it drains, exactly once).
    * *reported* — ``wait()``/``close()`` raised it.  Nothing re-raises it
      afterwards.

    So one failure is raised by at most one drain call and never silently
    dropped: an error nobody observed is re-raised by ``close()``.
    """

    def __init__(self, step: int, future, snapshot_s: float,
                 metrics_flag: bool = False):
        self.step = step
        self.snapshot_s = snapshot_s
        self._future = future
        self._observed = False   # seen via result()/exception()
        self._reported = False   # raised by wait()/close()
        # save-time metrics.enabled() flag: a preempt() that cancels this
        # handle decrements the pending_saves gauge iff it was incremented
        self._metrics_flag = metrics_flag

    def done(self) -> bool:
        return self._future.done()

    def cancelled(self) -> bool:
        """True if a ``preempt()`` cancelled this save before it touched
        storage (the snapshot was abandoned — nothing landed, no error)."""
        return self._future.cancelled()

    def result(self, timeout: Optional[float] = None) -> SaveResult:
        """Block until the background write commits; re-raises its error."""
        try:
            return self._future.result(timeout)
        except BaseException:
            self._observed = True
            raise

    def exception(self, timeout: Optional[float] = None):
        e = self._future.exception(timeout)
        if e is not None:
            self._observed = True
        return e

    def _unreported_error(self):
        """Settled-with-error and never seen by anyone (no blocking, no
        marking) — what ``close()`` must surface."""
        if not self._future.done() or self._reported or self._observed \
                or self._future.cancelled():
            return None
        return self._future.exception()

    def _drain_error(self):
        """Blocking: the error ``wait()`` owes the caller (not yet raised
        by a drain call), marking it reported."""
        if self._future.cancelled():  # abandoned by preempt(): no error owed
            return None
        e = self._future.exception()
        if e is None or self._reported:
            return None
        self._reported = True
        return e

    def __repr__(self) -> str:  # pragma: no cover
        state = "done" if self.done() else "pending"
        return f"AsyncSaveHandle(step={self.step}, {state})"


def _any_error_delivered(handles) -> bool:
    """True if some failed save in ``handles`` was already seen by the
    caller (observed via the handle, or raised by a drain call)."""
    return any(
        (h._observed or h._reported)
        and h._future.done() and not h._future.cancelled()
        and h._future.exception() is not None
        for h in handles
    )


def _cancel_and_promote(handles, sema, prefix: str,
                        deadline_s: Optional[float], t0: float):
    """Shared preemption core for the async engines: cancel every queued
    (not-yet-started) save except the newest, then wait for the newest to
    commit within what remains of the deadline.

    Returns ``(abandoned_steps, deadline_met)``.  A successfully cancelled
    save never ran its writer, so its backpressure slot and pending-saves
    gauge entry are released here (symmetric with the save-time acquire).
    The newest save is *promoted*: it gets the whole remaining budget; on
    timeout it is reported abandoned but left running — if it settles after
    the process survives anyway, the step is durable as normal."""
    abandoned: List[int] = []
    live = [h for h in handles if not h.done()]
    newest = live[-1] if live else None
    for h in live[:-1]:
        if h._future.cancel():
            abandoned.append(h.step)
            sema.release()
            if h._metrics_flag:
                metrics.add_gauge("ckpt.pending_saves", -1, ckpt=prefix)
    deadline_met = True
    if newest is not None:
        remaining = None
        if deadline_s is not None:
            remaining = max(0.0, deadline_s - (time.monotonic() - t0))
        try:
            e = newest._future.exception(remaining)
        except FutureTimeout:
            abandoned.append(newest.step)
            deadline_met = False
        else:
            if e is not None:
                # failed, not slow: the step is not durable.  The error
                # itself still surfaces through the handle/wait()/close()
                # contract — preempt() only records the abandonment.
                abandoned.append(newest.step)
    return sorted(abandoned), deadline_met


class AsyncCheckpointer:
    """Checkpointer whose ``save()`` blocks only for the host snapshot.

    Same construction surface as :class:`DirectCheckpointer` plus
    ``io_threads`` (shard-write parallelism) and ``max_pending``
    (host-memory backpressure).  ``save()`` returns an
    :class:`AsyncSaveHandle`; call :meth:`wait` to drain and surface any
    background write error.
    """

    def __init__(
        self,
        storage,
        prefix: str = "ckpt/model",
        *,
        keep: int = 5,
        n_shards: int = 1,
        sync: bool = True,
        quantize=None,
        io_threads: Optional[int] = None,
        max_pending: int = 2,
    ):
        self.saver = CheckpointSaver(
            storage, prefix, keep=keep, n_shards=n_shards, sync=sync,
            quantize=quantize, io_threads=io_threads,
        )
        self.prefix = prefix
        self.blocked_s: List[float] = []
        self._handles: List[AsyncSaveHandle] = []
        self._preempted = False
        #: Lifecycle hook (used by the fused CheckpointManager): called with
        #: the step number on the writer thread after the step committed.
        self.on_committed: Optional[Callable[[int], None]] = None
        self._sema = threading.BoundedSemaphore(max(1, max_pending))
        # One writer thread: checkpoints commit in submission order, so the
        # marker's `latest` is always the newest fully-landed step.
        self._executor: Optional[ThreadPoolExecutor] = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="ckpt-writer"
        )

    # -- producer (training thread) -----------------------------------------
    def save(self, step: int, tree: Any,
             extra_meta: Optional[dict] = None) -> AsyncSaveHandle:
        if self._executor is None:
            raise RuntimeError("AsyncCheckpointer is closed")
        if self._preempted:
            raise RuntimeError("save() on a preempted AsyncCheckpointer")
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
            fut = self._executor.submit(self._write, step, flat, extra_meta,
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
        # keep only unsettled and failed-but-not-yet-drain-reported handles:
        # the list must not grow with run length
        self._handles = [
            h for h in self._handles
            if not h.done()
            or (not h._future.cancelled() and not h._reported
                and h._future.exception() is not None)
        ]
        self._handles.append(handle)
        return handle

    # -- writer thread -------------------------------------------------------
    def _write(self, step: int, flat, extra_meta, treedef,
               m: bool) -> SaveResult:
        t0 = time.monotonic()
        try:
            res = self.saver.save_flat(step, flat, extra_meta, treedef=treedef)
            if metrics.enabled():
                metrics.observe("ckpt.write_s", time.monotonic() - t0,
                                ckpt=self.prefix)
                metrics.inc("ckpt.saves", 1, ckpt=self.prefix)
            if self.on_committed is not None:
                # commit hook: the fused manager runs deferred retention/GC
                # here, on the (single) writer thread, after the marker moved
                self.on_committed(step)
            return res
        finally:
            self._sema.release()
            if m:  # symmetric with the save-time increment: the gauge must
                   # never go negative when metrics toggles mid-run
                metrics.add_gauge("ckpt.pending_saves", -1, ckpt=self.prefix)

    # -- consumer-side API ----------------------------------------------------
    def wait(self) -> None:
        """Block until every issued save has committed; raise the first
        background error (interface parity with the burst buffer).  Settled
        handles are dropped — an error is reported once, not re-raised by
        every later ``wait()``."""
        handles, self._handles = self._handles, []
        errors = []
        for h in handles:
            e = h._drain_error()  # blocks until this save settles
            if e is not None:
                errors.append(e)
        if errors:
            raise errors[0]

    def pending(self) -> int:
        return sum(1 for h in self._handles if not h.done())

    def preempt(self, deadline_s: Optional[float] = None) -> PreemptionReport:
        """Graceful shutdown within a budget: stop accepting saves, cancel
        queued-but-unstarted writes except the newest, and wait up to
        ``deadline_s`` (``None`` = forever) for that newest write to
        commit.  Returns what was promoted vs abandoned."""
        t0 = time.monotonic()
        self._preempted = True
        abandoned, met = _cancel_and_promote(
            list(self._handles), self._sema, self.prefix, deadline_s, t0)
        return PreemptionReport(self.latest_step(), abandoned, deadline_s,
                                time.monotonic() - t0, met)

    def close(self, wait: bool = True) -> None:
        """Shut the writer down; surface (not silently drop) a background
        error that nobody ever saw.

        If any failure was already delivered (a handle's ``result()`` /
        ``exception()``, or a ``wait()`` raise), close stays quiet: with a
        sticky device fault every in-flight save fails the same way, and
        re-raising the tail of that cascade at teardown helps no one.  Only
        the never-delivered case is raised here."""
        if self._executor is not None:
            self._executor.shutdown(wait=wait)
            self._executor = None
        handles, self._handles = self._handles, []
        if _any_error_delivered(handles):
            return
        errors = [e for e in (h._unreported_error() for h in handles)
                  if e is not None]
        if errors:
            raise errors[0]

    # -- restore / introspection (delegate to the saver) ----------------------
    def restore_pytree(self, skeleton: Any, step: Optional[int] = None) -> Any:
        return self.saver.restore_pytree(skeleton, step)

    def restore_sharded(self, skeleton, shardings, step=None):
        return self.saver.restore_sharded(skeleton, shardings, step)

    def latest_step(self) -> Optional[int]:
        return self.saver.latest_step()
