"""The checkpoint engine: one class, set by its snapshot mode and its tiers.

The paper's checkpoint strategies (§III-C, Fig. 9/10) and the async
snapshot are four corners of one matrix.  ``snapshot="sync"`` writes the
first tier on the caller's thread (one tier: the paper's direct baseline).
``snapshot="async"`` blocks only for a host copy of the pytree
(``ckpt_backpressure`` then ``snapshot:`` spans) and hands the write to one
writer thread (``bb-stage`` with two tiers, ``ckpt-writer`` with one), so
steps commit in submission order.  With ``tiers=(fast, slow)`` each step
staged on the fast tier then drains to the slow one (the paper's burst
buffer, Fig. 9's 2.6x): its files split into ``drain_chunk`` ranges that
stream on ``drain_streams`` threads (``read_range`` → pwrite-style
``write_range``), optionally under a watchdog; the slow-tier marker is
published durably (sync barrier + tmp/rename), and the fast tier is
cleaned of every drained step but the newest (§V-C: "cleanup the buffer").

``save()`` returns a :class:`SaveHandle` that settles at the first-tier
commit (already settled for ``"sync"``); the step is then durable and
restorable.  ``on_staged(step)`` runs at that commit and ``on_drained(step)``
at the final-tier commit (the same moment for one tier):
:class:`~repro.core.recovery.CheckpointManager` drives its lifecycle and
retention from them.  The engine itself neither restores nor retains.
Background errors surface through the handle, :meth:`CheckpointEngine.wait`
and :meth:`CheckpointEngine.close`, each once; a ``"sync"`` first-tier
failure raises inline from ``save()``.
"""
from __future__ import annotations

import json
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from concurrent.futures import TimeoutError as FutureTimeout
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence, Tuple

from .. import metrics, trace
from .checkpoint import CheckpointSaver, PreemptionReport, SaveResult, \
    flatten_pytree, write_marker

#: Effectively-infinite ``keep`` for the engine's savers: the engine never
#: deletes a committed step (retention belongs to the manager).
NO_SAVER_GC = 1 << 30

SNAPSHOT_MODES = ("sync", "async")


class DrainStallError(RuntimeError):
    """A drain chunk stalled past the watchdog timeout on every attempt
    (initial + ``drain_requeue_limit`` re-queues).  Deliberately *not* an
    OSError: the watchdog re-queue is itself the retry mechanism — this
    surfacing means the slow tier is wedged, not flaky."""


@dataclass
class DrainRecord:
    step: int
    n_bytes: int
    staged_s: float     # time the first-tier write took
    drain_s: float      # background copy time (overlapped)
    completed_at: float


class SaveHandle:
    """Future-like handle for one save; settles at the first-tier commit.

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

    def __init__(self, step: int, future: Future, snapshot_s: float,
                 metrics_flag: bool = False):
        self.step = step
        self.snapshot_s = snapshot_s
        self._future = future
        self._observed = False   # seen via result()/exception()
        self._reported = False   # raised by wait()/close()
        # save-time metrics.enabled() flag: a preempt() that cancels this
        # handle decrements the pending_saves gauge iff it was incremented
        self._metrics_flag = metrics_flag

    @classmethod
    def settled(cls, result: SaveResult) -> "SaveHandle":
        """The handle of a save that committed on the caller's thread."""
        fut: Future = Future()
        fut.set_result(result)
        return cls(result.step, fut, result.seconds)

    def done(self) -> bool:
        return self._future.done()

    def cancelled(self) -> bool:
        """True if a ``preempt()`` cancelled this save before it touched
        storage (the snapshot was abandoned — nothing landed, no error)."""
        return self._future.cancelled()

    def result(self, timeout: Optional[float] = None) -> SaveResult:
        """Block until the first-tier write commits; re-raises its error."""
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

    def _failed(self) -> bool:
        return (self._future.done() and not self._future.cancelled()
                and self._future.exception() is not None)

    def _unreported_error(self):
        """Settled-with-error and never seen by anyone (no blocking, no
        marking) — what ``close()`` must surface."""
        if self._reported or self._observed or not self._failed():
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
        return f"SaveHandle(step={self.step}, {state})"


def _cancel_and_promote(handles: List[SaveHandle], sema, prefix: str,
                        deadline_s: Optional[float], t0: float):
    """Cancel every queued (not-yet-started) save except the newest, then
    wait for the newest to commit within what remains of the deadline;
    returns ``(abandoned_steps, deadline_met)``.

    A cancelled save never ran its writer, so its backpressure slot and
    pending-saves gauge entry are released here.  The newest is *promoted*:
    it gets the whole remaining budget; on timeout it is reported abandoned
    but left running, and is durable as normal if it settles later."""
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


class CheckpointEngine:
    """Save a pytree to ``tiers`` — ``(storage,)`` or ``(fast, slow)`` —
    with a ``"sync"`` or ``"async"`` snapshot (see the module docstring).

    ``n_shards``/``sync``/``quantize``/``io_threads`` configure each tier's
    :class:`~repro.core.checkpoint.CheckpointSaver`; ``max_pending`` bounds
    the async snapshots in host memory (a ``save()`` past it blocks, and
    the time lands in ``blocked_s``).  ``drain_stall_timeout`` arms the
    drain watchdog (``None`` disables it): set it above the worst-case
    single-chunk transfer time, or healthy chunks get aborted.
    """

    def __init__(
        self,
        tiers: Sequence[Any],
        prefix: str = "ckpt/model",
        *,
        snapshot: str = "sync",
        n_shards: int = 1,
        sync: bool = True,
        quantize: Optional[str] = None,
        io_threads: Optional[int] = None,
        max_pending: int = 2,
        drain_streams: int = 4,
        drain_chunk: int = 8 << 20,
        drain_stall_timeout: Optional[float] = None,
        drain_requeue_limit: int = 3,
    ):
        if snapshot not in SNAPSHOT_MODES:
            raise ValueError(
                f"snapshot must be one of {SNAPSHOT_MODES}, got {snapshot!r}")
        if len(tiers) not in (1, 2):
            raise ValueError(f"tiers must be (storage,) or (fast, slow), "
                             f"got {len(tiers)} tiers")
        self.prefix = prefix
        self.snapshot = snapshot
        #: one saver per tier, first tier first; they never delete a step
        self.savers = [CheckpointSaver(
            s, prefix, keep=NO_SAVER_GC, n_shards=n_shards, sync=sync,
            quantize=quantize, io_threads=io_threads) for s in tiers]
        self.drain_streams = max(1, drain_streams)
        self.drain_chunk = drain_chunk
        self.drain_stall_timeout = drain_stall_timeout
        self.drain_requeue_limit = max(0, drain_requeue_limit)
        self.drain_stalls = 0   # stall events the watchdog detected
        self.drain_aborts = 0   # streams it gave up on (leaked until unwedged)
        #: Lifecycle hooks, called with the step number after the first-tier
        #: commit / after the final-tier commit, on whichever thread made it.
        self.on_staged: Optional[Callable[[int], None]] = None
        self.on_drained: Optional[Callable[[int], None]] = None
        self.blocked_s: List[float] = []
        self.drains: List[DrainRecord] = []
        self.staged_step: Optional[int] = None  # newest first-tier commit
        self._closed = False
        self._preempted = False
        self._handles: List[SaveHandle] = []
        self._sema = threading.BoundedSemaphore(max(1, max_pending))
        self._writer: Optional[ThreadPoolExecutor] = None
        if snapshot == "async":
            self._writer = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix=(
                    "bb-stage" if len(tiers) == 2 else "ckpt-writer"))
        # drain bookkeeping: steps staged but not yet drained
        self._q: "queue.Queue" = queue.Queue()
        self._pending: List[int] = []
        self._drained: set = set()
        self._pending_lock = threading.Lock()
        self._errors: List[BaseException] = []
        self._thread: Optional[threading.Thread] = None
        if len(tiers) == 2:
            self._thread = threading.Thread(target=self._drain_loop,
                                            daemon=True)
            self._thread.start()

    @property
    def two_tier(self) -> bool:
        return len(self.savers) == 2

    # -- producer (training thread) -----------------------------------------
    def save(self, step: int, tree: Any,
             extra_meta: Optional[dict] = None) -> SaveHandle:
        if self._closed:
            raise RuntimeError("save() on a closed CheckpointEngine")
        if self._preempted:
            raise RuntimeError("save() on a preempted CheckpointEngine")
        if self.snapshot == "sync":
            r = self.savers[0].save(step, tree, extra_meta)
            self.blocked_s.append(r.seconds)
            self._staged(step, r, r.seconds, metrics.enabled())
            return SaveHandle.settled(r)
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
            fut = self._writer.submit(self._write, step, flat, extra_meta,
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
        handle = SaveHandle(step, fut, blocked, metrics_flag=m)
        # keep only unsettled and failed-but-not-yet-drain-reported handles:
        # the list must not grow with run length
        self._handles = [h for h in self._handles
                         if not h.done() or (h._failed() and not h._reported)]
        self._handles.append(handle)
        return handle

    # -- writer thread (async) ----------------------------------------------
    def _write(self, step: int, flat, extra_meta, treedef,
               m: bool) -> SaveResult:
        try:
            t0 = time.monotonic()
            if self.two_tier:
                with trace.span(trace.STAGE_STAGE,
                                f"stage:{self.prefix}-{step}") as sp:
                    r = self.savers[0].save_flat(step, flat, extra_meta,
                                                 treedef=treedef)
                    sp.set_bytes(r.n_bytes)
            else:
                r = self.savers[0].save_flat(step, flat, extra_meta,
                                             treedef=treedef)
            self._staged(step, r, time.monotonic() - t0, m)
            return r
        finally:
            self._sema.release()
            if m:  # symmetric with the save-time increment: the gauge must
                   # never go negative when metrics toggles mid-run
                metrics.add_gauge("ckpt.pending_saves", -1, ckpt=self.prefix)

    def _staged(self, step: int, r: SaveResult, write_s: float,
                m: bool) -> None:
        """The first tier committed ``step``: fire the hooks, and with two
        tiers queue its drain."""
        self.staged_step = step
        if self.two_tier:
            if m:
                metrics.observe("ckpt.staged_s", write_s, ckpt=self.prefix)
                metrics.add_gauge("ckpt.drain_backlog_bytes", r.n_bytes,
                                  ckpt=self.prefix)
        elif self.snapshot == "async" and metrics.enabled():
            metrics.observe("ckpt.write_s", write_s, ckpt=self.prefix)
            metrics.inc("ckpt.saves", 1, ckpt=self.prefix)
        if self.on_staged is not None:
            self.on_staged(step)
        if not self.two_tier:  # the first tier is the final one
            if self.on_drained is not None:
                self.on_drained(step)
            return
        with self._pending_lock:
            self._pending.append(step)
        # the job carries the save-time metrics flag so the backlog gauge is
        # decremented iff it was incremented (metrics may toggle mid-run)
        self._q.put((step, list(r.files), r.n_bytes, r.seconds, m))

    # -- drainer (two tiers) ------------------------------------------------
    def _drain_loop(self) -> None:
        while True:
            job = self._q.get()
            if job is None:
                self._q.task_done()
                return
            try:
                step, files, n_bytes, staged_s, m = job
                with trace.span(trace.STAGE_DRAIN,
                                f"drain:{self.prefix}-{step}", n_bytes):
                    self._drain_files(step, files, n_bytes, staged_s, m)
            except BaseException as e:  # surface on wait()/close()
                with self._pending_lock:
                    self._errors.append(e)
            finally:
                self._q.task_done()

    def _range_tasks(self, files: List[str]) -> List[Tuple[str, int, int]]:
        """Split every file of a step into ``drain_chunk``-byte ranges so
        one large shard drains on multiple streams (intra-file parallel)."""
        fast = self.savers[0].storage
        tasks: List[Tuple[str, int, int]] = []
        for path in files:
            size = fast.size(path)
            if size == 0:
                tasks.append((path, 0, 0))
                continue
            for offset in range(0, size, self.drain_chunk):
                tasks.append((path, offset,
                              min(self.drain_chunk, size - offset)))
        return tasks

    def _drain_range(self, path: str, offset: int, length: int) -> None:
        slow = self.savers[1].storage
        if length == 0:
            slow.write_file(path, b"", sync=False)
            return
        data = self.savers[0].storage.read_range(path, offset, length)
        slow.write_range(path, offset, data, sync=False)

    def _drain_files(self, step: int, files: List[str], n_bytes: int,
                     staged_s: float, m: bool) -> None:
        t0 = time.monotonic()
        fast_saver, slow_saver = self.savers
        # every chunk range streams on the drain streams; any failure aborts
        # before the marker moves.  The range writes are not synced one by
        # one: the marker write below is the durability barrier.
        self._run_drain_tasks(self._range_tasks(files))
        # slow-tier commit marker after all files landed — written durably
        # (sync=True barrier) via tmp+rename: it must never become durable
        # before the data it commits, and never be left half-written
        steps = slow_saver.all_steps()
        if step not in steps:
            steps.append(step)
        marker = json.dumps(dict(latest=step, all_steps=sorted(steps)))
        write_marker(slow_saver.storage, slow_saver._marker_path(),
                     marker.encode(), sync=True)
        with self._pending_lock:
            # compact drained steps out of both structures: neither may
            # grow with run length
            self._drained.add(step)
            self._pending = [s for s in self._pending
                             if s not in self._drained]
            self._drained.intersection_update(self._pending)
            pending = set(self._pending)
        # free buffer capacity: keep only the newest staged step for a fast
        # restore, and never evict a step still waiting in the drain queue
        fast_steps = fast_saver.all_steps()
        keep_newest = max(fast_steps) if fast_steps else None
        for old in fast_steps:
            if old != keep_newest and old not in pending:
                fast_saver._delete_step(old)
        self.drains.append(
            DrainRecord(step, n_bytes, staged_s, time.monotonic() - t0,
                        time.monotonic())
        )
        if metrics.enabled():
            metrics.observe("ckpt.drain_s", time.monotonic() - t0,
                            ckpt=self.prefix)
            metrics.inc("ckpt.drains", 1, ckpt=self.prefix)
        if m:
            metrics.add_gauge("ckpt.drain_backlog_bytes", -n_bytes,
                              ckpt=self.prefix)
        if self.on_drained is not None:
            self.on_drained(step)

    def _run_drain_tasks(self, tasks: List[Tuple[str, int, int]]) -> None:
        """Stream all chunk ranges of a step to the slow tier: serially for
        one stream or one task, on a plain pool without a stall timeout,
        and under the watchdog with one."""
        if self.drain_streams <= 1 or len(tasks) <= 1:
            for path, off, length in tasks:
                self._drain_range(path, off, length)
        elif self.drain_stall_timeout is None:
            with ThreadPoolExecutor(
                min(self.drain_streams, len(tasks)),
                thread_name_prefix="bb-drain",
            ) as pool:
                futs = [pool.submit(self._drain_range, path, off, length)
                        for path, off, length in tasks]
                for f in futs:
                    f.result()
        else:
            self._run_drain_tasks_watchdog(tasks)

    def _run_drain_tasks_watchdog(self, tasks: List[Tuple[str, int, int]]) -> None:
        """Watchdog-supervised multi-stream drain.

        Streams pull chunks from a shared queue and stamp a heartbeat as
        they claim one.  The drain thread polls: a stream whose chunk shows
        no progress past the timeout is **aborted** — its chunk re-queued
        on a replacement stream — so a wedged slow-tier op delays the drain
        by about one timeout instead of hanging ``wait()``.  Aborted streams
        stay parked in the stuck op (a thread in a syscall cannot be
        killed); if it completes, the duplicate write is byte-identical.  A
        chunk that stalls on every attempt raises :class:`DrainStallError`
        through the drain-error path."""
        timeout = self.drain_stall_timeout
        n_tasks = len(tasks)
        cond = threading.Condition()
        pending: deque = deque((i, 0) for i in range(n_tasks))  # (idx, tries)
        done: set = set()
        claims: dict = {}    # stream id -> (task idx, tries, heartbeat time)
        dead: set = set()    # streams the watchdog gave up on
        errors: List[BaseException] = []
        threads: dict = {}
        next_sid = [0]

        def finished() -> bool:
            return len(done) >= n_tasks or bool(errors)

        def stream(sid: int) -> None:
            while True:
                with cond:
                    while True:
                        if finished() or sid in dead:
                            return
                        if pending:
                            idx, tries = pending.popleft()
                            if idx in done:  # a leaked duplicate landed it
                                continue
                            claims[sid] = (idx, tries, time.monotonic())
                            break
                        cond.wait(min(timeout / 4.0, 0.05))
                path, off, length = tasks[idx]
                try:
                    self._drain_range(path, off, length)
                except BaseException as e:
                    with cond:
                        claims.pop(sid, None)
                        if sid not in dead:  # an abandoned stream's error
                            errors.append(e)  # belongs to its re-queued copy
                        cond.notify_all()
                    return
                with cond:
                    claims.pop(sid, None)
                    done.add(idx)
                    cond.notify_all()
                    if sid in dead:
                        return

        def spawn() -> None:
            sid = next_sid[0]
            next_sid[0] += 1
            t = threading.Thread(target=stream, args=(sid,),
                                 name=f"bb-drain-{sid}", daemon=True)
            threads[sid] = t
            t.start()

        for _ in range(min(self.drain_streams, n_tasks)):
            spawn()

        with cond:
            while not finished():
                cond.wait(min(timeout / 4.0, 0.05))
                now = time.monotonic()
                for sid, (idx, tries, hb) in list(claims.items()):
                    if now - hb <= timeout:
                        continue
                    # stall: abort the stream, re-queue its chunk
                    dead.add(sid)
                    claims.pop(sid)
                    self.drain_stalls += 1
                    self.drain_aborts += 1
                    if metrics.enabled():
                        metrics.inc("ckpt.drain_stalls", 1, ckpt=self.prefix)
                        metrics.inc("ckpt.drain_aborts", 1, ckpt=self.prefix)
                    path, off, length = tasks[idx]
                    if tries >= self.drain_requeue_limit:
                        errors.append(DrainStallError(
                            f"drain chunk {path!r}@{off}+{length} stalled "
                            f"past {timeout}s on {tries + 1} attempts "
                            f"(requeue limit {self.drain_requeue_limit})"))
                    else:
                        pending.append((idx, tries + 1))
                        spawn()
                cond.notify_all()
            cond.notify_all()
        for sid, t in threads.items():
            if sid not in dead:  # dead streams stay parked in the stuck op
                t.join(timeout=timeout + 1.0)
        if errors:
            raise errors[0]

    # -- consumer-side API ----------------------------------------------------
    def pending(self) -> int:
        """Async snapshots not yet committed to the first tier."""
        return sum(1 for h in self._handles if not h.done())

    def _take_errors(self) -> List[BaseException]:
        with self._pending_lock:
            errors, self._errors = self._errors, []
        return errors

    def wait(self) -> None:
        """Block until every issued save has committed at the final tier;
        raise the first background error (first-tier write or drain).
        Errors are reported once: a settled failure does not re-raise on
        every later ``wait()``."""
        handles, self._handles = self._handles, []
        errors = [e for e in (h._drain_error() for h in handles)
                  if e is not None]  # blocks until each save settles
        if self._thread is not None:
            # only now is the drain queue fully fed (stages enqueue drains)
            self._q.join()
            errors.extend(self._take_errors())
        if errors:
            raise errors[0]

    def preempt(self, deadline_s: Optional[float] = None) -> PreemptionReport:
        """Graceful shutdown within a budget: stop accepting saves, cancel
        queued-but-unstarted async snapshots except the newest, and wait up
        to ``deadline_s`` (``None`` = forever) for that newest one to commit
        at the first tier, the preemption-durability point.  Drains of
        staged steps keep running and are never abandoned.  A ``"sync"``
        save returned only after its first-tier commit, so there the
        report is met at once."""
        t0 = time.monotonic()
        self._preempted = True
        abandoned, met = _cancel_and_promote(
            list(self._handles), self._sema, self.prefix, deadline_s, t0)
        return PreemptionReport(self.staged_step, abandoned, deadline_s,
                                time.monotonic() - t0, met)

    def close(self) -> None:
        """Idempotent shutdown: finish the writer, stop the drain thread,
        and surface the first background error the caller never saw.

        If a failed first-tier write already reached the caller (a handle's
        ``result()``/``exception()``, or a ``wait()`` raise), its siblings
        stay quiet: with a sticky device fault every in-flight save fails
        the same way, and re-raising the tail of that cascade at teardown
        helps no one.  A drain error nobody collected is always raised."""
        self._closed = True
        if self._writer is not None:
            self._writer.shutdown(wait=True)
            self._writer = None
        handles, self._handles = self._handles, []
        errors: List[BaseException] = []
        if not any((h._observed or h._reported) and h._failed()
                   for h in handles):
            errors.extend(e for e in (h._unreported_error() for h in handles)
                          if e is not None)
        if self._thread is not None:
            self._q.put(None)
            self._thread.join(timeout=60)
            self._thread = None
        errors.extend(self._take_errors())
        if errors:
            raise errors[0]
