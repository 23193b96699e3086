"""Training loop: input pipeline + checkpointing + fault tolerance.

Integrates the paper's pieces end-to-end:

* data comes through the :mod:`repro.core.dataset` pipeline (parallel map +
  prefetch) and optionally :func:`prefetch_to_device`;
* checkpoints go through a Direct-, BurstBuffer-, Async- or
  AsyncBurstBuffer-checkpointer every ``ckpt_every`` steps (the paper's
  protocol: §IV-C).  With an async engine
  (:class:`repro.core.async_checkpoint.AsyncCheckpointer` or
  :class:`repro.core.async_burst_buffer.AsyncBurstBufferCheckpointer`),
  ``save()`` returns a future-like handle and the step loop never blocks
  past the host snapshot; the trainer tracks in-flight handles, re-raises
  background write failures at the next step boundary and at ``run()``
  exit, and blocks on the final preemption save so the checkpoint is
  durable (fast-tier committed, for the async burst buffer) before
  stopping.  A save still in flight when ``run()`` returns stays pending —
  call :meth:`Trainer.wait_for_checkpoints` to drain it and surface any
  error (the same contract as ``BurstBufferCheckpointer.wait``);
* **restart**: on construction the trainer restores the newest checkpoint
  if one exists (crash/preemption recovery);
* **preemption**: SIGTERM (or :meth:`Trainer.preempt`) triggers
  checkpoint-and-stop at the next step boundary; with a
  ``preempt_deadline_s`` budget and an engine that supports
  ``preempt()``, older queued snapshots are abandoned and the final save
  is promoted to its durability tier within the deadline — the outcome
  lands in :attr:`Trainer.preemption_report`;
* **straggler monitor**: per-step data-wait vs compute-time is recorded
  (paper Fig. 6: when prefetch works, data-wait ≈ 0), and compute splits
  into the step's dispatch and the sync that brings its metrics to the
  host; a sustained data-wait fraction above ``straggler_threshold`` is
  surfaced in ``report()``.

Under :mod:`repro.trace` each phase is a span on the training thread:
``next_batch`` (``STAGE_DATA_WAIT``); ``train_step`` (``STAGE_COMPUTE``)
holding ``STAGE_STEP_DISPATCH`` and the metrics' ``STAGE_STEP_SYNC``; the
step counter's own ``STAGE_STEP_SYNC``; ``STAGE_CKPT_SAVE`` holding
``STAGE_PIPELINE_STATE`` and the engine's spans; at a stop
``STAGE_PREEMPT`` holding the final save and ``STAGE_PREEMPT_PROMOTE``;
and ``STAGE_PIPELINE_CLOSE`` in :meth:`Trainer.close`.
"""
from __future__ import annotations

import signal
import time
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import numpy as np

from .. import metrics as live_metrics
from .. import trace
from ..core.stats import StepTimer


class Trainer:
    def __init__(
        self,
        train_step: Callable,                  # (state, batch) -> (state, metrics)
        state: Dict[str, Any],
        data_iter: Iterable,
        *,
        checkpointer=None,                     # Direct/BurstBuffer checkpointer
        ckpt_every: int = 0,
        resume: bool = True,
        preempt_deadline_s: Optional[float] = None,
        straggler_threshold: float = 0.2,
        install_sigterm: bool = False,
        on_step: Optional[Callable[[int, Dict], None]] = None,
        stall_detector=None,                   # repro.metrics.StallDetector
    ):
        self.train_step = train_step
        self.state = state
        self.data_iter = iter(data_iter)
        self.checkpointer = checkpointer
        self.ckpt_every = ckpt_every
        self.timer = StepTimer()
        self.straggler_threshold = straggler_threshold
        self.on_step = on_step
        self.stall_detector = stall_detector
        self.history: List[Dict] = []
        self._stop_requested = False
        self._preempt_deadline_s = preempt_deadline_s
        self._pending_saves: List[Any] = []  # AsyncSaveHandle-like objects
        self.recovered_step: Optional[int] = None
        self.preemption_report = None        # PreemptionReport after a stop
        self.preempt_s: Optional[float] = None  # stop-path wall time
        if install_sigterm:
            signal.signal(signal.SIGTERM, self._handle_sigterm)
        if resume and checkpointer is not None:
            if hasattr(checkpointer, "resume"):
                # CheckpointManager path: params AND input-pipeline position
                # (walks back past corrupt checkpoints; repositions a
                # ResumableIterator so no sample is skipped or replayed)
                res = checkpointer.resume(self.state, data_iter=self.data_iter)
                self.state = res.state
                self.recovered_step = res.step
            else:
                latest = checkpointer.latest_step()
                if latest is not None:
                    self.state = checkpointer.restore_pytree(self.state)
                    self.recovered_step = latest
                    # step counter lives in the state itself

    def _handle_sigterm(self, signum, frame):  # pragma: no cover
        self._stop_requested = True

    def request_stop(self) -> None:
        """Graceful-preemption hook (same path as SIGTERM)."""
        self._stop_requested = True

    def preempt(self, deadline_s: Optional[float] = None) -> None:
        """Graceful preemption with a shutdown budget: stop at the next
        step boundary, issue the final save, and give the checkpointer
        ``deadline_s`` seconds (overriding the constructor default) to
        promote the newest in-flight save to its durability tier —
        abandoning older ones.  The outcome lands in
        :attr:`preemption_report`."""
        if deadline_s is not None:
            self._preempt_deadline_s = deadline_s
        self._stop_requested = True

    @property
    def step(self) -> int:
        return int(jax.device_get(self.state["step"]))

    def run(self, n_steps: int) -> List[Dict]:
        for _ in range(n_steps):
            t0 = time.monotonic()
            with trace.span(trace.STAGE_DATA_WAIT, "next_batch"):
                try:
                    batch = next(self.data_iter)
                except StopIteration:
                    break
            t1 = time.monotonic()
            with trace.span(trace.STAGE_COMPUTE, "train_step"):
                with trace.span(trace.STAGE_STEP_DISPATCH, "train_step"):
                    self.state, metrics = self.train_step(self.state, batch)
                t_dispatched = time.monotonic()
                with trace.span(trace.STAGE_STEP_SYNC, "metrics"):
                    metrics = {k: float(jax.device_get(v))
                               for k, v in metrics.items()}
                t_synced = time.monotonic()
            t2 = time.monotonic()
            self.timer.data_wait_s.append(t1 - t0)
            self.timer.dispatch_s.append(t_dispatched - t1)
            self.timer.sync_s.append(t_synced - t_dispatched)
            self.timer.compute_s.append(t2 - t1)
            with trace.span(trace.STAGE_STEP_SYNC, "step_counter"):
                step = self.step
            metrics["step"] = step
            self.history.append(metrics)
            # live heartbeat: the paper's Fig. 6 observable, per step
            if live_metrics.enabled():
                live_metrics.inc("trainer.steps")
                live_metrics.observe("trainer.data_wait_s", t1 - t0)
                live_metrics.observe("trainer.compute_s", t2 - t1)
                live_metrics.set_gauge("trainer.step_s", t2 - t0)
                live_metrics.set_gauge("trainer.last_step", step)
            if self.stall_detector is not None:
                self.stall_detector.observe(step, t2 - t0)
            if self.on_step:
                self.on_step(step, metrics)

            if self.checkpointer is not None and self.ckpt_every and (
                step % self.ckpt_every == 0
            ):
                self._save_checkpoint(step)

            if self._stop_requested:
                if self.checkpointer is not None:
                    t_pre = time.monotonic()
                    with trace.span(trace.STAGE_PREEMPT, "preempt"):
                        handle = self._save_checkpoint(step)
                        with trace.span(trace.STAGE_PREEMPT_PROMOTE,
                                        "preempt_promote"):
                            self._promote(handle)
                    self.preempt_s = time.monotonic() - t_pre
                break
        # surface any background write failure that settled during the run
        # (in-flight saves stay pending: wait_for_checkpoints() drains them)
        self._reap_saves()
        return self.history

    # -- checkpointing --------------------------------------------------------
    def _promote(self, handle) -> None:
        """Make the preemption save durable before the stop."""
        preempt = getattr(self.checkpointer, "preempt", None)
        if callable(preempt):
            # graceful-shutdown budget: promote the newest in-flight save
            # (this one) within the deadline, abandon older queued snapshots
            self.preemption_report = preempt(self._preempt_deadline_s)
        elif handle is not None:
            handle.result()

    def _save_checkpoint(self, step: int):
        """Save; returns the async handle if the checkpointer is async.

        Only the blocking portion (full save for a synchronous
        checkpointer, host snapshot for an async one) lands in
        ``timer.checkpoint_s`` — the trainer's view of training-thread
        blocked time."""
        self._reap_saves()
        t3 = time.monotonic()
        with trace.span(trace.STAGE_CKPT_SAVE, "ckpt_save"):
            extra = None
            state_fn = getattr(self.data_iter, "state", None)
            if callable(state_fn):
                # iterator checkpoint rides along in the meta (tf.data-style),
                # captured on the training thread so it is consistent with
                # the params being saved even under an async engine
                with trace.span(trace.STAGE_PIPELINE_STATE, "pipeline_state"):
                    extra = {"pipeline": state_fn()}
            result = self.checkpointer.save(step, self.state,
                                            extra_meta=extra)
        self.timer.checkpoint_s.append(time.monotonic() - t3)
        if hasattr(result, "done") and hasattr(result, "exception"):
            self._pending_saves.append(result)
            return result
        return None

    def _reap_saves(self) -> None:
        """Drop completed async saves; re-raise the first background error
        (a checkpoint that can never land must not fail silently)."""
        still = []
        error = None
        for h in self._pending_saves:
            if h.done():
                if getattr(h, "cancelled", lambda: False)():
                    continue  # abandoned by preempt(): no error to report
                e = h.exception()
                if e is not None and error is None:
                    error = e
            else:
                still.append(h)
        self._pending_saves = still
        if error is not None:
            raise error

    def wait_for_checkpoints(self) -> None:
        """Drain all outstanding checkpoint work (async writes, burst-buffer
        drains); surfaces any background error."""
        if self.checkpointer is not None and hasattr(self.checkpointer, "wait"):
            self.checkpointer.wait()
        self._pending_saves = []

    def close(self) -> None:
        """Release the input pipeline: closes the data iterator end-to-end
        (prefetcher threads, in-flight reader-pool work) when it supports it.
        Training that abandons a ``repeat()`` pipeline mid-epoch must call
        this (or rely on GC) to stop the background producer promptly."""
        close = getattr(self.data_iter, "close", None)
        if close is not None:
            with trace.span(trace.STAGE_PIPELINE_CLOSE, "pipeline_close"):
                close()

    # -- diagnostics ---------------------------------------------------------
    def report(self) -> Dict[str, Any]:
        s = self.timer.summary()
        compute = max(s["compute"]["total"], 1e-9)
        data_frac = s["data_wait"]["total"] / (s["data_wait"]["total"] + compute)
        return dict(
            steps=len(self.timer.compute_s),
            recovered_step=self.recovered_step,
            data_wait_frac=data_frac,
            straggler_suspect=data_frac > self.straggler_threshold,
            timer=s,
            blocked_ckpt_s=(
                list(self.checkpointer.blocked_s)
                if self.checkpointer is not None and
                hasattr(self.checkpointer, "blocked_s") else []
            ),
            pending_async_saves=sum(
                1 for h in self._pending_saves if not h.done()
            ),
            preemption=(
                dict(
                    committed_step=self.preemption_report.committed_step,
                    abandoned_steps=list(
                        self.preemption_report.abandoned_steps),
                    deadline_s=self.preemption_report.deadline_s,
                    elapsed_s=self.preemption_report.elapsed_s,
                    deadline_met=self.preemption_report.deadline_met,
                    preempt_s=self.preempt_s,
                ) if self.preemption_report is not None else None
            ),
            stalls=(self.stall_detector.summary()
                    if self.stall_detector is not None else None),
        )
