"""Training loop: input pipeline + checkpointing + fault tolerance.

Integrates the paper's pieces end-to-end:

* data comes through the :mod:`repro.core.dataset` pipeline (parallel map +
  prefetch) and optionally :func:`prefetch_to_device`;
* checkpoints go through one checkpointer every ``ckpt_every`` steps (the
  paper's protocol: §IV-C), a :class:`repro.core.recovery.
  CheckpointManager` or anything that forwards to one.  The trainer speaks
  one interface and probes none of it: ``resume`` on construction,
  ``save`` (which returns a future-like handle that settles at the
  first-tier commit), ``preempt`` at a stop, ``wait`` to drain, and
  ``blocked_s`` in :meth:`Trainer.report`;
* **saves in flight**: with an async snapshot the step loop never blocks
  past the host copy.  The trainer keeps the handles, re-raises a
  background write failure at the next step boundary and at ``run()``
  exit, and leaves a save still in flight when ``run()`` returns pending:
  :meth:`Trainer.wait_for_checkpoints` drains it and surfaces its error;
* **restart**: on construction the trainer resumes the newest restorable
  checkpoint, params and input-pipeline position (crash/preemption
  recovery);
* **preemption**: SIGTERM (or :meth:`Trainer.preempt`) triggers
  checkpoint-and-stop at the next step boundary: the final save, then the
  checkpointer's ``preempt(deadline_s)``, which abandons older queued
  snapshots and promotes the final save to its durability tier within the
  ``preempt_deadline_s`` budget — the outcome lands in
  :attr:`Trainer.preemption_report`;
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
        checkpointer=None,                     # CheckpointManager-like
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
        self._pending_saves: List[Any] = []  # SaveHandles not yet settled
        self.recovered_step: Optional[int] = None
        self.preemption_report = None        # PreemptionReport after a stop
        self.preempt_s: Optional[float] = None  # stop-path wall time
        if install_sigterm:
            signal.signal(signal.SIGTERM, self._handle_sigterm)
        if resume and checkpointer is not None:
            # params AND input-pipeline position (walks back past corrupt
            # checkpoints; repositions a ResumableIterator so no sample is
            # skipped or replayed); the step counter lives in the state
            res = checkpointer.resume(self.state, data_iter=self.data_iter)
            self.state = res.state
            self.recovered_step = res.step

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
                        self._save_checkpoint(step)
                        # graceful-shutdown budget: promote the newest
                        # in-flight save (this one) within the deadline,
                        # abandon older queued snapshots
                        with trace.span(trace.STAGE_PREEMPT_PROMOTE,
                                        "preempt_promote"):
                            self.preemption_report = self.checkpointer.preempt(
                                self._preempt_deadline_s)
                    self.preempt_s = time.monotonic() - t_pre
                break
        # surface any background write failure that settled during the run
        # (in-flight saves stay pending: wait_for_checkpoints() drains them)
        self._reap_saves()
        return self.history

    # -- checkpointing --------------------------------------------------------
    def _save_checkpoint(self, step: int) -> None:
        """Save, and keep the handle until it settles.

        Only the blocking portion (the full save for a synchronous
        snapshot, the host copy for an async one) lands in
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
            handle = self.checkpointer.save(step, self.state,
                                            extra_meta=extra)
        self.timer.checkpoint_s.append(time.monotonic() - t3)
        self._pending_saves.append(handle)

    def _reap_saves(self) -> None:
        """Drop completed async saves; re-raise the first background error
        (a checkpoint that can never land must not fail silently)."""
        still = []
        error = None
        for h in self._pending_saves:
            if h.done():
                if h.cancelled():
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
        if self.checkpointer is not None:
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
            blocked_ckpt_s=(list(self.checkpointer.blocked_s)
                            if self.checkpointer is not None else []),
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
