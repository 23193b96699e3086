"""One run of one cell: set-up, the measured window, and what it leaves.

The traffic file says what the window does: the input's parameters (which
the architecture's module reads), the checkpoint cadence (``"ckpt"``) and
the preemptions (``"preempt"``: the first step, the steps between two, and
with ``"count"`` how many there are at most).  The model, its data and its
step come from the architecture's module (``bench/arch/<arch>.py``).  This
one loop serves every traffic file and every architecture; a new one is a
new file.
"""
from __future__ import annotations

import math
import os
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax

from repro.core import CheckpointManager, ResumableIterator, make_storage
from repro.train.trainer import Trainer

from . import check, hooks

FIRST_STEPS = 3        # set-up steps, the ones the reference follows
CHUNK = 25             # most steps per Trainer.run call between deadline checks


@dataclass
class StepRecord:
    step: int
    t_ask: float
    t_done: float
    data_wait_s: float
    kind: str = "step"          # "step", "preempt" or "resumed"


@dataclass
class Resume:
    t_begin: float
    t_end: float
    saved_state: Any            # the trainer's state when it was preempted
    restored: Any               # ResumeResult of the fresh trainer
    next_batch: Any             # what the preempted stream would have given
    first_batch: Any            # what the resumed stream gave first


@dataclass
class Segment:
    """One Trainer with its feed and checkpoint manager."""
    trainer: Trainer
    feed: hooks.Feed
    data: Any
    manager: Any = None


class CellRun:
    def __init__(self, cfg: dict, traffic: dict, seed: int, devices: list,
                 workdir: str, arch, make_train_step=None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.arch = arch
        self.model = cfg["model"]
        self.batch = cfg["batch"]
        self.devices = devices
        self.workdir = workdir
        make = make_train_step or arch.make_train_step
        self.train_step = make(self.model, devices)
        self.ckpt_log = hooks.CheckpointLog()
        self.steps: List[StepRecord] = []
        self.resumes: List[Resume] = []
        self.preempt_steps: List[int] = []
        self.preempts: List[Tuple[float, float]] = []  # the save and stop
        self.cur = 0
        self.seg: Optional[Segment] = None
        self.corpus = self.epoch = None
        ckpt, pre = traffic.get("ckpt"), traffic.get("preempt")
        self.ckpt_every = ckpt["every_steps"] if ckpt else 0
        self.next_preempt = pre["first_step"] if pre else None

    # -- building blocks -----------------------------------------------------
    def _pipeline(self, keep: int = 0):
        data = ResumableIterator(self.epoch)
        return data, hooks.Feed(data, keep)

    def _manager(self):
        ckpt = self.traffic.get("ckpt")
        if not ckpt:
            return None
        slow = make_storage("native", os.path.join(self.workdir, "slow"))
        fast = make_storage("native", os.path.join(self.workdir, "fast"))
        mgr = CheckpointManager(slow, f"ckpt/{self.model['arch']}",
                                engine=ckpt["engine"],
                                fast_storage=fast,
                                max_pending=ckpt["max_pending"])
        return hooks.Checkpoints(mgr, self.ckpt_log)

    def _on_step(self, feed: hooks.Feed, trainer_ref: list):
        def on_step(step: int, metrics: Dict) -> None:
            now = time.monotonic()
            wait = trainer_ref[0].timer.data_wait_s[-1]
            self.steps.append(StepRecord(step, feed.last_ask, now, wait))
            self.cur = step
        return on_step

    def _segment(self, state, *, resume: bool, keep: int = 0) -> Segment:
        data, feed = self._pipeline(keep)
        mgr = self._manager()
        ref: list = []
        tr = Trainer(self.train_step, state, feed, checkpointer=mgr,
                     ckpt_every=self.ckpt_every, resume=resume,
                     on_step=self._on_step(feed, ref))
        ref.append(tr)
        return Segment(tr, feed, data, mgr)

    def _close(self, seg: Segment, wait: bool = True) -> None:
        try:
            if wait and seg.manager is not None:
                seg.trainer.wait_for_checkpoints()
        finally:
            seg.trainer.close()
            seg.data.close()
            if seg.manager is not None:
                seg.manager.close()

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        """Corpus, state, pipeline, trainer; then the first steps through
        the window's own call and feed, which compile every program the
        window runs.  Keeps what the reference needs of them, copied to the
        host as it is taken and before the next step could donate it
        (``kept``): the parameters before the first step, the first
        gradient as the optimizer got it, and the parameters after the
        last.  The only state left on the device is the trainer's."""
        t0 = time.monotonic()
        self.corpus = self.arch.build_corpus(
            self.cfg, self.seed, os.path.join(self.workdir, "corpus"))
        self.epoch = self.arch.epoch_factory(self.corpus, self.cfg,
                                             self.traffic, self.batch)
        t1 = time.monotonic()
        state = self.arch.make_state(self.seed, self.model, self.devices)
        self.skeleton = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
        self.seg = self._segment(state, resume=False, keep=FIRST_STEPS)
        p0 = check.to_host(state["params"])
        del state
        trainer = self.seg.trainer
        trainer.run(1)
        g1 = self.arch.first_gradient(p0, trainer.state, self.model)
        trainer.run(FIRST_STEPS - 1)
        self.kept = (p0, g1, check.to_host(trainer.state["params"]))
        self.first_losses = [h["loss"] for h in trainer.history]
        self.first_batches = list(self.seg.feed.kept)
        self.setup_phases = {"corpus_s": t1 - t0,
                             "weights_and_first_steps_s":
                             time.monotonic() - t1}

    # -- the window ----------------------------------------------------------
    def window(self, seconds: float) -> None:
        """Train until the close.  Each call makes at most the steps that
        the step time of the call before says fit in the time left (the
        first call one step, to time it), so the window's last step begins
        before the close and ends within about one step of it."""
        self.t_w0 = time.monotonic()
        self.t_w1 = self.t_w0 + seconds
        pre = self.traffic.get("preempt") or {}
        step_s = None
        while (now := time.monotonic()) < self.t_w1:
            if self.next_preempt is not None \
                    and self.cur + 1 >= self.next_preempt:
                self._preempt_and_resume()
                self.next_preempt += pre["every_steps"]
                if len(self.preempt_steps) == pre.get("count"):
                    self.next_preempt = None
                continue
            n = 1 if step_s is None else min(
                CHUNK, max(1, math.ceil((self.t_w1 - now) / step_s)))
            if self.next_preempt is not None:
                n = min(n, self.next_preempt - self.cur - 1)
            done = len(self.steps)
            self.seg.trainer.run(n)
            if len(self.steps) > done:
                step_s = (time.monotonic() - now) / (len(self.steps) - done)

    def _preempt_and_resume(self) -> None:
        """The next step is the preempted one: it runs, saves and stops
        within the notice (``Trainer.preempt``); a fresh trainer and
        pipeline then resume from what was saved."""
        pre = self.traffic["preempt"]
        seg = self.seg
        seg.trainer.ckpt_every = 0      # this step's save is the preemption's
        seg.trainer.preempt(pre["deadline_s"])
        seg.trainer.run(1)
        self.steps[-1].kind = "preempt"
        self.preempt_steps.append(self.cur)
        saved = seg.trainer.state
        next_batch = next(seg.data)
        self._close(seg, wait=False)
        t0 = time.monotonic()
        self.preempts.append((self.steps[-1].t_done, t0))
        self.seg = self._segment(self.skeleton, resume=True, keep=1)
        if self.seg.trainer.recovered_step != self.cur:
            raise RuntimeError(f"resumed at step "
                               f"{self.seg.trainer.recovered_step}, "
                               f"preempted at {self.cur}")
        self.seg.trainer.run(1)
        self.steps[-1].kind = "resumed"
        self.resumes.append(Resume(
            t0, self.steps[-1].t_done, saved, self.ckpt_log.restored[-1],
            next_batch, self.seg.feed.kept[0]))

    def finish(self) -> None:
        """Wait for the saves still draining, then release the pipeline,
        the trainer and the checkpoint manager."""
        if self.seg is not None:
            seg, self.seg = self.seg, None
            self._close(seg, wait=True)

    # -- what the window did -------------------------------------------------
    def in_window(self) -> List[StepRecord]:
        return [s for s in self.steps if self.t_w0 <= s.t_done <= self.t_w1]

    def host_activity(self) -> List[Tuple[str, float, float]]:
        """``(name, t0, t1)`` on the host clock of every call the window
        made: waiting for a batch, the step from dispatch to its loss on the
        host, the blocked part of a save, a preemption's save and stop, a
        resume."""
        out = []
        for s in self.steps:
            out.append(("next_batch", s.t_ask, s.t_ask + s.data_wait_s))
            out.append(("train_step", s.t_ask + s.data_wait_s, s.t_done))
        out.extend(("save", t, t + b) for t, b in self.ckpt_log.saved.values())
        out.extend(("preempt", a, b) for a, b in self.preempts)
        out.extend(("resume", r.t_begin, r.t_end) for r in self.resumes)
        return out

    def periodic_saves(self) -> List[int]:
        pre = set(self.preempt_steps)
        return sorted(s for s, (t, _) in self.ckpt_log.saved.items()
                      if self.t_w0 <= t <= self.t_w1 and s not in pre)
