"""Wrappers the benchmark puts around its calls into the program.

Each records the host clock of the calls it passes on, for the run's
records and for naming what the host was doing around each device idle gap
of a traced run.  They change nothing that passes through them; the program
itself is not edited.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List


class Feed:
    """The data iterator as the trainer sees it: times each ``next`` and
    keeps the first ``keep`` batches for the correctness check.  Every other
    attribute (``state``, ``restore_state``, ``close``) is the wrapped
    iterator's."""

    def __init__(self, inner, keep: int = 0):
        self._inner = inner
        self._it = iter(inner)
        self._keep = keep
        self.kept: List[Any] = []
        self.last_ask = None

    def __iter__(self):
        return self

    def __next__(self):
        self.last_ask = time.monotonic()
        batch = next(self._it)
        if len(self.kept) < self._keep:
            self.kept.append(batch)
        return batch

    def __getattr__(self, name):
        return getattr(self._inner, name)


class Checkpoints:
    """Proxy of a ``CheckpointManager``: times ``save`` (the blocked part)
    and ``resume``, and stamps the slow-tier commit of every step through the
    engine's drain hook."""

    def __init__(self, manager, log: "CheckpointLog"):
        self._mgr = manager
        self._log = log
        engine = manager.engine
        committed = engine.on_drained

        def on_drained(step: int) -> None:
            log.committed[step] = time.monotonic()
            if committed is not None:
                committed(step)

        engine.on_drained = on_drained

    def save(self, step: int, tree: Any, extra_meta=None):
        t0 = time.monotonic()
        out = self._mgr.save(step, tree, extra_meta)
        self._log.saved[step] = (t0, time.monotonic() - t0)
        return out

    def resume(self, skeleton: Any, **kw):
        res = self._mgr.resume(skeleton, **kw)
        self._log.restored.append(res)
        return res

    def __getattr__(self, name):
        return getattr(self._mgr, name)


class CheckpointLog:
    """What the checkpoint proxies saw, over every manager of a run."""

    def __init__(self):
        self.saved: Dict[int, tuple] = {}       # step -> (t_save, blocked_s)
        self.committed: Dict[int, float] = {}   # step -> t of slow-tier commit
        self.restored: List[Any] = []           # ResumeResult of each resume
