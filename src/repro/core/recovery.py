"""Checkpoint retention + corruption-aware restore + train-state resume.

The paper's restart story (§III-C) is "restart quickly from a checkpoint";
PR 2/7 made the *save* path crash-consistent, this module makes recovery
actually work end-to-end:

* :class:`CheckpointManager` owns **retention** (keep-last-k plus
  keep-every-n milestones) on top of a :class:`~repro.core.checkpoint.
  CheckpointSaver`, with a GC whose invariant is *never delete the only
  valid restore target* and whose ordering is crash-safe: the marker is
  rewritten to the retained set **first**, files are deleted second — a
  crash in between leaves stray files (reclaimed by the next GC), never a
  marker pointing at deleted data.
* :func:`validate_step` / :func:`latest_valid_step` — structural
  validation (meta + index parse, every shard present and long enough for
  its tensor extents) that detects torn writes, rolled-back unsynced data
  and half-deleted steps *without* reading tensor bytes.  ``restore()``
  walks valid steps newest-first, past corrupt/torn/unsynced checkpoints —
  the marker-fallback generalization of the burst-buffer restore: step
  candidates come from the union of the marker and a directory listing, so
  a torn/missing marker alone never makes data unreachable.
* :meth:`CheckpointManager.resume` — TrainState-level restart: restores
  params into a skeleton **and** re-positions a
  :class:`~repro.core.dataset.ResumableIterator` from the pipeline state
  the trainer attached at save time (``extra_meta["pipeline"]``), so a
  resumed run neither skips nor replays samples.

The manager fronts the one save engine, :class:`~repro.core.engine.
CheckpointEngine`, under four preset names (``engine=direct|async|bb|
asyncbb``, each a snapshot mode and a number of tiers): one lifecycle
subsystem instead of "retention *or* the async blocked-time win".  Each
step moves through explicit states —
``SNAPSHOTTED`` (host copy taken) → ``STAGED`` (durable at the engine's
preemption tier) → ``COMMITTED`` (durable at the final tier) — with
retention/GC **deferred past drain commit** via engine hooks, so a step
staged on the fast tier but not yet drained is never collected and
``latest_valid()``/``restore()`` consult both tiers.  ``preempt(
deadline_s)`` forwards the graceful-shutdown budget to the engine
(promote the newest in-flight save, abandon the rest, record it).

The manager is the checkpointer the :class:`~repro.train.trainer.Trainer`
speaks to (``resume``/``save``/``preempt``/``wait``/``blocked_s``),
optionally with a :class:`~repro.core.retry.RetryingStorage` wrap for
transient-fault absorption (``retry_policy=...``).
"""
from __future__ import annotations

import json
import re
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from .. import metrics, trace
from .checkpoint import (CHECKPOINT_MARKER, CheckpointSaver,
                         PreemptionReport, place_sharded, unflatten_pytree,
                         write_marker)
from .engine import NO_SAVER_GC, CheckpointEngine, SaveHandle
from .retry import RetryingStorage, RetryPolicy

#: Per-step lifecycle states of the fused manager (monotonic order).
SNAPSHOTTED = "SNAPSHOTTED"   # host snapshot taken; nothing on storage yet
STAGED = "STAGED"             # durable at the engine's preemption tier
COMMITTED = "COMMITTED"       # durable at the final (slow) tier; GC-eligible
ABANDONED = "ABANDONED"       # given up by preempt() to meet its deadline
_STATE_ORDER = {SNAPSHOTTED: 0, STAGED: 1, COMMITTED: 2}

#: Engine presets: name -> (snapshot mode, number of tiers).
ENGINES = {"direct": ("sync", 1), "async": ("async", 1),
           "bb": ("sync", 2), "asyncbb": ("async", 2)}
#: How many COMMITTED entries the per-step state map keeps around (all
#: non-committed entries are always kept — they are live lifecycle state).
_STATE_HISTORY = 64


def _split_prefix(prefix: str) -> Tuple[str, str]:
    """``"ckpt/model"`` -> ``("ckpt", "model")``."""
    if "/" in prefix:
        d, name = prefix.rsplit("/", 1)
    else:
        d, name = ".", prefix
    return d, name


def list_steps(storage, prefix: str) -> List[int]:
    """Steps present on disk (by filename), sorted ascending.

    Deliberately *not* marker-based: after a torn marker write or a
    half-finished GC the marker under-reports what is restorable.
    """
    d, name = _split_prefix(prefix)
    pat = re.compile(re.escape(name) + r"-(\d+)\.(meta|index|data-\d+-of-\d+)$")
    steps: Set[int] = set()
    try:
        names = storage.listdir(d)
    except (FileNotFoundError, OSError):
        return []
    for n in names:
        m = pat.match(n)
        if m:
            steps.add(int(m.group(1)))
    return sorted(steps)


def marker_steps(storage, prefix: str) -> List[int]:
    """Steps the commit marker claims (``[]`` on a missing/corrupt marker)."""
    d, _ = _split_prefix(prefix)
    path = f"{d}/{CHECKPOINT_MARKER}"
    try:
        if not storage.exists(path):
            return []
        marker = json.loads(storage.read_file(path))
        steps = {int(s) for s in marker.get("all_steps", [])}
        if "latest" in marker and marker["latest"] is not None:
            steps.add(int(marker["latest"]))
        return sorted(steps)
    except (OSError, ValueError, KeyError, TypeError):
        return []


def validate_step(storage, prefix: str, step: int) -> bool:
    """Structural validity: can ``restore(step)`` possibly succeed?

    Checks the meta and index parse as JSON, and that every data shard
    exists with at least the bytes its tensor extents require — which
    catches torn shard writes (truncated content), unsynced writes rolled
    back by a crash (missing/short files), and half-deleted steps, without
    reading any tensor data.
    """
    base = f"{prefix}-{step}"
    try:
        meta = json.loads(storage.read_file(f"{base}.meta"))
        if int(meta["step"]) != step:
            return False
        index = json.loads(storage.read_file(f"{base}.index"))
        n_shards = int(index["n_shards"])
        need = [0] * n_shards
        for e in index["tensors"].values():
            s = int(e["shard"])
            need[s] = max(need[s], int(e["offset"]) + int(e["length"]))
        for s in range(n_shards):
            p = f"{base}.data-{s:05d}-of-{n_shards:05d}"
            if storage.size(p) < need[s]:
                return False
        return True
    except (OSError, ValueError, KeyError, TypeError):
        return False


def valid_steps(storage, prefix: str) -> List[int]:
    """All structurally-valid steps, sorted ascending.  Candidates are the
    union of the directory listing and the marker (marker-fallback: either
    source alone may be damaged)."""
    cands = set(list_steps(storage, prefix)) | set(marker_steps(storage, prefix))
    return [s for s in sorted(cands) if validate_step(storage, prefix, s)]


def latest_valid_step(storage, prefix: str) -> Optional[int]:
    vs = valid_steps(storage, prefix)
    return vs[-1] if vs else None


@dataclass
class ResumeResult:
    """What :meth:`CheckpointManager.resume` recovered.

    ``step is None`` means no restorable checkpoint existed — ``state`` is
    the untouched skeleton and training starts fresh.
    """

    step: Optional[int]
    state: Any
    meta: Dict[str, Any] = field(default_factory=dict)
    pipeline: Optional[Dict[str, Any]] = None
    restore_s: float = 0.0

    @property
    def fresh(self) -> bool:
        return self.step is None


class CheckpointManager:
    """Retention + corruption-aware restore, fused with any save engine.

    ``engine`` names a preset of :class:`~repro.core.engine.
    CheckpointEngine` (:data:`ENGINES`; all four share one commit
    protocol):

    * ``"direct"`` (default) — synchronous sharded save to ``storage``;
    * ``"async"`` — snapshot-only blocking, background write;
    * ``"bb"`` — stage to ``fast_storage``, background drain to
      ``storage``;
    * ``"asyncbb"`` — snapshot-only blocking, background stage *and*
      drain.

    The manager drives every step through explicit lifecycle states
    (:data:`SNAPSHOTTED` → :data:`STAGED` → :data:`COMMITTED`, readable via
    :meth:`step_states`), and owns retention: ``keep_last`` newest steps
    plus ``keep_every`` milestones, with the latest *valid* step always
    kept.  With a background engine, GC is **deferred past drain commit** —
    it runs from the engine's commit hook, on the engine's own thread, so a
    step staged on the fast tier but not yet drained is never deleted and
    stays restorable for a preemption restart.  :meth:`latest_valid` and
    :meth:`restore` consult **both tiers** (fast preferred: it holds the
    newest data and reads faster).

    ``retry_policy`` wraps both storages in :class:`~repro.core.retry.
    RetryingStorage` so transient device faults are absorbed below the
    checkpoint protocol.  :meth:`preempt` forwards the graceful-shutdown
    budget to the engine and records what was abandoned; :meth:`close` is
    idempotent and delivers a pending background error exactly once.
    """

    def __init__(
        self,
        storage,
        prefix: str = "ckpt/model",
        *,
        engine: str = "direct",
        fast_storage=None,
        keep_last: int = 5,
        keep_every: Optional[int] = None,
        retry_policy: Optional[RetryPolicy] = None,
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
        if keep_last < 1:
            raise ValueError(f"keep_last must be >= 1, got {keep_last}")
        if keep_every is not None and keep_every < 1:
            raise ValueError(f"keep_every must be >= 1, got {keep_every}")
        if engine not in ENGINES:
            raise ValueError(
                f"engine must be one of {tuple(ENGINES)}, got {engine!r}")
        snapshot, n_tiers = ENGINES[engine]
        if n_tiers == 2 and fast_storage is None:
            raise ValueError(f"engine={engine!r} requires fast_storage")
        if retry_policy is not None:
            storage = RetryingStorage(storage, retry_policy)
            if fast_storage is not None:
                fast_storage = RetryingStorage(fast_storage, retry_policy)
        self.storage = storage
        self.fast_storage = fast_storage
        self.prefix = prefix
        self.keep_last = keep_last
        self.keep_every = keep_every
        # the slow-tier saver never GCs (keep=inf) and is used for restore
        # and GC bookkeeping only: deletion policy lives in the manager,
        # where "valid" is a first-class concept
        saver_kw = dict(n_shards=n_shards, sync=sync, quantize=quantize,
                        io_threads=io_threads)
        self.saver = CheckpointSaver(storage, prefix, keep=NO_SAVER_GC,
                                     **saver_kw)
        tiers = (fast_storage, storage) if n_tiers == 2 else (storage,)
        self.engine = CheckpointEngine(
            tiers, prefix, snapshot=snapshot, max_pending=max_pending,
            drain_streams=drain_streams, drain_chunk=drain_chunk,
            drain_stall_timeout=drain_stall_timeout,
            drain_requeue_limit=drain_requeue_limit, **saver_kw)
        self.engine.on_staged = self._on_staged
        self.engine.on_drained = self._on_committed
        self.fast_saver = self.engine.savers[0] if n_tiers == 2 else None
        self._dir, _ = _split_prefix(prefix)
        self.gc_deleted: List[int] = []  # every step GC ever removed
        self.abandoned_steps: List[int] = []  # given up by preempt()
        self._sync = sync
        self._closed = False
        self._gc_lock = threading.Lock()
        self._state_lock = threading.Lock()
        self._step_states: "OrderedDict[int, str]" = OrderedDict()

    # -- lifecycle state machine ----------------------------------------------
    @property
    def blocked_s(self) -> List[float]:
        """Training-thread blocked time, straight from the engine."""
        return self.engine.blocked_s

    def _mark(self, step: int, state: str) -> None:
        """Advance ``step``'s lifecycle state (monotonic: hooks firing out
        of order can never move a step backwards).  Runs on the training
        thread and on engine background threads."""
        with self._state_lock:
            cur = self._step_states.get(step)
            if (state in _STATE_ORDER and cur in _STATE_ORDER
                    and _STATE_ORDER[state] < _STATE_ORDER[cur]):
                return
            self._step_states[step] = state
            self._step_states.move_to_end(step)
            committed = [s for s, st in self._step_states.items()
                         if st == COMMITTED]
            for s in committed[:-_STATE_HISTORY]:
                del self._step_states[s]
        if metrics.enabled():
            metrics.inc("ckpt.lifecycle_transitions", 1, state=state)

    def step_states(self) -> Dict[int, str]:
        """Snapshot of the per-step lifecycle map (newest last)."""
        with self._state_lock:
            return dict(self._step_states)

    def _on_staged(self, step: int) -> None:
        """Engine hook: the step committed at the preemption tier."""
        self._mark(step, STAGED)

    def _on_committed(self, step: int) -> None:
        """Engine hook: the step committed at the final tier.  Deferred
        retention runs *here* — never earlier, so an undrained step can't
        be collected out from under a preemption restart."""
        self._mark(step, COMMITTED)
        self.gc()

    # -- save + retention ------------------------------------------------------
    def save(self, step: int, tree: Any,
             extra_meta: Optional[dict] = None) -> SaveHandle:
        """Save through the engine; the handle settles at the first-tier
        commit (already settled for a ``"sync"`` snapshot, whose hooks —
        and so the inline GC of ``"direct"`` — ran inside the call)."""
        if self._closed:
            raise RuntimeError("save() on a closed CheckpointManager")
        handle = self.engine.save(step, tree, extra_meta)
        self._mark(step, SNAPSHOTTED)  # a no-op once a hook moved it on
        return handle

    def retained_steps(self) -> List[int]:
        """The set the current policy would keep, given what's on disk."""
        steps = list_steps(self.storage, self.prefix)
        if not steps:
            return []
        retained: Set[int] = set(steps[-self.keep_last:])
        if self.keep_every:
            retained |= {s for s in steps if s % self.keep_every == 0}
        lv = latest_valid_step(self.storage, self.prefix)
        if lv is not None:
            retained.add(lv)
        return sorted(retained)

    def gc(self) -> List[int]:
        """Apply retention on the final (slow) tier; return the steps
        deleted.

        Ordering is crash-safe: the marker is rewritten to the retained set
        *before* any file is deleted, so a crash mid-GC strands extra files
        (reclaimed by the next GC) but never publishes a marker whose steps
        are gone.  The latest valid step is always in the retained set —
        GC can never delete the only restore target.  With a background
        engine this runs on the engine's commit thread (serialized with its
        marker publishes); the lock only guards against a concurrent
        user-initiated call.  Steps staged on the fast tier but not yet
        drained are untouchable by construction: they have no slow-tier
        files, and the engine's own fast-tier cleanup never evicts the
        newest or still-pending steps.
        """
        with self._gc_lock:
            steps = list_steps(self.storage, self.prefix)
            if not steps:
                return []
            retained = set(self.retained_steps())
            doomed = [s for s in steps if s not in retained]
            lv = latest_valid_step(self.storage, self.prefix)
            latest = lv if lv is not None else max(retained)
            marker = json.dumps(
                dict(latest=latest, all_steps=sorted(retained))).encode()
            write_marker(self.storage, self.saver._marker_path(), marker,
                         sync=self.saver.sync)
            for s in doomed:
                self.saver._delete_step(s)
            self.gc_deleted.extend(doomed)
            return doomed

    # -- introspection ---------------------------------------------------------
    def all_steps(self) -> List[int]:
        """Steps on the final (slow) tier — the set retention governs."""
        return list_steps(self.storage, self.prefix)

    def fast_steps(self) -> List[int]:
        """Steps on the fast tier (``[]`` for single-tier engines)."""
        if self.fast_storage is None:
            return []
        return list_steps(self.fast_storage, self.prefix)

    def valid_steps(self) -> List[int]:
        """Structurally-valid steps across **both** tiers: a step staged on
        the fast tier but not yet drained is restorable (the
        preemption-restart contract)."""
        vs: Set[int] = set(valid_steps(self.storage, self.prefix))
        if self.fast_storage is not None:
            vs |= set(valid_steps(self.fast_storage, self.prefix))
        return sorted(vs)

    def latest_valid(self) -> Optional[int]:
        vs = self.valid_steps()
        return vs[-1] if vs else None

    def latest_step(self) -> Optional[int]:
        """Newest *restorable* step (the Trainer's resume entry point) —
        deliberately stricter than the marker's ``latest``."""
        return self.latest_valid()

    # -- restore ---------------------------------------------------------------
    def _tiers(self) -> List[Tuple[Any, CheckpointSaver]]:
        """(storage, saver) pairs in restore-preference order: fast tier
        first (it holds the newest data and reads faster), slow second."""
        out: List[Tuple[Any, CheckpointSaver]] = []
        if self.fast_saver is not None:
            out.append((self.fast_storage, self.fast_saver))
        out.append((self.storage, self.saver))
        return out

    def restore(self, step: Optional[int] = None
                ) -> Tuple[Dict[str, Any], dict, int]:
        """Restore ``step`` (or the newest restorable step), walking back
        past corrupt/torn/unsynced checkpoints across both tiers.  Returns
        ``(flat, meta, step_restored)``.
        """
        if step is not None:
            for storage, saver in self._tiers():
                if storage is not self.storage and \
                        not validate_step(storage, self.prefix, step):
                    continue
                try:
                    flat, meta = saver.restore(step)
                    return flat, meta, step
                except (OSError, ValueError, KeyError):
                    if storage is self.storage:
                        raise  # slow tier was the last resort: error parity
        with trace.span(trace.STAGE_CKPT_VALIDATE, "ckpt_validate"):
            candidates = self.valid_steps()
        for s in reversed(candidates):
            for storage, saver in self._tiers():
                if not validate_step(storage, self.prefix, s):
                    continue
                try:
                    flat, meta = saver.restore(s)
                    return flat, meta, s
                except (OSError, ValueError, KeyError):
                    continue  # damage validate_step can't see (bad JSON field)
        raise FileNotFoundError(
            f"no restorable checkpoint under {self.prefix}")

    def restore_pytree(self, skeleton: Any, step: Optional[int] = None) -> Any:
        import jax

        flat, _meta, _s = self.restore(step)
        treedef = jax.tree_util.tree_structure(skeleton)
        return unflatten_pytree(flat, treedef)

    def restore_sharded(self, skeleton: Any, shardings: Any,
                        step: Optional[int] = None) -> Any:
        """Elastic restore of ``step`` (or the newest restorable step), in
        :meth:`restore`'s tier order, placed on the mesh of ``shardings``
        (a pytree of shardings matching ``skeleton``)."""
        return place_sharded(self.restore_pytree(skeleton, step), shardings)

    def resume(self, skeleton: Any, *, data_iter: Any = None,
               step: Optional[int] = None) -> ResumeResult:
        """TrainState-level restart: params + input-pipeline position.

        Restores the newest restorable checkpoint into ``skeleton``'s
        structure; if the checkpoint carries pipeline state (the trainer
        attaches ``extra_meta={"pipeline": it.state()}`` at save time) and
        ``data_iter`` supports ``restore_state``, the iterator is
        re-positioned so the resumed run neither skips nor replays samples.
        With no checkpoint at all, returns a fresh :class:`ResumeResult`
        (``step=None``, skeleton untouched).  Traced as ``STAGE_CKPT_VALIDATE``
        (finding the newest valid step), ``STAGE_CKPT_RESTORE`` and
        ``STAGE_ITERATOR_SEEK``.
        """
        import jax

        t0 = time.monotonic()
        try:
            flat, meta, s = self.restore(step)
        except FileNotFoundError:
            if step is not None:
                raise
            return ResumeResult(step=None, state=skeleton)
        treedef = jax.tree_util.tree_structure(skeleton)
        state = unflatten_pytree(flat, treedef)
        pipeline = (meta.get("extra") or {}).get("pipeline")
        if data_iter is not None and pipeline is not None \
                and hasattr(data_iter, "restore_state"):
            with trace.span(trace.STAGE_ITERATOR_SEEK, "iterator_seek"):
                data_iter.restore_state(pipeline)
        return ResumeResult(step=s, state=state, meta=meta,
                            pipeline=pipeline,
                            restore_s=time.monotonic() - t0)

    # -- checkpointer-interface parity ----------------------------------------
    def wait(self) -> None:
        """Block until every issued save has committed at the final tier;
        surfaces the first background error (report-once, engine contract)."""
        self.engine.wait()

    def preempt(self, deadline_s: Optional[float] = None) -> PreemptionReport:
        """Graceful-shutdown budget, forwarded to the engine: stop issuing
        new saves, promote the newest in-flight save to its preemption-tier
        commit within ``deadline_s``, abandon the rest.  Abandoned steps
        are recorded in :attr:`abandoned_steps` and marked
        :data:`ABANDONED` in the lifecycle map."""
        report = self.engine.preempt(deadline_s)
        if report.committed_step is None:
            # the engine's view may lag (e.g. queued cleanups); fall back to
            # what is actually restorable across both tiers
            report.committed_step = self.latest_valid()
        self.abandoned_steps.extend(report.abandoned_steps)
        for s in report.abandoned_steps:
            self._mark(s, ABANDONED)
        return report

    def close(self) -> None:
        """Idempotent shutdown.  The first call closes the engine and lets
        its never-delivered background error (if any) surface; later calls
        are no-ops — the error is delivered exactly once, even when the
        engine still has pending saves."""
        if self._closed:
            return
        self._closed = True
        with trace.span(trace.STAGE_CKPT_CLOSE, "ckpt_close"):
            self.engine.close()
