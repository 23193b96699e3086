"""A tf.data-like input pipeline (paper §II-A / Fig. 2), in pure Python.

The pipeline is a chain of lazily-evaluated nodes::

    Dataset.list_files(storage).shard(n_workers, rank)
        .shuffle(buffer_size, seed)
        .interleave(stream_shard, cycle_length=8,      # parallel shard streaming
                    block_length=16, num_parallel_calls=8)
        .map_and_batch(decode_into, 64,                # fused decode-into-buffer
                       num_parallel_calls=8)
        .prefetch(1)                                   # background thread

Semantics follow the paper's description of the TF Dataset API:

* ``map(num_parallel_calls=k)`` keeps ``k`` elements in flight on the shared
  :class:`~repro.core.readerpool.ReaderPool`.  ``deterministic=True``
  (default) yields results in input order — like TF — by maintaining a
  window of futures; ``False`` yields in completion order via
  ``wait(FIRST_COMPLETED)`` (lower latency jitter, straggler mitigation).
* ``interleave`` is tf.data's ``parallel_interleave``: ``cycle_length``
  input elements are expanded to sub-streams consumed round-robin,
  ``block_length`` elements at a time; with ``num_parallel_calls`` the next
  block of each cycle slot is fetched on the reader pool while earlier
  slots' blocks are being consumed.  Output order is deterministic
  (independent of thread timing).
* ``map_and_batch`` is the fused tf.contrib path: elements decode directly
  into a preallocated ``(batch, *out_shape)`` buffer — no per-element
  ``np.asarray`` + ``np.stack`` — with error slots refilled from upstream
  when ``ignore_errors=True``.
* ``shard(n, i)`` keeps every n-th element (multi-worker data sharding).
* ``shuffle`` is TF's streaming buffer shuffle: fill a ``buffer_size``
  reservoir, emit a uniformly random element, refill.
* ``batch`` stacks ``n`` consecutive elements (pytree-aware) with one
  allocation per batch.
* ``prefetch`` inserts the background-thread prefetcher (see prefetcher.py).
* ``cache`` memoizes the upstream stream in host memory after epoch 1
  (paper §IV-B: "after the first epoch all samples ... cached in memory").
* ``ignore_errors`` drops elements whose map fn raised (tf.contrib.data.
  ignore_errors), so corrupt records don't kill a large run.

Iterators are closeable end-to-end: ``iter(ds)`` returns an iterator whose
``close()`` propagates through every node down to prefetcher background
threads and in-flight reader-pool futures, so an abandoned pipeline releases
its resources immediately instead of waiting for GC.
"""
from __future__ import annotations

import inspect
import itertools
import random
import threading
from collections import deque
from concurrent.futures import FIRST_COMPLETED, Future
from concurrent.futures import wait as futures_wait
from typing import (Any, Callable, Iterable, Iterator, List, NamedTuple,
                    Optional, Sequence)

import numpy as np

from .. import metrics, trace
from .prefetcher import PrefetchIterator
from .readerpool import reader_pool


class _ErrorMarker:
    """Carries an element-level failure downstream (TF semantics: the error
    surfaces at the iterator unless ``ignore_errors()`` drops it)."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


def _close_iter(it: Any) -> None:
    """Propagate close to any iterator that supports it (generators,
    PrefetchIterator, _RaisingIterator)."""
    close = getattr(it, "close", None)
    if close is not None:
        close()


class _RaisingIterator:
    """Terminal iterator: unwraps :class:`_ErrorMarker` into raises and
    forwards ``close()`` up the node chain."""

    __slots__ = ("_it",)

    def __init__(self, it: Iterator):
        self._it = it

    def __iter__(self) -> "_RaisingIterator":
        return self

    def __next__(self) -> Any:
        item = next(self._it)
        if isinstance(item, _ErrorMarker):
            raise item.exc
        return item

    def close(self) -> None:
        _close_iter(self._it)

    def __enter__(self) -> "_RaisingIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _raising(it: Iterator) -> Iterator:
    return _RaisingIterator(it)


def _take_future(window: List[Future], deterministic: bool) -> Future:
    """Next future to consume: input order, or first-completed order."""
    if deterministic or len(window) == 1:
        return window.pop(0)
    done, _ = futures_wait(window, return_when=FIRST_COMPLETED)
    for i, f in enumerate(window):
        if f in done:
            return window.pop(i)
    return window.pop(0)  # unreachable: wait() returned at least one


class _InterleaveSlot:
    """One cycle slot: an input element and its lazily-opened sub-iterator.

    A slot placed from an :class:`InterleaveState` opens its sub-stream
    ``skip`` elements in, and its first turn delivers ``block`` elements
    (``None``: a full block) and, if ``last``, retires the slot after them;
    a slot with nothing left to deliver retires unopened."""

    __slots__ = ("item", "it", "skip", "block", "last")

    def __init__(self, item: Any, skip: int = 0,
                 block: Optional[int] = None, last: bool = False):
        self.item = item
        self.it: Optional[Iterator] = None
        self.skip = skip
        self.block = block
        self.last = last


class InterleaveState(NamedTuple):
    """Where :meth:`Dataset.interleave` stands after some delivered
    elements, as :func:`interleave_state` derives it with no I/O.

    ``cycle``: the cycle's slots in round-robin order, head first, as
    ``(source_index, taken, block, last)``: elements taken, the elements
    its next turn delivers, and whether the slot retires after that turn;
    ``entered``: source elements that have entered the cycle (retired ones
    included)."""

    cycle: tuple
    entered: int


def _shard_key(item: Any) -> Any:
    """Stable identity for a pipeline input element: the shard path for
    ``(path, labels)`` tuples, the element itself otherwise."""
    if isinstance(item, tuple) and item and isinstance(item[0], str):
        return item[0]
    return item


class ShardQuarantine:
    """Cross-epoch registry of shards that failed mid-stream.

    ``interleave(quarantine=...)`` records every shard whose open or read
    failed (after any retry budget underneath is exhausted).  On the next
    epoch, instead of silently re-paying the failure, the engine
    *probe-reads* each quarantined shard as it comes up: one cheap record
    pull through the same ``fn``.  A shard that heals (the fault was
    transient at a longer horizon — an OST failover finished, a flaky mount
    recovered) is **re-admitted** and streams normally again, counted in
    ``pipeline.readmitted_shards``; one that is still bad is skipped for
    the rest of the epoch without burning its full retry budget.

    Thread-safe; share one instance across epochs (and pipelines) for the
    same corpus.  ``key`` maps an input element to its stable identity
    (default: the shard path).
    """

    def __init__(self, key: Callable[[Any], Any] = _shard_key):
        self._key = key
        self._lock = threading.Lock()
        self._bad: dict = {}            # key -> repr(last error)
        self.readmitted = 0             # attribute mirror of the live counter

    def quarantine(self, item: Any, exc: BaseException) -> None:
        with self._lock:
            self._bad[self._key(item)] = repr(exc)

    def is_quarantined(self, item: Any) -> bool:
        with self._lock:
            return self._key(item) in self._bad

    def readmit(self, item: Any) -> None:
        with self._lock:
            if self._bad.pop(self._key(item), None) is not None:
                self.readmitted += 1

    def quarantined(self) -> List[Any]:
        """Currently-quarantined keys (snapshot)."""
        with self._lock:
            return list(self._bad)

    def __len__(self) -> int:
        with self._lock:
            return len(self._bad)


class Dataset:
    """Lazily-evaluated pipeline node; iterate to pull elements through.

    ``seek``, optional, is ``start -> Dataset``: the same stream from
    element ``start`` on, positioned without producing the elements before
    it.  Transformations do not carry it forward; only a builder that can
    prove its stream's order attaches it (:func:`sharded_image_pipeline`).
    """

    def __init__(self, gen_fn: Callable[[], Iterator],
                 seek: Optional[Callable[[int], "Dataset"]] = None):
        self._gen_fn = gen_fn
        self._seek = seek

    @property
    def seekable(self) -> bool:
        """True where :meth:`seek` positions the stream without reading."""
        return self._seek is not None

    def seek(self, start: int) -> Optional["Dataset"]:
        """This stream from element ``start`` on, or ``None`` where the
        Dataset cannot position itself without reading the elements
        before it.  A ``start`` at or past the end gives an empty stream."""
        if self._seek is None:
            return None
        return self._seek(max(int(start), 0))

    # -- sources ---------------------------------------------------------------
    @staticmethod
    def from_tensor_slices(items: Sequence) -> "Dataset":
        items = list(items)
        return Dataset(lambda: iter(items))

    @staticmethod
    def list_files(storage, dirpath: str = ".", suffix: str = ".rrf") -> "Dataset":
        # sorted: storage listdir order is backend-dependent (POSIX readdir,
        # object-store listing, ...) — a fixed seed must shuffle the same
        # file sequence on every backend for reproducible epochs.
        names = sorted(n for n in storage.listdir(dirpath) if n.endswith(suffix))
        if dirpath not in (".", ""):
            names = [f"{dirpath}/{n}" for n in names]
        return Dataset.from_tensor_slices(names)

    @staticmethod
    def range(n: int) -> "Dataset":
        return Dataset(lambda: iter(range(n)))

    # -- transformations -------------------------------------------------------
    def shard(self, num_shards: int, index: int) -> "Dataset":
        """Keep elements whose position ``% num_shards == index`` (tf.data
        ``Dataset.shard``): disjoint per-worker subsets that cover the input."""
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        if not 0 <= index < num_shards:
            raise ValueError(f"index {index} out of range [0, {num_shards})")
        upstream = self._gen_fn

        def gen():
            it = upstream()
            try:
                for i, item in enumerate(it):
                    if i % num_shards == index:
                        yield item
            finally:
                _close_iter(it)

        return Dataset(gen)

    def shuffle(self, buffer_size: int, seed: Optional[int] = None) -> "Dataset":
        upstream = self._gen_fn

        def gen():
            rng = random.Random(seed)
            buf: List[Any] = []
            it = upstream()
            try:
                for item in it:
                    buf.append(item)
                    if len(buf) >= buffer_size:
                        idx = rng.randrange(len(buf))
                        buf[idx], buf[-1] = buf[-1], buf[idx]
                        yield buf.pop()
                while buf:
                    idx = rng.randrange(len(buf))
                    buf[idx], buf[-1] = buf[-1], buf[idx]
                    yield buf.pop()
            finally:
                _close_iter(it)

        return Dataset(gen)

    def map(
        self,
        fn: Callable[[Any], Any],
        num_parallel_calls: int = 1,
        deterministic: bool = True,
    ) -> "Dataset":
        upstream = self._gen_fn
        fn_label = getattr(fn, "__name__", "map_fn")

        def safe_fn(item):
            # one decode-stage span per element; nested storage_read spans
            # (from fn's read_file call) attribute the I/O share of this time
            with trace.span(trace.STAGE_DECODE, fn_label), \
                    metrics.timer("pipeline.decode_s"):
                try:
                    out = fn(item)
                except Exception as e:  # surfaced at the iterator (TF semantics)
                    return _ErrorMarker(e)
                metrics.inc("pipeline.records")
                return out

        if num_parallel_calls <= 1:
            def gen_serial():
                it = upstream()
                try:
                    for item in it:
                        yield safe_fn(item)
                finally:
                    _close_iter(it)
            return Dataset(gen_serial)

        def gen_parallel():
            # shared pool, sized once; the window caps this stage's in-flight
            # work at num_parallel_calls even when the pool is larger
            pool = reader_pool(num_parallel_calls)
            src = upstream()
            window: List[Future] = []
            try:
                # prime the window
                for item in src:
                    window.append(pool.submit(safe_fn, item))
                    if len(window) >= num_parallel_calls:
                        break
                for item in src:
                    fut = _take_future(window, deterministic)
                    window.append(pool.submit(safe_fn, item))
                    yield fut.result()
                while window:
                    yield _take_future(window, deterministic).result()
            finally:
                for f in window:
                    f.cancel()
                _close_iter(src)

        return Dataset(gen_parallel)

    def interleave(
        self,
        fn: Callable[[Any], Iterable],
        cycle_length: int = 4,
        block_length: int = 1,
        num_parallel_calls: int = 0,
        quarantine: Optional[ShardQuarantine] = None,
        start: Optional[InterleaveState] = None,
    ) -> "Dataset":
        """Expand each input element to a sub-stream via ``fn`` and interleave
        ``cycle_length`` of them round-robin, ``block_length`` elements at a
        time (tf.data ``parallel_interleave``).

        ``start`` (from :func:`interleave_state`) begins the stream where
        that state stands: the first ``start.entered`` upstream elements
        are pulled without opening any but the cycle's, whose sub-streams
        open advanced past the elements already taken.  The result is the
        unstarted stream from that element on, provided every sub-stream
        holds the counts the state was derived from.

        With ``num_parallel_calls > 1`` the next block of up to
        ``min(cycle_length, num_parallel_calls)`` slots is fetched on the
        shared reader pool while earlier blocks are consumed — so e.g. eight
        ``.rrf`` shards stream concurrently record-by-record instead of one
        whole file per element.  Each slot has at most one outstanding fetch,
        which serializes its sub-iterator without locks.  Output order is
        deterministic regardless of thread timing.

        Errors (``fn`` raising, or a sub-iterator raising mid-stream) become
        element-level markers: the failing slot is retired and the rest of
        the cycle keeps streaming, so one corrupt shard doesn't kill the
        epoch when ``ignore_errors()`` is downstream.

        With a :class:`ShardQuarantine`, failed elements are additionally
        recorded by identity; on later epochs quarantined elements are
        probe-read before re-entering the cycle — healed shards re-admit
        (``pipeline.readmitted_shards``), still-bad ones are skipped for
        the epoch.
        """
        if cycle_length < 1:
            raise ValueError(f"cycle_length must be >= 1, got {cycle_length}")
        if block_length < 1:
            raise ValueError(f"block_length must be >= 1, got {block_length}")
        if start is not None and quarantine is not None:
            raise ValueError("start= cannot model a quarantine's skips")
        upstream = self._gen_fn
        fn_label = getattr(fn, "__name__", "interleave_fn")

        def _fetch_block(slot: _InterleaveSlot):
            """Pull up to block_length elements from one slot (pool task).

            Returns ``(values, exhausted)``; per-element failures append a
            marker and retire the slot."""
            with trace.span(trace.STAGE_DECODE, fn_label), \
                    metrics.timer("pipeline.interleave_block_s"):
                out: List[Any] = []
                if slot.block is None:
                    n, last = block_length, False
                else:
                    n, last = slot.block, slot.last
                    slot.block = None
                    if n == 0:
                        return out, last    # spent: retires unopened
                if slot.it is None:
                    try:
                        slot.it = iter(fn(slot.item))
                        if slot.skip:
                            next(itertools.islice(slot.it, slot.skip,
                                                  slot.skip), None)
                    except Exception as e:
                        # a shard we could not even open is dropped from the
                        # cycle — with a RetryingStorage underneath, the
                        # error arriving here means the retry budget is
                        # already exhausted
                        metrics.inc("pipeline.quarantined_shards")
                        if quarantine is not None:
                            quarantine.quarantine(slot.item, e)
                        return [_ErrorMarker(e)], True
                for _ in range(n):
                    try:
                        out.append(next(slot.it))
                    except StopIteration:
                        return out, True
                    except Exception as e:
                        metrics.inc("pipeline.quarantined_shards")
                        if quarantine is not None:
                            quarantine.quarantine(slot.item, e)
                        out.append(_ErrorMarker(e))
                        return out, True
                return out, last

        def _probe_readmit(item) -> bool:
            """One cheap open + single-record pull of a quarantined shard.
            True ⇒ healed (caller re-admits); False ⇒ still bad, skip."""
            it = None
            try:
                it = iter(fn(item))
                next(it, None)
                return True
            except Exception:
                return False
            finally:
                _close_iter(it)

        parallel = num_parallel_calls > 1
        window = min(cycle_length, num_parallel_calls) if parallel else 0

        def _placed(src) -> deque:
            """The cycle ``start`` describes, its upstream elements pulled
            from ``src``; only the cycle's are kept."""
            wanted = {slot[0] for slot in start.cycle}
            items = {i: item for i, item in
                     enumerate(itertools.islice(src, start.entered))
                     if i in wanted}
            return deque(_InterleaveSlot(items[i], taken, block, last)
                         for i, taken, block, last in start.cycle
                         if i in items)

        def gen():
            pool = reader_pool(num_parallel_calls) if parallel else None
            src = upstream()
            cycle: deque = deque()      # slots in round-robin order
            futs: dict = {}             # slot -> in-flight block fetch
            src_done = False
            try:
                if start is not None:
                    cycle = _placed(src)
                while True:
                    while len(cycle) < cycle_length and not src_done:
                        try:
                            nxt = next(src)
                        except StopIteration:
                            src_done = True
                            break
                        if isinstance(nxt, _ErrorMarker):
                            yield nxt
                            continue
                        if quarantine is not None and \
                                quarantine.is_quarantined(nxt):
                            if _probe_readmit(nxt):
                                quarantine.readmit(nxt)
                                metrics.inc("pipeline.readmitted_shards")
                            else:
                                continue    # still bad: skip this epoch
                        cycle.append(_InterleaveSlot(nxt))
                    if not cycle:
                        return
                    if pool is not None:
                        for s in itertools.islice(cycle, 0, window):
                            if s not in futs:
                                futs[s] = pool.submit(_fetch_block, s)
                    slot = cycle.popleft()
                    if pool is not None:
                        fut = futs.pop(slot, None)
                        if fut is None:
                            fut = pool.submit(_fetch_block, slot)
                        vals, exhausted = fut.result()
                    else:
                        vals, exhausted = _fetch_block(slot)
                    if not exhausted:
                        cycle.append(slot)
                    yield from vals
            finally:
                for f in futs.values():
                    f.cancel()
                # cancel() cannot stop RUNNING fetches — wait them out so no
                # pool worker is still inside next(slot.it) when we close the
                # sub-iterators (generator.close() from another thread would
                # raise "generator already executing" and abort the teardown)
                if futs:
                    futures_wait(list(futs.values()))
                for s in cycle:
                    _close_iter(s.it)
                _close_iter(src)

        return Dataset(gen)

    def ignore_errors(self) -> "Dataset":
        upstream = self._gen_fn

        def gen():
            it = upstream()
            try:
                for item in it:
                    if isinstance(item, _ErrorMarker):
                        # live drop-rate signal (a corpus going bad shows up
                        # here long before accuracy does)
                        metrics.inc("pipeline.dropped")
                        continue
                    yield item
            finally:
                _close_iter(it)

        return Dataset(gen)

    def batch(self, batch_size: int, drop_remainder: bool = True) -> "Dataset":
        upstream = self._gen_fn

        def _stack(elems: List[Any]):
            first = elems[0]
            if isinstance(first, tuple):
                return tuple(
                    _stack([e[i] for e in elems]) for i in range(len(first))
                )
            if isinstance(first, dict):
                return {k: _stack([e[k] for e in elems]) for k in first}
            if isinstance(first, np.ndarray):
                # one allocation + per-element copy into it (no asarray churn)
                out = np.empty((len(elems),) + first.shape, first.dtype)
                for i, e in enumerate(elems):
                    out[i] = e
                return out
            return np.asarray(elems)

        def gen():
            buf: List[Any] = []
            it = _raising(upstream())
            try:
                for item in it:
                    buf.append(item)
                    if len(buf) == batch_size:
                        yield _stack(buf)
                        buf = []
                if buf and not drop_remainder:
                    yield _stack(buf)
            finally:
                _close_iter(it)

        return Dataset(gen)

    def map_and_batch(
        self,
        fn: Callable[[Any, np.ndarray], Any],
        batch_size: int,
        *,
        num_parallel_calls: int = 1,
        drop_remainder: bool = True,
        out_shape: Sequence[int] = (),
        out_dtype: Any = np.float32,
        ignore_errors: bool = False,
    ) -> "Dataset":
        """Fused map+batch (tf.contrib.data ``map_and_batch``): ``fn(item,
        out)`` decodes each element *directly into its row of a preallocated*
        ``(batch_size, *out_shape)`` buffer and returns an optional auxiliary
        scalar (e.g. the label).

        Batches are the buffer alone, or ``(buffer, np.asarray(auxes))`` when
        ``fn`` returns non-None — no per-element ``np.asarray``/``np.stack``
        ever runs.  With ``num_parallel_calls > 1``, up to that many rows
        fill concurrently on the shared reader pool.  ``ignore_errors=True``
        gives the fused equivalent of ``map().ignore_errors().batch()``: a
        failed row is refilled from the next upstream element (same element
        multiset as the legacy chain; row order within the batch may differ
        after a failure).
        """
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        upstream = self._gen_fn
        fn_label = getattr(fn, "__name__", "map_and_batch_fn")
        out_shape = tuple(out_shape)

        class _Exhausted(Exception):
            pass

        def _next_item(src):
            while True:
                try:
                    item = next(src)
                except StopIteration:
                    raise _Exhausted from None
                if isinstance(item, _ErrorMarker):
                    if ignore_errors:
                        metrics.inc("pipeline.dropped")
                        continue
                    raise item.exc
                return item

        def _row(buf, i):
            # 0-d rows need an explicit view: buf[i] on a 1-D buffer is a
            # scalar copy, so fn's writes would be lost
            return buf[i] if out_shape else buf[i:i + 1].reshape(())

        def _run(item, row):
            with trace.span(trace.STAGE_DECODE, fn_label), \
                    metrics.timer("pipeline.decode_s"):
                out = fn(item, row)
            metrics.inc("pipeline.records")
            return out

        def _assemble(buf, aux, rows):
            """Finalize one batch from the filled row indices."""
            if len(rows) < buf.shape[0]:
                rows = sorted(rows)
                buf = buf[rows]
                aux = [aux[i] for i in rows]
            if all(a is None for a in aux):
                return buf
            return buf, np.asarray(aux)

        def gen_serial():
            src = upstream()
            try:
                while True:
                    buf = np.empty((batch_size,) + out_shape, out_dtype)
                    aux: List[Any] = [None] * batch_size
                    filled: List[int] = []
                    try:
                        for i in range(batch_size):
                            while True:
                                item = _next_item(src)
                                try:
                                    aux[i] = _run(item, _row(buf, i))
                                except Exception as e:
                                    if ignore_errors:
                                        metrics.inc("pipeline.dropped")
                                        continue
                                    yield _ErrorMarker(e)
                                    return
                                filled.append(i)
                                break
                    except _Exhausted:
                        if filled and not drop_remainder:
                            yield _assemble(buf, aux, filled)
                        return
                    yield _assemble(buf, aux, filled)
            finally:
                _close_iter(src)

        if num_parallel_calls <= 1:
            return Dataset(gen_serial)

        def gen_parallel():
            pool = reader_pool(num_parallel_calls)
            src = upstream()
            try:
                exhausted = False
                while not exhausted:
                    buf = np.empty((batch_size,) + out_shape, out_dtype)
                    aux: List[Any] = [None] * batch_size
                    filled: List[int] = []
                    to_fill: deque = deque(range(batch_size))
                    inflight: dict = {}  # future -> row index
                    error: Optional[BaseException] = None
                    while (to_fill or inflight) and error is None:
                        while (to_fill and not exhausted
                               and len(inflight) < num_parallel_calls):
                            row = to_fill.popleft()
                            try:
                                item = _next_item(src)
                            except _Exhausted:
                                exhausted = True
                                break
                            inflight[pool.submit(_run, item, _row(buf, row))] = row
                        if not inflight:
                            break
                        done, _ = futures_wait(
                            inflight, return_when=FIRST_COMPLETED)
                        for f in done:
                            row = inflight.pop(f)
                            exc = f.exception()
                            if exc is None:
                                aux[row] = f.result()
                                filled.append(row)
                            elif ignore_errors:
                                metrics.inc("pipeline.dropped")
                                to_fill.append(row)  # refill from upstream
                            elif error is None:
                                error = exc
                    if error is not None:
                        for f in inflight:
                            f.cancel()
                        futures_wait(list(inflight))  # rows may still be writing
                        yield _ErrorMarker(error)
                        return
                    if len(filled) == batch_size or (filled and not drop_remainder):
                        yield _assemble(buf, aux, filled)
            finally:
                _close_iter(src)

        return Dataset(gen_parallel)

    def repeat(self, count: Optional[int] = None) -> "Dataset":
        upstream = self._gen_fn

        def gen():
            i = 0
            while count is None or i < count:
                it = upstream()
                try:
                    yield from it
                finally:
                    _close_iter(it)
                i += 1

        return Dataset(gen)

    def take(self, n: int) -> "Dataset":
        upstream = self._gen_fn

        def gen():
            it = upstream()
            try:
                for _ in range(n):
                    try:
                        yield next(it)
                    except StopIteration:
                        return
            finally:
                _close_iter(it)

        return Dataset(gen)

    def cache(self) -> "Dataset":
        upstream = self._gen_fn
        memo: dict = {"items": None, "lock": threading.Lock()}

        def gen():
            with memo["lock"]:
                cached = memo["items"]
            if cached is not None:
                yield from cached
                return
            # epoch 1 (possibly concurrent with another epoch-1 iterator:
            # each computes independently; a partial iteration never
            # publishes, so the memo only ever holds a complete stream)
            items = []
            it = upstream()
            try:
                for item in it:
                    items.append(item)
                    yield item
            finally:
                _close_iter(it)
            with memo["lock"]:
                if memo["items"] is None:
                    memo["items"] = items

        return Dataset(gen)

    def prefetch(self, buffer_size: int = 1) -> "Dataset":
        if buffer_size <= 0:
            return self
        upstream = self._gen_fn
        return Dataset(lambda: PrefetchIterator(upstream(), buffer_size))

    # -- sinks -------------------------------------------------------------------
    def __iter__(self) -> Iterator:
        """Closeable iterator: ``it.close()`` (or ``with iter(ds) as it:``)
        tears down prefetch threads and in-flight reader-pool work."""
        return _raising(self._gen_fn())

    def as_numpy(self) -> List[Any]:
        return list(self)


def _accepts_start(factory: Callable) -> bool:
    """True if ``factory`` can be called as ``factory(epoch, start)`` —
    the seekable-pipeline contract of :class:`ResumableIterator`."""
    try:
        sig = inspect.signature(factory)
    except (TypeError, ValueError):
        return False
    params = list(sig.parameters.values())
    if any(p.kind is p.VAR_POSITIONAL for p in params):
        return True
    positional = [p for p in params
                  if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
    if len(positional) >= 2:
        return True
    return any(p.name == "start" and p.kind is p.KEYWORD_ONLY
               for p in params)


class _InterleaveModel:
    """:meth:`Dataset.interleave` run on per-source element ``counts``
    (sources in upstream order) with no I/O, one cycle turn at a time: the
    one model that :func:`interleave_order` and :func:`interleave_state`
    read.

    Faithful to one subtlety of the real operator: exhaustion is only
    observed on ``StopIteration``, so a source whose remaining count is an
    exact ``block_length`` multiple is re-appended after its last full
    block and occupies one extra (empty) cycle turn before retiring.
    """

    def __init__(self, counts: Sequence[int], cycle_length: int,
                 block_length: int):
        if cycle_length < 1:
            raise ValueError(f"cycle_length must be >= 1, got {cycle_length}")
        if block_length < 1:
            raise ValueError(f"block_length must be >= 1, got {block_length}")
        self.counts = [int(c) for c in counts]
        self.cycle_length = cycle_length
        self.block_length = block_length
        self.pos = [0] * len(self.counts)   # elements taken, per source
        self.cycle: deque = deque()         # the slots behind the turn's
        self.entered = 0

    def next_turn(self, s: int) -> tuple:
        """Source ``s``'s next turn as an :class:`InterleaveState` slot."""
        take = min(self.block_length, self.counts[s] - self.pos[s])
        return s, self.pos[s], take, take < self.block_length

    def turns(self) -> Iterator[tuple]:
        """Yield ``(source, first, take)`` per turn: the source delivers
        elements ``first .. first + take - 1``.  While a turn is yielded,
        ``cycle``, ``pos`` and ``entered`` describe the operator with that
        source popped and its block not yet taken."""
        pos, cycle = self.pos, self.cycle
        while True:
            while len(cycle) < self.cycle_length \
                    and self.entered < len(self.counts):
                cycle.append(self.entered)
                self.entered += 1
            if not cycle:
                return
            s, first, take, _ = self.next_turn(cycle.popleft())
            yield s, first, take
            pos[s] += take
            if take == self.block_length:
                # a full block: StopIteration not yet observed — the slot
                # stays in the cycle even if it is now empty (one extra
                # empty turn)
                cycle.append(s)


def interleave_order(counts: Sequence[int], cycle_length: int = 4,
                     block_length: int = 1) -> List[tuple]:
    """Arithmetic replica of :meth:`Dataset.interleave` delivery order.

    Given per-source element ``counts`` (sources in upstream order),
    returns the exact global delivery order as ``(source_index,
    element_index)`` pairs — the order the real interleave produces when
    every sub-stream is error-free.  Zero I/O: this is how a seekable
    pipeline (:func:`sharded_record_dataset`) converts a flat resume
    offset into per-shard read positions.
    """
    model = _InterleaveModel(counts, cycle_length, block_length)
    return [(s, i) for s, first, take in model.turns()
            for i in range(first, first + take)]


def interleave_state(counts: Sequence[int], cycle_length: int,
                     block_length: int, offset: int) -> InterleaveState:
    """Where :meth:`Dataset.interleave` over sources of ``counts``
    elements stands once ``offset`` elements are delivered, with no I/O
    (the model of :func:`interleave_order`).  ``interleave(...,
    start=state)`` continues from there.  At or past the end: an empty
    cycle with every source entered."""
    model = _InterleaveModel(counts, cycle_length, block_length)
    delivered = 0
    for s, first, take in model.turns():
        if offset < delivered + take:
            cut = offset - delivered
            head = (s, first + cut, take - cut, take < block_length)
            return InterleaveState(
                (head,) + tuple(model.next_turn(c) for c in model.cycle),
                model.entered)
        delivered += take
    return InterleaveState((), model.entered)


def sharded_record_dataset(storage, paths: Sequence[str], rec_bytes: int, *,
                           cycle_length: int = 4, block_length: int = 4,
                           num_parallel_calls: int = 0, seed: int = 0,
                           start: int = 0) -> Dataset:
    """Interleaved fixed-size-record shard streaming with O(1) seek.

    The fig13 read-engine shape: shard paths are buffer-shuffled by
    ``seed``, then ``cycle_length`` shards stream concurrently
    record-by-record (``rec_bytes`` per ``read_range``, short final
    record allowed), ``block_length`` records per cycle turn.

    ``start`` positions the stream *arithmetically*: the shuffled shard
    order is replayed over the path list (pure Python, zero I/O), record
    counts come from ``storage.size`` (unpaced metadata), and
    :func:`interleave_order` maps the flat offset to per-shard positions —
    so resuming deep into an epoch costs a handful of ``size`` calls, not
    a replay of every skipped record.  Use as a seekable
    :class:`ResumableIterator` factory::

        it = ResumableIterator(
            lambda ep, start=0: sharded_record_dataset(
                storage, paths, rec_bytes, seed=ep, start=start))

    The two paths deliver byte-identical element sequences: the ``start``
    path reads exactly the records the ``start=0`` interleave would have
    delivered from that offset on, in the same order.
    """
    shard_order = list(
        Dataset.from_tensor_slices(list(paths))
        .shuffle(max(len(paths), 1), seed=seed))

    if start <= 0:
        def stream_shard(path):
            def gen():
                size = storage.size(path)
                for off in range(0, size, rec_bytes):
                    yield storage.read_range(path, off,
                                             min(rec_bytes, size - off))
            return gen()

        return (Dataset.from_tensor_slices(list(paths))
                .shuffle(max(len(paths), 1), seed=seed)
                .interleave(stream_shard, cycle_length=cycle_length,
                            block_length=block_length,
                            num_parallel_calls=num_parallel_calls))

    # seek path: rebuild the delivery order arithmetically, skip `start`
    # entries by slicing (no data I/O), and read only the tail
    sizes = [storage.size(p) for p in shard_order]
    counts = [(sz + rec_bytes - 1) // rec_bytes for sz in sizes]
    order = interleave_order(counts, cycle_length, block_length)

    def gen_spans():
        for s, i in itertools.islice(iter(order), start, None):
            off = i * rec_bytes
            yield (shard_order[s], off, min(rec_bytes, sizes[s] - off))

    spans = Dataset(gen_spans)
    reader = lambda t: storage.read_range(*t)  # noqa: E731
    reader.__name__ = "read_record"
    return spans.map(reader,
                     num_parallel_calls=max(num_parallel_calls, 1))


class ResumableIterator:
    """Epoch-aware iterator with a lightweight save/restore position.

    The tf.data-style iterator checkpoint: position is ``{"epoch": e,
    "offset": k}`` — *k elements of epoch e already delivered to the
    consumer*.  :meth:`state` is cheap enough to attach to every checkpoint
    (the trainer stores it in ``extra_meta["pipeline"]``);
    :meth:`restore_state` re-opens epoch ``e`` past its first ``k``
    elements, by a seek where the pipeline offers one and else by a
    deterministic replay, so a resumed run neither skips nor replays
    samples.

    ``source`` is either a :class:`Dataset` (re-iterated per epoch — same
    element order every epoch) or a factory ``epoch -> Dataset`` for
    per-epoch seeding (``lambda ep: pipeline(seed=base_seed + ep)``); with
    a factory, skip-based restore still lands on the exact element because
    the factory rebuilds epoch ``e``'s order from its seed.  The offset
    counts elements *delivered through this iterator*: keep it downstream
    of ``prefetch`` (wrap the whole pipeline) so buffered-but-unconsumed
    elements are not counted as seen.

    **Seek instead of replay**, two ways, tried in order:

    1. a factory that also accepts a start offset — ``(epoch, start) ->
       Dataset`` yielding epoch ``e``'s stream *from element* ``start``
       (e.g. built on :func:`sharded_record_dataset`, which positions
       arithmetically instead of reading).  Detected from the factory's
       signature; :meth:`state` then carries ``"seek": True`` so a restore
       on a non-seekable pipeline of the same corpus still works (it
       falls back to replay);
    2. a Dataset that can position itself (:attr:`Dataset.seekable`,
       e.g. :func:`sharded_image_pipeline` with ``labels_per_shard``):
       the epoch's Dataset is opened through :meth:`Dataset.seek`.

    Either way no skipped element is ever produced, so resume cost is
    independent of how deep into the epoch the checkpoint was; counted in
    ``pipeline.resume_seeks``, the second traced as ``resume_seek``.  A
    replay is counted in ``pipeline.resume_skipped`` and traced as
    ``resume_skip``.

    Determinism caveat: skip-restore replays the pipeline's element order,
    which is deterministic for ``deterministic=True`` stages (the default);
    under ``ignore_errors`` the offset counts *surviving* elements, so a
    fault that is present in one run and absent in the replay shifts the
    alignment — exactly tf.data's contract.
    """

    def __init__(self, source, *, epochs: Optional[int] = None):
        if isinstance(source, Dataset):
            self._factory = lambda epoch: source
            self._seekable = False
        elif callable(source):
            self._factory = source
            self._seekable = _accepts_start(source)
        else:
            raise TypeError(
                f"source must be a Dataset or epoch->Dataset factory, "
                f"got {type(source).__name__}")
        self.epochs = epochs
        self._epoch = 0
        self._offset = 0
        self._it: Optional[Iterator] = None
        self._done = False

    # -- position ----------------------------------------------------------------
    def state(self) -> dict:
        """Snapshot the position (JSON-serializable, O(1))."""
        s = {"epoch": self._epoch, "offset": self._offset, "version": 1}
        if self._seekable:
            s["seek"] = True
        return s

    def _open_epoch(self, epoch: int, start: int = 0) -> Iterator:
        if start > 0 and self._seekable:
            return iter(self._factory(epoch, start))
        return iter(self._factory(epoch))

    def restore_state(self, state: dict) -> None:
        """Re-open at ``state``: a direct seek when the factory supports a
        start offset or the epoch's Dataset is seekable, else by skipping
        already-delivered elements."""
        self.close()
        self._epoch = int(state["epoch"])
        self._offset = 0
        self._done = False
        target = int(state["offset"])
        if target > 0 and self._seekable:
            # O(1) reposition: the factory opens epoch `epoch` already
            # advanced past the first `target` elements (no replay I/O).
            # A target beyond the epoch end yields an empty tail; the
            # nonzero offset makes __next__ roll the epoch naturally.
            self._it = self._open_epoch(self._epoch, target)
            self._offset = target
            metrics.inc("pipeline.resume_seeks")
            return
        ds = self._factory(self._epoch)
        if target > 0 and getattr(ds, "seekable", False):
            # the Dataset positions itself arithmetically; an empty tail
            # past the epoch end rolls the epoch, as above
            with trace.span(trace.STAGE_DATA_WAIT,
                            f"resume_seek:{target}@epoch{self._epoch}"):
                self._it = iter(ds.seek(target))
            self._offset = target
            metrics.inc("pipeline.resume_seeks")
            return
        self._it = iter(ds)
        with trace.span(trace.STAGE_DATA_WAIT,
                        f"resume_skip:{target}@epoch{self._epoch}"):
            for _ in range(target):
                try:
                    next(self._it)
                except StopIteration:
                    # position beyond epoch end (e.g. the corpus shrank):
                    # roll into the next epoch rather than fail the resume
                    break
                self._offset += 1
        metrics.inc("pipeline.resume_skipped", self._offset)

    # -- iteration ---------------------------------------------------------------
    def __iter__(self) -> "ResumableIterator":
        return self

    def __next__(self) -> Any:
        if self._done:
            raise StopIteration
        if self._it is not None:
            try:
                item = next(self._it)
            except StopIteration:
                self._end_epoch()
            else:
                self._offset += 1
                return item
        # closing the finished epoch, opening the next, its first element
        with trace.span(trace.STAGE_EPOCH_OPEN, "epoch_open"):
            while True:
                if self._it is None:
                    self._it = self._open_epoch(self._epoch)
                try:
                    item = next(self._it)
                except StopIteration:
                    self._end_epoch()
                    continue
                self._offset += 1
                return item

    def _end_epoch(self) -> None:
        """Close the exhausted epoch and move to the next; raises
        ``StopIteration`` when there is none."""
        _close_iter(self._it)
        self._it = None
        empty_epoch = self._offset == 0
        self._epoch += 1
        self._offset = 0
        if (self.epochs is not None and self._epoch >= self.epochs) \
                or empty_epoch:
            # empty epoch: the source is exhausted/empty — stop instead of
            # spinning on zero-element epochs forever
            self._done = True
            raise StopIteration

    def close(self) -> None:
        if self._it is not None:
            _close_iter(self._it)
            self._it = None

    def __enter__(self) -> "ResumableIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def image_pipeline(
    storage,
    paths: Sequence[str],
    labels: Optional[Sequence[int]] = None,
    *,
    batch_size: int = 64,
    num_parallel_calls: int = 4,
    prefetch: int = 1,
    shuffle_buffer: int = 1024,
    out_hw: tuple = (224, 224),
    seed: int = 0,
    preprocess: bool = True,
    repeat: bool = False,
    channels: int = 3,
    vectorized: bool = True,
) -> Dataset:
    """The paper's full input pipeline (Fig. 2) over an image-file corpus.

    ``vectorized=True`` (default) runs the fused ``map_and_batch`` path:
    zero-copy record decode, LUT-gather resize with the dtype conversion
    folded in, rows written straight into the batch buffer.
    ``vectorized=False`` keeps the seed per-element ``map -> ignore_errors ->
    batch`` chain (the fig11 baseline).
    """
    from . import records

    if labels is not None:
        src = Dataset.from_tensor_slices(list(zip(paths, labels)))
    else:
        src = Dataset.from_tensor_slices(list(paths))

    ds = src.shuffle(shuffle_buffer, seed=seed)
    if repeat:
        ds = ds.repeat()

    if preprocess and vectorized:
        if labels is not None:
            def load_into(item, out):
                path, label = item
                blob = storage.read_file(path)                   # tf.read_file
                payload = records.decode_single_record(blob, copy=False)
                records.preprocess_image_into(payload, out)
                return np.int32(label)
        else:
            def load_into(path, out):
                blob = storage.read_file(path)
                payload = records.decode_single_record(blob, copy=False)
                records.preprocess_image_into(payload, out)
                return None

        ds = ds.map_and_batch(
            load_into, batch_size, num_parallel_calls=num_parallel_calls,
            out_shape=(*out_hw, channels), out_dtype=np.float32,
            ignore_errors=True, drop_remainder=True)
    else:
        if labels is not None:
            def load(item):
                path, label = item
                blob = storage.read_file(path)                   # tf.read_file
                payload = records.decode_single_record(blob)
                if preprocess:
                    img = records.preprocess_image(payload, *out_hw)
                else:
                    img = np.frombuffer(payload, dtype=np.uint8)  # read-only
                return img, np.int32(label)
        else:
            def load(path):
                blob = storage.read_file(path)
                payload = records.decode_single_record(blob)
                if preprocess:
                    return records.preprocess_image(payload, *out_hw)
                return np.frombuffer(payload, dtype=np.uint8)

        ds = ds.map(load, num_parallel_calls=num_parallel_calls)
        ds = ds.ignore_errors()
        ds = ds.batch(batch_size, drop_remainder=True)

    if prefetch:
        ds = ds.prefetch(prefetch)
    return ds


def sharded_image_pipeline(
    storage,
    shard_paths: Sequence[str],
    labels_per_shard: Optional[Sequence[Sequence[int]]] = None,
    *,
    batch_size: int = 64,
    cycle_length: int = 4,
    block_length: int = 8,
    num_parallel_calls: int = 4,
    prefetch: int = 1,
    out_hw: tuple = (224, 224),
    seed: int = 0,
    preprocess: bool = True,
    repeat: bool = False,
    channels: int = 3,
    num_shards: int = 1,
    shard_index: int = 0,
    batched_preprocess: Optional[str] = None,
    cache=None,
    readahead=None,
    quarantine: Optional[ShardQuarantine] = None,
) -> Dataset:
    """High-throughput ingestion over multi-record ``.rrf`` shards.

    The vectorized read engine: shards are shuffled, ``cycle_length`` of
    them stream concurrently record-by-record through ``interleave`` (one
    sequential storage read per shard instead of one seek per image), and
    records decode zero-copy straight into the fused ``map_and_batch``
    buffer.  ``num_shards``/``shard_index`` apply ``Dataset.shard`` for
    multi-worker disjoint coverage.

    ``batched_preprocess`` switches resize+convert from per-record-on-host
    to whole-batch: ``"numpy"`` uses the batched LUT gather, ``"pallas"``
    the fused device kernel (:func:`repro.kernels.preprocess.
    resize_convert_images`).  Both require a uniform-size corpus
    (``write_sharded_image_dataset(hw_jitter=0)``).

    ``cache`` serves shard reads through a block cache: pass a
    :class:`~repro.core.cache.BlockCache` (wrapped here) or a ready-made
    :class:`~repro.core.cache.CachingStorage` — warm epochs then stream
    from DRAM (and the spill tier, if configured) instead of re-reading
    the device.  ``readahead`` prefetches upcoming shards' blocks ahead
    of the interleave cursor: a :class:`~repro.core.cache.
    ReadaheadScheduler`, or ``True``/an int window to build one over the
    cache (requires ``cache``).  ``quarantine`` enables cross-epoch shard
    quarantine with probe-read re-admission (see :class:`ShardQuarantine`).

    **Seek.**  With ``labels_per_shard`` (each shard's record count is its
    labels' length), and with no ``quarantine``, ``readahead`` or
    ``repeat``, the returned Dataset is seekable: ``ds.seek(k)`` is the
    stream from batch ``k`` on, which :class:`ResumableIterator` uses on a
    restore.  Batch ``k`` begins at record ``k * batch_size``; the shard
    order is replayed in pure Python and the interleave's state at that
    record derived by :func:`interleave_state`, so only the shards still
    in the cycle or after it are read, and everything after the cut runs
    the same decode, batch and prefetch as a fresh epoch.  No seek is
    offered, and a restore replays, without the counts, with a quarantine
    (whose skips the model cannot know), with readahead (which would
    announce, and so read, the shards before the cut) or with ``repeat``.
    Caveat (as for :func:`sharded_record_dataset` and tf.data): the
    pipeline ignores errors, and a record before the cut that fails to
    read or decode shifts the count, so the seek starts one record early
    for each.
    """
    from . import records

    if cache is not None:
        from .cache import BlockCache, CachingStorage
        if isinstance(cache, CachingStorage):
            storage = cache
        elif isinstance(cache, BlockCache):
            storage = CachingStorage(storage, cache)
        else:
            raise TypeError(
                f"cache= expects BlockCache or CachingStorage, got "
                f"{type(cache).__name__}")

    scheduler = None
    if readahead is not None and readahead is not False:
        from .cache import CachingStorage, ReadaheadScheduler
        if isinstance(readahead, ReadaheadScheduler):
            scheduler = readahead
        else:
            if not isinstance(storage, CachingStorage):
                raise TypeError("readahead= requires cache= (prefetch "
                                "needs a CachingStorage to land blocks in)")
            window = 8 if readahead is True else int(readahead)
            scheduler = ReadaheadScheduler(storage, window=window)

    if labels_per_shard is not None:
        items: List[Any] = [
            (p, list(ls)) for p, ls in zip(shard_paths, labels_per_shard)
        ]
    else:
        items = list(shard_paths)

    src = Dataset.from_tensor_slices(items)
    if num_shards > 1:
        src = src.shard(num_shards, shard_index)
    src = src.shuffle(max(len(items), 1), seed=seed)
    if repeat:
        src = src.repeat()

    if scheduler is not None:
        # lookahead node: announce each shard to the readahead scheduler
        # `lookahead_shards` positions before the interleave cursor reaches
        # it, so its blocks are (being) cached by the time it streams
        upstream = src._gen_fn
        lookahead = scheduler.lookahead_shards

        def gen_readahead():
            it = upstream()
            buf: deque = deque()
            try:
                for item in it:
                    if not isinstance(item, _ErrorMarker):
                        scheduler.schedule(_shard_key(item))
                    buf.append(item)
                    if len(buf) > lookahead:
                        yield buf.popleft()
                while buf:
                    yield buf.popleft()
            finally:
                scheduler.clear()   # don't prefetch past an abandoned epoch
                _close_iter(it)

        src = Dataset(gen_readahead)

    if labels_per_shard is not None:
        def stream_shard(item):
            path, labels = item
            blob = storage.read_file(path)          # one sequential shard read
            return zip(records.iter_record_views(blob), labels)
    else:
        def stream_shard(path):
            blob = storage.read_file(path)
            return records.iter_record_views(blob)

    def decode_and_batch(ds: Dataset) -> Dataset:
        """Everything after the interleave: decode, batch, resize,
        prefetch."""
        if not preprocess:
            # read-only mode (fig5): element = record byte length
            def record_len(item):
                view = item[0] if labels_per_shard is not None else item
                return np.int64(len(view))

            ds = ds.map(record_len).ignore_errors()
            ds = ds.batch(batch_size, drop_remainder=True)
        elif batched_preprocess:
            # decode raw uint8 on host, resize+convert whole batches at once
            from ..kernels import preprocess as kpre

            if labels_per_shard is not None:
                def decode_raw(item):
                    view, label = item
                    return (records.decode_image(view, copy=False),
                            np.int32(label))
            else:
                def decode_raw(view):
                    return records.decode_image(view, copy=False)

            ds = ds.map(decode_raw, num_parallel_calls=num_parallel_calls)
            ds = ds.ignore_errors()
            ds = ds.batch(batch_size, drop_remainder=True)

            def batch_resize(batch):
                imgs = batch[0] if labels_per_shard is not None else batch
                # with the kernel: the uint8 batch's copy to the device and
                # the kernel's dispatch, on the pipeline's thread
                with trace.span(trace.STAGE_DEVICE_PREPROCESS,
                                batched_preprocess, imgs.nbytes):
                    out = kpre.resize_convert(imgs, *out_hw,
                                              backend=batched_preprocess)
                if labels_per_shard is not None:
                    return out, batch[1]
                return out

            ds = ds.map(batch_resize)
        else:
            if labels_per_shard is not None:
                def decode_into(item, out):
                    view, label = item
                    records.preprocess_image_into(view, out)
                    return np.int32(label)
            else:
                def decode_into(view, out):
                    records.preprocess_image_into(view, out)
                    return None

            ds = ds.map_and_batch(
                decode_into, batch_size,
                num_parallel_calls=num_parallel_calls,
                out_shape=(*out_hw, channels), out_dtype=np.float32,
                ignore_errors=True, drop_remainder=True)

        if prefetch:
            ds = ds.prefetch(prefetch)
        return ds

    def interleave(start: Optional[InterleaveState] = None) -> Dataset:
        return src.interleave(
            stream_shard, cycle_length=cycle_length,
            block_length=block_length, num_parallel_calls=num_parallel_calls,
            quarantine=quarantine, start=start)

    ds = decode_and_batch(interleave())
    if labels_per_shard is None or quarantine is not None \
            or scheduler is not None or repeat:
        return ds

    def seek(k: int) -> Dataset:
        # the shard()/shuffle(seed) order replayed in pure Python, each
        # shard's count from its labels: batch k begins at record k * B
        counts = [len(labels) for _, labels in src]
        return decode_and_batch(interleave(interleave_state(
            counts, cycle_length, block_length, k * batch_size)))

    return Dataset(ds._gen_fn, seek)
