"""Sharded checkpoint save/restore (paper §II-B: tf.train.Saver analogue).

Layout mirrors TF's Saver, generalized to N data shards (one per writer
host on a pod):

    <prefix>-<step>.meta                   # JSON: step, treedef, user config
    <prefix>-<step>.index                  # JSON: tensor -> (shard, offset, ...)
    <prefix>-<step>.data-00000-of-00004    # raw tensor bytes
    <prefix>-<step>.data-00001-of-00004
    ...
    checkpoint                             # commit marker: latest + retained steps

Guarantees:

* **Atomic commit** — data/index/meta are fully written (and optionally
  fsync'd, paper §III-C) *before* the ``checkpoint`` marker is rewritten;
  a crash mid-save leaves the previous checkpoint restorable.
* **Retention** — keep the newest ``keep`` checkpoints (TF default 5).
* **Elastic restore** — the index is topology-free; restore can re-shard
  onto any mesh via ``jax.make_array_from_callback``.
* **Parallel shard I/O** — the N data shards are written (and read back)
  concurrently on an ``io_threads`` pool, the write-side analogue of the
  paper's read thread-scaling (Fig. 4/5); ``save_flat`` takes an
  already-snapshotted flat dict so :class:`repro.core.engine.
  CheckpointEngine` can run the whole write off the training thread.
* **int8 option** — blockwise-quantized storage (2x–4x smaller bursts), with
  scales stored alongside; see also ``repro.kernels.quantize`` for the TPU
  kernel version of the same transform.
"""
from __future__ import annotations

import json
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import trace

CHECKPOINT_MARKER = "checkpoint"
_QBLOCK = 256  # quantization block (last-dim) size


def write_marker(storage, path: str, payload: bytes, sync: bool = True) -> None:
    """Commit-marker write: tmp file + atomic rename.

    A plain ``write_file`` truncates-then-writes, so a crash *mid-marker*
    (a torn write — see ``repro.core.faults``) can leave a corrupt marker
    and make **both** the old and new checkpoint unreachable.  Writing to a
    sibling tmp and renaming keeps the old marker intact until the new one
    exists in full; ``sync=True`` makes the tmp durable (a write barrier)
    before the rename publishes it — the restorability commit point of the
    whole protocol.
    """
    tmp = path + ".tmp"
    storage.write_file(tmp, payload, sync=sync)
    storage.rename(tmp, path)

#: dtypes eligible for int8 blockwise quantization (by name, so the check
#: never needs np.dtype("bfloat16") — which raises unless ml_dtypes has
#: registered it).
_QUANTIZABLE_DTYPES = ("float32", "float64", "bfloat16")


def resolve_dtype(name: str) -> np.dtype:
    """``np.dtype(name)`` with an ``ml_dtypes`` fallback.

    Extension dtypes (bfloat16, float8_*, ...) are only resolvable by
    string name once ``ml_dtypes`` has been imported somewhere in the
    process; a checkpoint written from a jax pytree but restored in a
    process that never touched jax would otherwise crash with a bare
    ``TypeError: data type 'bfloat16' not understood``.
    """
    try:
        return np.dtype(name)
    except TypeError:
        pass
    try:
        import ml_dtypes
    except ImportError as e:
        raise TypeError(
            f"checkpoint dtype {name!r} is not a numpy builtin and "
            "ml_dtypes is not installed; install ml_dtypes (a jax "
            "dependency) to restore extension-dtype tensors"
        ) from e
    try:
        return np.dtype(getattr(ml_dtypes, name))
    except (AttributeError, TypeError) as e:
        raise TypeError(f"unknown checkpoint dtype {name!r}") from e


# ---------------------------------------------------------------------------
# pytree <-> flat dict of numpy arrays
# ---------------------------------------------------------------------------
def flatten_pytree(tree: Any, copy: bool = False) -> Tuple[Dict[str, np.ndarray], Any]:
    """Flatten ``tree`` to ``{path: host ndarray}`` + its treedef.

    With ``copy=True`` the result is a true point-in-time snapshot that a
    background writer can consume while training mutates the originals:
    any leaf that still aliases caller-owned memory is copied.  That
    includes numpy leaves (passed through by reference) *and* CPU-backend
    jax arrays, where ``np.asarray(jax.device_get(x))`` can be a zero-copy
    view of the live XLA buffer — lethal under donated arguments.
    """
    import jax

    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    treedef = jax.tree_util.tree_structure(tree)
    flat = {}
    for path, leaf in leaves_with_paths:
        key = "/".join(_path_str(p) for p in path) or "leaf"
        arr = np.asarray(jax.device_get(leaf))
        if copy and (arr is leaf or arr.base is not None
                     or not arr.flags["OWNDATA"]):
            arr = np.array(arr, copy=True)
        flat[key] = arr
    return flat, treedef


def _path_str(p) -> str:
    import jax

    if isinstance(p, jax.tree_util.DictKey):
        return str(p.key)
    if isinstance(p, jax.tree_util.SequenceKey):
        return str(p.idx)
    if isinstance(p, jax.tree_util.GetAttrKey):
        return str(p.name)
    return str(p)


def unflatten_pytree(flat: Dict[str, np.ndarray], treedef) -> Any:
    import jax

    # Re-flatten a skeleton to get key order, then rebuild.
    skeleton = jax.tree_util.tree_unflatten(treedef, list(range(treedef.num_leaves)))
    paths = jax.tree_util.tree_flatten_with_path(skeleton)[0]
    ordered = []
    for path, _ in paths:
        key = "/".join(_path_str(p) for p in path) or "leaf"
        ordered.append(flat[key])
    return jax.tree_util.tree_unflatten(treedef, ordered)


# ---------------------------------------------------------------------------
# int8 blockwise quantization (numpy mirror of kernels/quantize.py)
# ---------------------------------------------------------------------------
def quantize_blockwise(arr: np.ndarray, block: int = _QBLOCK):
    flat = arr.astype(np.float32).reshape(-1)
    pad = (-len(flat)) % block
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.float32)])
    blocks = flat.reshape(-1, block)
    scale = np.abs(blocks).max(axis=1, keepdims=True) / 127.0
    scale = np.where(scale == 0, 1.0, scale)
    q = np.clip(np.round(blocks / scale), -127, 127).astype(np.int8)
    return q, scale.astype(np.float32), pad


def dequantize_blockwise(q: np.ndarray, scale: np.ndarray, pad: int,
                         shape, dtype) -> np.ndarray:
    flat = (q.astype(np.float32) * scale).reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Saver
# ---------------------------------------------------------------------------
@dataclass
class SaveResult:
    step: int
    n_bytes: int
    seconds: float
    files: List[str]


@dataclass
class PreemptionReport:
    """Outcome of a graceful-shutdown ``preempt(deadline_s)`` call.

    ``committed_step`` is the newest step durable at the engine's
    preemption tier (the fast tier for the burst buffers) when the call
    returned; ``abandoned_steps`` are saves given up to meet the deadline —
    queued snapshots that were cancelled before touching storage, plus the
    newest in-flight save if it missed the budget.  ``deadline_met`` is
    False only in that last case."""

    committed_step: Optional[int]
    abandoned_steps: List[int]
    deadline_s: Optional[float]
    elapsed_s: float
    deadline_met: bool


class CheckpointSaver:
    """TF-Saver-like sharded checkpointer over a :class:`Storage`.

    ``io_threads`` controls shard-level I/O concurrency: the N data shards
    are written (and, on restore, read) on a thread pool of that size — the
    write-side analogue of the paper's read thread-scaling (Fig. 4/5: 2.3x
    on HDD, 7.8x on Lustre).  ``None`` (default) sizes the pool to
    ``min(n_shards, 8)``; ``1`` forces serial I/O.
    """

    def __init__(
        self,
        storage,
        prefix: str = "ckpt/model",
        *,
        keep: int = 5,
        n_shards: int = 1,
        sync: bool = True,
        quantize: Optional[str] = None,  # None | "int8"
        io_threads: Optional[int] = None,
    ):
        self.storage = storage
        self.prefix = prefix
        self.keep = keep
        self.n_shards = max(1, n_shards)
        self.sync = sync
        self.quantize = quantize
        self.io_threads = (
            min(self.n_shards, 8) if io_threads is None else max(1, io_threads)
        )
        d = prefix.rsplit("/", 1)[0] if "/" in prefix else "."
        self._dir = d
        storage.makedirs(d)

    # -- naming ----------------------------------------------------------------
    def _base(self, step: int) -> str:
        return f"{self.prefix}-{step}"

    def _marker_path(self) -> str:
        return f"{self._dir}/{CHECKPOINT_MARKER}"

    # -- save --------------------------------------------------------------------
    def save(self, step: int, tree: Any, extra_meta: Optional[dict] = None) -> SaveResult:
        t0 = time.monotonic()
        with trace.span(trace.STAGE_CKPT_SNAPSHOT,
                        f"snapshot:{self.prefix}-{step}") as sp:
            flat, treedef = flatten_pytree(tree)
            sp.set_bytes(sum(a.nbytes for a in flat.values()))
        result = self.save_flat(step, flat, extra_meta, treedef=treedef)
        result.seconds = time.monotonic() - t0  # include the snapshot
        return result

    def save_flat(self, step: int, flat: Dict[str, np.ndarray],
                  extra_meta: Optional[dict] = None, *,
                  treedef=None) -> SaveResult:
        """Save an already-snapshotted flat dict of host arrays (the entry
        point the async engine calls from its writer thread)."""
        with trace.span(trace.STAGE_CKPT_WRITE, f"save:{self.prefix}-{step}") as sp:
            result = self._save_flat(step, flat, extra_meta, treedef)
            sp.set_bytes(result.n_bytes)
        return result

    def _serialize(self, flat: Dict[str, np.ndarray]):
        """Pack tensors into one exact-size ``uint8`` buffer per shard, plus
        the tensor index.

        Every size is known before a byte is copied, so each shard is one
        allocation, and each tensor reaches it by a numpy copy, which
        releases the interpreter lock: the training thread keeps running
        beside a background save.  The bytes are those of ``tobytes()``: C
        order, tensors back to back.
        """
        # Assign tensors to shards round-robin by size (largest first) so the
        # N writer hosts carry balanced bytes.
        names = sorted(flat, key=lambda k: -flat[k].nbytes)
        shard_of: Dict[str, int] = {}
        shard_bytes = [0] * self.n_shards
        for name in names:
            s = int(np.argmin(shard_bytes))
            shard_of[name] = s
            shard_bytes[s] += flat[name].nbytes

        sizes = [0] * self.n_shards
        parts: List[Tuple[int, int, np.ndarray]] = []  # (shard, offset, array)
        index: Dict[str, dict] = {}
        for name in flat:
            arr = flat[name]
            s = shard_of[name]
            entry: Dict[str, Any] = dict(
                shard=s,
                offset=sizes[s],
                shape=list(arr.shape),
                dtype=str(arr.dtype),
            )
            if (self.quantize == "int8"
                    and str(arr.dtype) in _QUANTIZABLE_DTYPES
                    and arr.size >= _QBLOCK):
                q, scale, pad = quantize_blockwise(arr)
                entry.update(
                    quant="int8", qpad=pad, qblock=_QBLOCK,
                    scale_offset=sizes[s] + q.nbytes, scale_len=scale.nbytes,
                )
                pieces = (q, scale)
            else:
                pieces = (arr,)
            for piece in pieces:
                parts.append((s, sizes[s], piece))
                sizes[s] += piece.nbytes
            entry["length"] = sizes[s] - entry["offset"]
            index[name] = entry

        buffers = [np.empty(n, np.uint8) for n in sizes]
        for s, offset, piece in parts:
            dst = buffers[s][offset:offset + piece.nbytes]
            dst.view(piece.dtype).reshape(piece.shape)[...] = piece
        return buffers, index

    def _save_flat(self, step: int, flat: Dict[str, np.ndarray],
                   extra_meta: Optional[dict] = None,
                   treedef=None) -> SaveResult:
        t0 = time.monotonic()
        base = self._base(step)
        with trace.span(trace.STAGE_CKPT_SERIALIZE,
                        f"serialize:{base}") as sp:
            buffers, index = self._serialize(flat)
            sp.set_bytes(sum(buf.nbytes for buf in buffers))

        files: List[str] = []
        total = 0
        # 1) data shards — concurrently on the writer pool (any failure
        #    aborts the save before the marker is touched)
        shard_paths = [
            f"{base}.data-{s:05d}-of-{self.n_shards:05d}"
            for s in range(self.n_shards)
        ]
        # zero-copy views — bytes(buf) would transiently double peak host
        # memory on a multi-GB checkpoint
        shard_blobs = [memoryview(buf) for buf in buffers]
        if self.io_threads > 1 and self.n_shards > 1:
            with ThreadPoolExecutor(
                min(self.io_threads, self.n_shards),
                thread_name_prefix="ckpt-shard-io",
            ) as pool:
                futs = [
                    pool.submit(self.storage.write_file, p, b, self.sync)
                    for p, b in zip(shard_paths, shard_blobs)
                ]
                for f in futs:
                    f.result()
        else:
            for p, b in zip(shard_paths, shard_blobs):
                self.storage.write_file(p, b, sync=self.sync)
        files.extend(shard_paths)
        total += sum(len(b) for b in shard_blobs)
        # 2) index
        index_blob = json.dumps(dict(tensors=index, n_shards=self.n_shards)).encode()
        self.storage.write_file(f"{base}.index", index_blob, sync=self.sync)
        files.append(f"{base}.index")
        total += len(index_blob)
        # 3) meta (graph-structure analogue: the treedef + user config)
        meta = dict(
            step=step,
            treedef=None if treedef is None else str(treedef),
            created=time.time(),
            quantize=self.quantize,
            extra=extra_meta or {},
        )
        meta_blob = json.dumps(meta).encode()
        self.storage.write_file(f"{base}.meta", meta_blob, sync=self.sync)
        files.append(f"{base}.meta")
        total += len(meta_blob)
        if self.sync:
            self.storage.fsync_dir(self._dir)  # paper: syncfs() after Saver

        # 4) commit marker LAST (atomicity), then retention.
        steps = self.all_steps()
        if step not in steps:
            steps.append(step)
        steps.sort()
        retained = steps[-self.keep:]
        marker = json.dumps(dict(latest=step, all_steps=retained)).encode()
        write_marker(self.storage, self._marker_path(), marker,
                     sync=self.sync)
        for old in steps[:-self.keep] if len(steps) > self.keep else []:
            self._delete_step(old)

        return SaveResult(step, total, time.monotonic() - t0, files)

    def _delete_step(self, step: int) -> None:
        base_name = self._base(step).rsplit("/", 1)[-1]
        for name in self.storage.listdir(self._dir):
            if name.startswith(base_name + "."):
                self.storage.remove(f"{self._dir}/{name}")

    # -- introspection -----------------------------------------------------------
    def all_steps(self) -> List[int]:
        if not self.storage.exists(self._marker_path()):
            return []
        marker = json.loads(self.storage.read_file(self._marker_path()))
        return list(marker.get("all_steps", []))

    def latest_step(self) -> Optional[int]:
        if not self.storage.exists(self._marker_path()):
            return None
        return json.loads(self.storage.read_file(self._marker_path()))["latest"]

    # -- restore -------------------------------------------------------------------
    def restore(self, step: Optional[int] = None, treedef=None) -> Tuple[Dict[str, np.ndarray], dict]:
        """Return (flat dict of numpy arrays, meta). Use ``treedef`` (or
        ``restore_pytree``) to rebuild the original structure."""
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoint under {self.prefix}")
        with trace.span(trace.STAGE_CKPT_RESTORE, f"restore:{self.prefix}-{step}") as sp:
            flat, meta = self._restore(step)
            sp.set_bytes(sum(a.nbytes for a in flat.values()))
        return flat, meta

    def _restore(self, step: int) -> Tuple[Dict[str, np.ndarray], dict]:
        base = self._base(step)
        meta = json.loads(self.storage.read_file(f"{base}.meta"))
        index = json.loads(self.storage.read_file(f"{base}.index"))
        n_shards = index["n_shards"]
        shard_paths = [
            f"{base}.data-{s:05d}-of-{n_shards:05d}" for s in range(n_shards)
        ]
        # shard reads on the same pool policy as shard writes (Fig. 4/5:
        # read thread-scaling is the paper's headline result)
        if self.io_threads > 1 and n_shards > 1:
            with ThreadPoolExecutor(
                min(self.io_threads, n_shards),
                thread_name_prefix="ckpt-shard-io",
            ) as pool:
                blobs = list(pool.map(self.storage.read_file, shard_paths))
        else:
            blobs = [self.storage.read_file(p) for p in shard_paths]
        shards: Dict[int, bytes] = dict(enumerate(blobs))
        flat: Dict[str, np.ndarray] = {}
        for name, e in index["tensors"].items():
            raw = shards[e["shard"]][e["offset"] : e["offset"] + e["length"]]
            shape, dtype = tuple(e["shape"]), resolve_dtype(e["dtype"])
            if e.get("quant") == "int8":
                qlen = e["scale_offset"] - e["offset"]
                q = np.frombuffer(raw[:qlen], dtype=np.int8).reshape(-1, e["qblock"])
                scale = np.frombuffer(
                    raw[qlen : qlen + e["scale_len"]], dtype=np.float32
                ).reshape(-1, 1)
                flat[name] = dequantize_blockwise(q, scale, e["qpad"], shape, dtype)
            else:
                flat[name] = np.frombuffer(raw, dtype=dtype).reshape(shape).copy()
        return flat, meta

    def restore_pytree(self, skeleton: Any, step: Optional[int] = None) -> Any:
        """Restore into the structure of ``skeleton`` (a pytree of anything)."""
        import jax

        flat, _meta = self.restore(step)
        treedef = jax.tree_util.tree_structure(skeleton)
        return unflatten_pytree(flat, treedef)

    def restore_sharded(self, skeleton: Any, shardings: Any,
                        step: Optional[int] = None) -> Any:
        """Elastic restore: place each tensor on the mesh given by
        ``shardings`` (pytree of NamedSharding matching ``skeleton``),
        regardless of the topology that wrote the checkpoint."""
        return place_sharded(self.restore_pytree(skeleton, step), shardings)


def place_sharded(restored: Any, shardings: Any) -> Any:
    """Place each host array of ``restored`` on the mesh of the matching
    sharding in ``shardings``: the index is topology-free, so any mesh will
    do."""
    import jax

    def _place(arr, sharding):
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx]
        )

    return jax.tree.map(_place, restored, shardings)
