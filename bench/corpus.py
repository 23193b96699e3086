"""The image corpus a cell trains on, made from the seed.

Sizes, labels and the shard layout come from the configuration and the
seed alone, through this file's own generator, so a change to the program's
dataset writers cannot move the traffic.  The bytes are the program's record
format (``records.encode_record`` around ``records.encode_image``), because
the program's pipeline has to read them.

The shards are written once per run, in set-up, through the program's
native storage into a directory of the run's own, and the timed pipeline
reads them back through native storage: files just written, so the host's
page cache serves most of those reads.  The reference reads its records
from the same files with plain ``open``, at offsets this file recorded.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from repro.core import make_storage, records
from repro.core.storage import Storage


@dataclass
class Corpus:
    storage: Storage            # the program's native storage over ``root``
    root: str
    paths: List[str]
    labels_per_shard: List[List[int]]
    labels: np.ndarray          # (n_images,) int32, record order
    shard_of: np.ndarray        # (n_images,) shard index of each record
    body_offset: np.ndarray     # (n_images,) offset of the pixels in the shard
    shape: tuple                # stored (height, width, channels) of every image

    @property
    def n_images(self) -> int:
        return len(self.labels)

    def pixels(self, i: int) -> np.ndarray:
        """Record ``i``'s pixels as written, ``(h, w, channels)`` uint8."""
        n = int(np.prod(self.shape))
        with open(os.path.join(self.root, self.paths[self.shard_of[i]]),
                  "rb") as f:
            f.seek(int(self.body_offset[i]))
            data = f.read(n)
        return np.frombuffer(data, np.uint8).reshape(self.shape)


def build_corpus(cfg: dict, seed: int, root: str) -> Corpus:
    """``cfg["n_images"]`` RGB images of random pixels, each stored at
    ``cfg["image_hw"]`` square, ``cfg["images_per_shard"]`` to a shard,
    labels uniform over ``cfg["n_classes"]``, written under ``root``; the
    same seed gives the same bytes."""
    n, per_shard = cfg["n_images"], cfg["images_per_shard"]
    shape = (cfg["image_hw"], cfg["image_hw"], cfg["channels"])
    size = int(np.prod(shape))
    rng = np.random.default_rng([seed, 0])
    labels = rng.integers(0, cfg["n_classes"], n).astype(np.int32)
    head = records.RECORD_HDR.size + records.IMG_HDR.size
    storage = make_storage("native", root)
    paths: List[str] = []
    n_shards = -(-n // per_shard)
    shard_of = np.repeat(np.arange(n_shards), per_shard)[:n]
    body_offset = np.zeros(n, np.int64)
    for s in range(n_shards):
        lo, hi = s * per_shard, min(n, (s + 1) * per_shard)
        pixels = np.frombuffer(rng.bytes(size * (hi - lo)), np.uint8)
        parts = []
        off = 0
        for k, i in enumerate(range(lo, hi)):
            img = pixels[k * size:(k + 1) * size].reshape(shape)
            rec = records.encode_record(records.encode_image(img))
            body_offset[i] = off + head
            off += len(rec)
            parts.append(rec)
        path = f"shard_{s:05d}.rrf"
        storage.write_file(path, b"".join(parts))
        paths.append(path)
    labels_per_shard = [labels[s * per_shard:(s + 1) * per_shard].tolist()
                        for s in range(len(paths))]
    return Corpus(storage, root, paths, labels_per_shard, labels, shard_of,
                  body_offset, shape)


def corner_key(img: np.ndarray) -> bytes:
    """The four corner pixels of an ``(h, w, c)`` uint8 image: 96 random
    bits, enough to tell every record of a corpus apart."""
    return np.ascontiguousarray(img[[0, -1]][:, [0, -1]]).tobytes()


def corner_index(corpus: Corpus) -> Dict[bytes, int]:
    """``corner_key -> record index`` over the whole corpus."""
    index = {}
    for i in range(corpus.n_images):
        index[corner_key(corpus.pixels(i))] = i
    if len(index) != corpus.n_images:
        raise ValueError("two records share their corner pixels")
    return index
