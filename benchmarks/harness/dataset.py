"""The traffic generator for training cells: one general writer of image
shards, driven by a traffic file's parameters and ``--seed``, and the check
that a fed batch is made of the data set's images.

Shard format (what ``ImageNetPipeline`` memory-maps)::

    shard-00000-images.npy   (N, H, W, 3) uint8
    shard-00000-labels.npy   (N,) int32

The writer is the benchmark's own, so that the program may change its
``write_synthetic_imagenet`` and the yardstick stays. Every image differs
(uniform random bytes), every shard has a generator of its own spawned from
the seed, and shards are written by a few threads.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence, Tuple

import numpy as np


def write_image_shards(data_dir: str, traffic: dict, seed: int,
                       threads: int = 8) -> int:
    """Write the data set for ``seed``; returns the bytes written."""
    n = int(traffic["images"])
    size, shard = int(traffic["image_size"]), int(traffic["shard_images"])
    classes = int(traffic["num_classes"])
    os.makedirs(data_dir, exist_ok=True)
    n_shards = -(-n // shard)
    seeds = np.random.SeedSequence(int(seed)).spawn(n_shards)

    def one(i: int) -> int:
        m = min(shard, n - i * shard)
        bits = np.random.SFC64(seeds[i])
        nbytes = m * size * size * 3
        # raw 64-bit words, viewed as bytes: a GB/s per thread, where
        # Generator.bytes and integers(dtype=uint8) give a tenth of that
        imgs = bits.random_raw(-(-nbytes // 8)).view(np.uint8)[:nbytes]
        imgs = imgs.reshape(m, size, size, 3)
        labels = np.random.Generator(bits).integers(
            0, classes, m).astype(np.int32)
        np.save(os.path.join(data_dir, f"shard-{i:05d}-images.npy"), imgs)
        np.save(os.path.join(data_dir, f"shard-{i:05d}-labels.npy"), labels)
        return imgs.nbytes + labels.nbytes

    with ThreadPoolExecutor(threads) as pool:
        return sum(pool.map(one, range(n_shards)))


class ShardIndex:
    """The data set as written, for checking what the infeed delivers."""

    def __init__(self, data_dir: str):
        names = sorted(f for f in os.listdir(data_dir)
                       if f.endswith("-images.npy"))
        self.images = [np.load(os.path.join(data_dir, f), mmap_mode="r")
                       for f in names]
        self.labels = np.concatenate([
            np.load(os.path.join(data_dir, f.replace("-images", "-labels")))
            for f in names])
        self._starts = np.cumsum([0] + [len(a) for a in self.images])
        order = np.argsort(self.labels, kind="stable")
        bounds = np.searchsorted(self.labels[order],
                                 np.arange(self.labels.max() + 2))
        self._by_label = (order, bounds)

    def candidates(self, label: int) -> np.ndarray:
        order, bounds = self._by_label
        if not 0 <= label < len(bounds) - 1:
            return order[:0]
        return order[bounds[label]:bounds[label + 1]]

    def image(self, idx: int) -> np.ndarray:
        s = int(np.searchsorted(self._starts, idx, side="right")) - 1
        return self.images[s][idx - self._starts[s]]

    def find_crop(self, row: np.ndarray, label: int) -> int:
        """Index of the image of which ``row`` is a crop (flipped or not)
        and which carries ``label``; -1 if there is none."""
        c = row.shape[0]
        pr, pc = c // 2, c // 4           # one pixel of the crop finds it
        for idx in self.candidates(int(label)):
            img = self.image(int(idx))
            slack = img.shape[0] - c
            region = img[pr:pr + slack + 1]
            for flip in (False, True):
                r = row[:, ::-1] if flip else row
                hits = np.all(region[:, pc:pc + slack + 1] == r[pr, pc],
                              axis=-1)
                for dy, dx in zip(*np.nonzero(hits)):
                    if np.array_equal(img[dy:dy + c, dx:dx + c], r):
                        return int(idx)
        return -1


def count_bad_rows(index: ShardIndex,
                   epochs: Sequence[Sequence[Tuple[np.ndarray, np.ndarray]]]
                   ) -> Dict[str, int]:
    """Rows of the fed batches that are no crop of a data-set image with the
    batch's label, or that repeat an image inside one epoch. ``epochs`` holds,
    for each epoch that fed a batch, its (images, labels) host arrays."""
    bad = rows = 0
    for batches in epochs:
        seen: List[int] = []
        for x, y in batches:
            for r in range(len(x)):
                rows += 1
                idx = index.find_crop(x[r], int(y[r]))
                if idx < 0:
                    bad += 1
                else:
                    seen.append(idx)
        bad += len(seen) - len(set(seen))
    return {"rows": rows, "bad": bad}
