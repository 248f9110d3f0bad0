"""Plain reference of a sharded train state saved as one file per
(leaf, rank) into a fixed-block store.

The state's bytes are made here again from the seed with ``numpy``, a
block at a time: the layout, the keys and which experts train come from
``bench/content/trainstate.py`` (pure Python there; nothing imports JAX
or ``repro``), and every element's value is the integer hash this
module computes itself.  A file is cut into ``block_size`` blocks (the
last one shorter), each with the store's block digest
(``bench/reference/digest.py``) and the CRC-32 of its bytes.  A block
whose bytes do not depend on the step (frozen tensors, the untrained
experts of a stack) is computed once per run.
"""
from __future__ import annotations

import threading
import zlib
from typing import Dict, List, Tuple

import numpy as np

from bench.content.trainstate import (GOLDEN, Leaf, element_steps, key,
                                      layout)
from bench.reference.digest import block_digest


def _fmix(x: np.ndarray) -> np.ndarray:
    """murmur3's fmix32, in place."""
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def values(k: int, first: int, n: int, dtype: str) -> np.ndarray:
    """The bit patterns of elements ``first .. first + n`` under key
    ``k``: uint16 for bfloat16, uint32 for float32."""
    h = np.arange(first, first + n, dtype=np.uint32)
    h *= np.uint32(GOLDEN)
    h += np.uint32(k)
    _fmix(h)
    if dtype == "bfloat16":
        out = (h >> 16) & 0x8000
        out |= ((h >> 7) & 0xF) + 0x70 << 7
        out |= h & 0x7F
        return out.astype("<u2")
    out = ((h >> 23) & 0xF) + 0x70 << 23
    out |= h & 0x80000000
    out |= h & 0x7FFFFF
    return out.astype("<u4", copy=False)


def block_bytes_of(leaf: Leaf, seed: int, rank: int, step: int, lo: int,
                   hi: int) -> bytes:
    """Bytes ``lo .. hi`` of the file of ``leaf``'s shard on ``rank`` at
    ``step`` (element-aligned)."""
    size = leaf.itemsize
    e0, e1 = lo // size, hi // size
    first, stop, s = element_steps(leaf, seed, rank, step)
    parts = []
    for a, b, st in ((e0, min(e1, first), 0),
                     (max(e0, first), min(e1, stop), s),
                     (max(e0, stop), e1, 0)):
        if b > a:
            parts.append(values(key(seed, leaf.uid, rank, st), a, b - a,
                                leaf.dtype))
    return b"".join(p.tobytes() for p in parts)


def _depends_on_step(leaf: Leaf, seed: int, rank: int, lo: int,
                     hi: int) -> bool:
    first, stop, _ = element_steps(leaf, seed, rank, 1)
    return lo // leaf.itemsize < stop and hi // leaf.itemsize > first


class TrainState:
    """Digests and CRC-32s of every block of every file of a state's
    save at a given step, for ``ranks`` ranks."""

    def __init__(self, config: Dict, seed: int, ranks: int):
        self.seed = int(seed)
        self.ranks = ranks
        self.block = int(config["sai"]["block_size"])
        self.leaves = layout(config)
        self._fixed: Dict[tuple, Tuple[bytes, int, int]] = {}
        self._lock = threading.Lock()

    def files(self) -> List[Tuple[Leaf, int]]:
        return [(leaf, r) for leaf in self.leaves for r in range(self.ranks)]

    def blocks(self, leaf: Leaf, rank: int, step: int
               ) -> List[Tuple[bytes, int, int]]:
        """(digest, length, crc32) of each block of the file."""
        out = []
        for lo in range(0, leaf.nbytes, self.block):
            hi = min(lo + self.block, leaf.nbytes)
            fixed = not _depends_on_step(leaf, self.seed, rank, lo, hi)
            memo = (leaf.uid, rank, lo)
            if fixed:
                with self._lock:
                    got = self._fixed.get(memo)
                if got is not None:
                    out.append(got)
                    continue
            data = block_bytes_of(leaf, self.seed, rank, step, lo, hi)
            got = (block_digest(data), len(data), zlib.crc32(data))
            if fixed:
                with self._lock:
                    self._fixed[memo] = got
            out.append(got)
        return out


def chunk_ends(data, sai: Dict) -> List[int]:
    """Block end offsets of a file's bytes; the last is ``len(data)``."""
    bs = int(sai["block_size"])
    return list(range(bs, len(data), bs)) + [len(data)]


def block_bytes(sai: Dict, object_bytes: int) -> Tuple[int, int]:
    """Least and most bytes the longest block of one file can hold."""
    n = min(int(sai["block_size"]), int(object_bytes))
    return 1, n
