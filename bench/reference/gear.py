"""Plain Gear fingerprints and the chunk boundaries they set.

Gear (FastCDC, USENIX ATC '16): ``h = (h << 1) + G[byte]`` over uint32.
A byte's term is shifted out after 32 steps, so the hash at byte p is
the 32-tap sum ``sum_{j<32} G[data[p-j]] << j`` (mod 2**32), taken here
literally.  ``G[b]`` is murmur3's fmix32 of ``b + 1``.

Boundaries follow restic's chunker rule with Gear in place of its Rabin
fingerprint: walking the data, a chunk ends after byte p when the chunk
holds at least ``min_chunk`` bytes and ``h[p] & mask == 0`` (``mask``
has log2(``avg_chunk``) low bits), or when it reaches ``max_chunk``
bytes; the data's end closes the last chunk.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench.reference.digest import digests  # noqa: F401  (the interface)

WINDOW = 32
_BLOCK = 1 << 20


def fmix32(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


TABLE = fmix32(np.arange(1, 257, dtype=np.uint32))


def hashes(data) -> np.ndarray:
    """Gear hash at every byte of ``data`` (the first 31 sum only the
    bytes that exist)."""
    g = TABLE[np.frombuffer(data, np.uint8)]
    h = g.copy()
    for j in range(1, WINDOW):
        h[j:] += g[:-j] << np.uint32(j)
    return h


def cut_candidates(data, mask: int) -> np.ndarray:
    """Chunk ends (exclusive offsets) after bytes whose hash has its
    ``mask`` bits clear: ``hashes`` block by block, so the work stays
    in cache."""
    buf = np.frombuffer(data, np.uint8)
    found = []
    for lo in range(0, buf.size, _BLOCK):
        first = max(lo - (WINDOW - 1), 0)
        h = hashes(buf[first:lo + _BLOCK])[lo - first:]
        found.append(np.flatnonzero((h & np.uint32(mask)) == 0) + lo + 1)
    return np.concatenate(found) if found else np.empty(0, np.int64)


def boundaries(data, avg_chunk: int, min_chunk: int,
               max_chunk: int) -> List[int]:
    """Chunk end offsets of ``data`` by the rule above; the last is
    ``len(data)``."""
    total = len(data)
    mask = avg_chunk - 1
    assert avg_chunk & mask == 0, "avg_chunk is a power of two"
    cands = cut_candidates(data, mask)
    cands = cands[cands < total]
    ends: List[int] = []
    last = 0
    while True:
        i = int(np.searchsorted(cands, last + min_chunk))
        nxt = int(cands[i]) if i < cands.size else None
        if nxt is not None and nxt - last <= max_chunk:
            last = nxt
        elif total - last > max_chunk:
            last += max_chunk
        else:
            break
        ends.append(last)
    ends.append(total)
    return ends


def chunk_ends(data, sai: Dict) -> List[int]:
    """Chunk end offsets of ``data`` at the configuration's sizes."""
    return boundaries(data, int(sai["avg_chunk"]), int(sai["min_chunk"]),
                      int(sai["max_chunk"]))


def block_bytes(sai: Dict, object_bytes: int) -> Tuple[int, int]:
    """Least and most bytes the longest chunk of one write can hold: a
    write of more than ``min_chunk`` bytes has a chunk of at least that
    many (only its last chunk may be shorter)."""
    n = int(object_bytes)
    return min(int(sai["min_chunk"]), n), min(int(sai["max_chunk"]), n)
