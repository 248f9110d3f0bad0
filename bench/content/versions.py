"""Successive versions of one image per stream, the BLCR checkpoint-image
model that makes fixed blocks miss and content-defined chunks hit.

Version 1 is random; version k+1 rewrites a contiguous ``rewrite_frac``
of version k in place and then inserts ``n`` random bytes at one offset
and deletes ``n`` at another (``n`` in ``[indel_min, indel_max]``).
Objects of a stream are asked for in order, as each is made from the
one before; the source keeps only the latest version of each stream,
so a run keeps the bytes of what it wrote (``keeps_bytes``)."""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench.generator import rng


class Source:
    keeps_bytes = True

    def __init__(self, traffic: Dict, seed: int):
        self.size = int(traffic["object_bytes"])
        self.content = traffic["content"]
        self.seed = int(seed)
        self._last: Dict[int, tuple] = {}

    def obj(self, stream: int, k: int) -> bytes:
        last = self._last.get(stream)
        if k == 0:
            img = rng(self.seed, 1, stream).bytes(self.size)
        else:
            if last is None or last[0] != k - 1:
                raise ValueError(
                    f"stream {stream}: version {k} asked before {k - 1}")
            img = mutate(last[1], self.content, rng(self.seed, 2, stream, k))
        self._last[stream] = (k, img)
        return img


def mutate(img: bytes, content: Dict, gen: np.random.Generator) -> bytes:
    """Next checkpoint version: an in-place rewrite of a contiguous
    ``rewrite_frac`` of the image, then an insert/delete pair of ``n``
    random bytes (the length is unchanged)."""
    buf = bytearray(img)
    span = int(len(buf) * float(content["rewrite_frac"]))
    start = int(gen.integers(0, len(buf) - span))
    buf[start:start + span] = gen.bytes(span)
    n = int(gen.integers(int(content["indel_min"]),
                         int(content["indel_max"]) + 1))
    ins = int(gen.integers(0, len(buf)))
    buf[ins:ins] = gen.bytes(n)
    dele = int(gen.integers(0, len(buf) - n))
    del buf[dele:dele + n]
    return bytes(buf)
