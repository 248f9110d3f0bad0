"""Objects of ``object_bytes`` whose every block of ``stamp_every``
bytes is unique.

One random base buffer per run (from the seed) carries a 20-byte stamp
(seed, stream, object, block) at the start of each block, so no two
blocks of a run are equal and an object costs one copy of the base
(numpy's, which does not hold the interpreter lock) and one copy into
the ``bytes`` that is written, rather than fresh random bytes."""
from __future__ import annotations

import struct
from typing import Dict

import numpy as np

from bench.generator import rng

_STAMP = struct.Struct("<QIII")          # seed, stream, object, block


class Source:
    keeps_bytes = False                  # any object is made again cheaply

    def __init__(self, traffic: Dict, seed: int):
        self.size = int(traffic["object_bytes"])
        self.every = int(traffic["content"]["stamp_every"])
        self.seed = int(seed) % (1 << 64)
        self._base = np.frombuffer(rng(seed, 0).bytes(self.size), np.uint8)

    def obj(self, stream: int, k: int) -> bytes:
        buf = self._base.copy()
        for b, off in enumerate(range(0, self.size, self.every)):
            _STAMP.pack_into(buf, off, self.seed, stream, k, b)
        return buf.tobytes()
