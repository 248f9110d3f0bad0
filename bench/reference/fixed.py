"""Plain reference of a fixed-block store: every ``block_size`` bytes
of a write make one block (the last may be shorter), each with the
store's block digest (``bench/reference/digest.py``)."""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench.reference.digest import digests  # noqa: F401  (the interface)


def chunk_ends(data, sai: Dict) -> List[int]:
    """Block end offsets of ``data``; the last is ``len(data)``."""
    bs = int(sai["block_size"])
    return list(range(bs, len(data), bs)) + [len(data)]


def block_bytes(sai: Dict, object_bytes: int) -> Tuple[int, int]:
    """Least and most bytes the longest block of one write can hold."""
    n = min(int(sai["block_size"]), int(object_bytes))
    return n, n
