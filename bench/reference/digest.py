"""The store's block digest, by hashlib.

Block digest = MD5( data zero-padded to a multiple of 4 bytes ||
little-endian u32 byte length ).  The length trailer tells apart blocks
that differ only in trailing zeros, which content-defined boundaries
can produce.
"""
from __future__ import annotations

import hashlib
import struct

_LEN = struct.Struct("<I")


def block_digest(block) -> bytes:
    """Digest of one block (``bytes`` or a ``memoryview`` of one)."""
    n = len(block)
    h = hashlib.md5(block)
    h.update(b"\x00" * (-n % 4) + _LEN.pack(n))
    return h.digest()


def digests(data, ends):
    """Digests of the blocks of ``data`` that end at ``ends`` (the first
    starts at 0)."""
    view = memoryview(data)
    out, start = [], 0
    for end in ends:
        out.append(block_digest(view[start:end]))
        start = end
    return out
