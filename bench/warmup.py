"""Compile, before the window, every direct-hash launch shape a cell's
traffic can produce.

The engine stages a fused direct launch as a [B, W] uint8 matrix with B
and W rounded up to powers of two (B <= ``max_batch`` jobs of one row
each, or one job's rows), and a row is one block padded to a multiple
of 4, plus a 4-byte length, rounded up to a power of two.  So the row
widths follow from the block sizes the configuration can cut, and each
is run once here for every power-of-two B up to the widest launch it
can make.  A launch of one write's blocks is as wide as its longest
block, so the widths run from the least to the most that block can
hold.  The stream kernels (gear) see one shape per object size and
are warmed by the cell's own set-up writes.
"""
from __future__ import annotations

from typing import List

MAX_ROWS = 64                 # CrystalTPU's default max_batch
MAX_LAUNCH_BYTES = 1 << 30    # no job of these cells stages more


def _row_width(block_bytes: int) -> int:
    w = (block_bytes + 3) // 4 * 4 + 4
    return 1 << (w - 1).bit_length()


def row_widths(lo: int, hi: int) -> List[int]:
    """Staged row widths of the direct launches whose widest block holds
    from ``lo`` to ``hi`` bytes (the configuration's reference module
    gives the range, ``block_bytes``)."""
    widths = []
    w = _row_width(lo)
    while w <= _row_width(hi):
        widths.append(w)
        w *= 2
    return widths


def direct_shapes(engine, widths: List[int]) -> int:
    """Run ``direct_hash_device`` once per (B, W) on every engine device,
    on zeros made on the device.  Returns the number of shapes."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    n = 0
    for dev in engine.devices:
        for w in widths:
            b = 1
            while b <= MAX_ROWS and b * w <= MAX_LAUNCH_BYTES:
                words = jnp.zeros((b, w // 4), jnp.uint32, device=dev)
                lens = jnp.full((b,), w // 4, jnp.int32, device=dev)
                jax.block_until_ready(ops.direct_hash_device(words, lens))
                n += 1
                b *= 2
    return n
