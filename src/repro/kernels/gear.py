"""Pallas TPU kernel: windowed gear rolling hash (beyond-paper CDC).

The paper's sliding-window MD5 costs 64 rounds (~10 uint32 ops each) per
byte offset ~= 640 ops/byte.  For *boundary detection* a cryptographic
hash is unnecessary — production dedup (FastCDC, Shredder's successor
designs) uses a gear hash.  The sequential gear recurrence
``h = (h << 1) + gear[b]`` looks serial, but because bits shift out after
32 steps it is exactly a 32-tap windowed weighted sum:

    h_p = sum_{j=0}^{31} gear(b_{p-j}) << j

i.e. a convolution — computable as shifted vector adds, fully parallel
across lanes.

TPU-native details:
  * the gear function is table-free (murmur3 fmix32 of the byte) — a VMEM
    table gather would serialize on the VPU; 5 int ops beat a gather;
  * one byte per element: the input is a uint8 [rows, 128] byte matrix,
    widened to uint32 as it is loaded, so byte p of a stream sits at
    element p of the flattened rows and its hash is written to the same
    element of a uint32 [rows, 128] output.  The output leaves the chip
    in byte order; the host only reshapes it.  A tap j bytes back is a
    shift by j elements along the flattened stream, a lane roll plus a
    one-row carry (``layout.shift_words``);
  * the kernel walks its tile in groups of :data:`GROUP_ROWS` rows; each
    group is hashed together with the 8 rows before it (31 bytes of
    history need one row of them), so a tile reads the rows before it
    as a halo block — zeros at stream start, which hash as ``gear(0)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import layout
from repro.kernels.layout import LANES, SUBLANES
from repro.kernels.ref import GEAR_WINDOW

GROUP_ROWS = 32                 # rows per group: one uint8 (32, 128) tile
TILE = GROUP_ROWS * LANES       # minimum bytes per grid step


def _mix32(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    return x


def _back(s, j: int):
    """``s`` at the byte ``j`` positions earlier."""
    return layout.shift_words(s, -j)


def _gear_direct(g):
    """32 direct taps."""
    h = g
    for j in range(1, GEAR_WINDOW):
        h = h + (_back(g, j) << jnp.uint32(j))
    return h


def _gear_doubling(g):
    """§Perf C2: log-doubling construction of the 32-tap windowed sum.

    S_0(p) = g_p;  S_{k+1}(p) = S_k(p) + (S_k(p - 2^k) << 2^k)
    After 5 levels S_5 equals the full 32-tap sum — 5 shifted adds per
    byte instead of 32.  Shifted-in garbage only touches the first 31
    bytes of the history rows, which the output never reads.
    """
    s = g
    for k in range(5):                                       # shifts 1..16
        s = s + (_back(s, 1 << k) << jnp.uint32(1 << k))
    return s


def _gear_hybrid(g):
    """§Perf C3: depth-1 doubling then 16 direct taps.

    S1(p) = g_p + (g_{p-1} << 1) computed once over the group; the
    32-tap sum becomes 16 taps of S1 at even byte offsets:
    h_p = sum_{m=0}^{15} S1(p - 2m) << 2m."""
    s1 = g + (_back(g, 1) << jnp.uint32(1))
    h = s1
    for m in range(1, 16):
        h = h + (_back(s1, 2 * m) << jnp.uint32(2 * m))
    return h


_VERSIONS = {1: _gear_direct, 2: _gear_doubling, 3: _gear_hybrid}


def _gear_kernel(halo_ref, cur_ref, out_ref, *, version: int):
    hash_fn = _VERSIONS[version]
    # the 8 rows before the tile; none at stream start
    hist = halo_ref[0, pl.ds(GROUP_ROWS - SUBLANES, SUBLANES), :]
    hist = jnp.where(pl.program_id(1) == 0, jnp.uint8(0), hist)

    def group(i, prev):
        base = pl.multiple_of(i * GROUP_ROWS, GROUP_ROWS)
        cur = cur_ref[0, pl.ds(base, GROUP_ROWS), :].astype(jnp.uint32)
        x = jnp.concatenate([prev, cur], axis=0)
        h = hash_fn(_mix32(x + jnp.uint32(1)))
        out_ref[0, pl.ds(base, GROUP_ROWS), :] = h[SUBLANES:]
        return cur[GROUP_ROWS - SUBLANES:]

    jax.lax.fori_loop(0, cur_ref.shape[1] // GROUP_ROWS, group,
                      hist.astype(jnp.uint32))


def gear_pallas(data: jax.Array, version: int, tile: int = TILE) -> jax.Array:
    """Windowed gear hash of every byte position over B parallel streams.

    data: [B, R, 128] uint8, row b's bytes in row-major order, R * 128 a
    multiple of ``tile`` and ``tile`` a multiple of :data:`TILE`.  Rows
    are independent streams (the offload engine fuses a burst of gear
    jobs into one launch by stacking them here), each hashed as if 32
    zero bytes came before it; the grid runs (row, tile) so a single
    launch covers the whole batch.  ``tile`` is the BlockSpec width in
    bytes: larger tiles = fewer grid steps (bounded by the wrapper).
    ``version`` picks the tap construction (1 direct, 2 log-doubling,
    3 hybrid); all three give identical outputs.
    Returns [B, R, 128] uint32 in byte order: h for row b's byte
    position p at [b, p // 128, p % 128].
    """
    B, R, _ = data.shape
    assert (R * LANES) % tile == 0 and tile % TILE == 0, (R, tile)
    S = tile // LANES                       # rows per tile
    kernel = functools.partial(_gear_kernel, version=version)
    halo = S // GROUP_ROWS                  # halo blocks per tile
    return layout.pallas_call(
        kernel,
        grid=(B, R // S),
        in_specs=[
            pl.BlockSpec((1, GROUP_ROWS, LANES),
                         lambda b, i: (b, jnp.maximum(i * halo - 1, 0), 0)),
            pl.BlockSpec((1, S, LANES), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, S, LANES), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, R, LANES), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(data, data)
