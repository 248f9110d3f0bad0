"""Pallas TPU kernel: windowed gear rolling hash (beyond-paper CDC).

The paper's sliding-window MD5 costs 64 rounds (~10 uint32 ops each) per
byte offset ~= 640 ops/byte.  For *boundary detection* a cryptographic
hash is unnecessary — production dedup (FastCDC, Shredder's successor
designs) uses a gear hash.  The sequential gear recurrence
``h = (h << 1) + gear[b]`` looks serial, but because bits shift out after
32 steps it is exactly a 32-tap windowed weighted sum:

    h_p = sum_{j=0}^{31} gear(b_{p-j}) << j

i.e. a convolution — computable as 32 shifted vector adds, fully parallel
across lanes.  ~35 ops/byte: an ~18x arithmetic-intensity reduction over
sliding MD5 at equal chunking quality (§Perf hillclimb #3).

TPU-native details:
  * the gear function is table-free (murmur3 fmix32 of the byte) — a VMEM
    table gather would serialize on the VPU; 5 int ops beat a gather;
  * input is packed uint32 words laid out as rows of 128 words; the 4
    byte phases r in {0,1,2,3} are extracted in-register and each output
    stream h_r is assembled from cross-phase shifted streams (tap j of
    phase r reads phase (r-j) mod 4 at word offset
    -((j - r + (r-j)%4)/4)), shifted by lane rolls
    (``layout.shift_words``);
  * the kernel walks its tile in (8, 128) groups of 1024 words; each
    group is hashed together with the group before it (31 bytes of
    history need at most 8 words of it), so a tile reads the 8 rows
    before it as a halo block — zeros at stream start.
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

TILE_W = SUBLANES * LANES  # minimum words per tile


def _mix32(x):
    x = x.astype(jnp.uint32)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    return x


def _phases(x):
    """g[r][p] = gear(byte r of word p) for the 4 byte phases."""
    return [_mix32(((x >> jnp.uint32(8 * r)) & jnp.uint32(0xFF))
                   + jnp.uint32(1)) for r in range(4)]


def _back(r: int, j: int):
    """(phase, word offset) of the byte j positions before phase r."""
    rp = (r - j) % 4
    return rp, (j - r + rp) // 4


def _gear_direct(x):
    """32 direct taps; shifted streams shared between output phases."""
    g = _phases(x)
    taps = {}
    for r in range(4):
        for j in range(GEAR_WINDOW):
            taps.setdefault(_back(r, j), []).append((r, j))
    h = [None] * 4
    for (rp, a), uses in sorted(taps.items()):
        src = layout.shift_words(g[rp], -a)
        for r, j in uses:
            t = src << jnp.uint32(j)
            h[r] = t if h[r] is None else h[r] + t
    return h


def _gear_doubling(x):
    """§Perf C2: log-doubling construction of the 32-tap windowed sum.

    S_0(p) = g_p;  S_{k+1}(p) = S_k(p) + (S_k(p - 2^k) << 2^k)
    After 5 levels S_5 equals the full 32-tap sum — 5 shifted adds per
    byte instead of 32 (napkin: ~2.8x fewer VPU ops than the direct
    kernel; measured via cost_analysis in benchmarks/kernel_roofline).

    Byte shifts of 1 and 2 cross the 4 byte phases; shifts 4/8/16 are
    whole words (phase-preserving).  Shifted-in garbage only touches the
    first words of the history group, which the output never reads
    (31 bytes of history < one group).
    """
    s_cur = _phases(x)
    for k in range(5):                                       # shifts 1..16
        s = 1 << k
        nxt = []
        for r in range(4):
            rp, a = _back(r, s)
            src = layout.shift_words(s_cur[rp], -a)
            nxt.append(s_cur[r] + (src << jnp.uint32(s)))
        s_cur = nxt
    return s_cur


def _gear_hybrid(x):
    """§Perf C3: depth-1 doubling then 16 direct taps.

    S1(p) = g_p + (g_{p-1} << 1) computed once over the halo window; the
    32-tap sum becomes 16 taps of S1 at even byte offsets:
    h_p = sum_{m=0}^{15} S1(p - 2m) << 2m.  Napkin: ~52 VPU ops/byte vs
    the direct kernel's ~85 (taps halve; the one doubling level touches
    the halo window only once)."""
    g = _phases(x)
    s1 = []
    for r in range(4):
        rp, a = _back(r, 1)
        s1.append(g[r] + (layout.shift_words(g[rp], -a) << jnp.uint32(1)))
    h = []
    for r in range(4):
        acc = None
        for m in range(16):
            rp, a = _back(r, 2 * m)
            t = layout.shift_words(s1[rp], -a) << jnp.uint32(2 * m)
            acc = t if acc is None else acc + t
        h.append(acc)
    return h


_VERSIONS = {1: _gear_direct, 2: _gear_doubling, 3: _gear_hybrid}


def _gear_kernel(halo_ref, cur_ref, out_ref, ext_ref, *, version: int):
    S = cur_ref.shape[1]
    ext_ref[pl.ds(0, SUBLANES), :] = halo_ref[0]
    ext_ref[pl.ds(SUBLANES, S), :] = cur_ref[0]
    hash_fn = _VERSIONS[version]

    def group(g, carry):
        base = pl.multiple_of(g * SUBLANES, SUBLANES)
        x = ext_ref[pl.ds(base, 2 * SUBLANES), :]  # previous group + this
        for r, h in enumerate(hash_fn(x)):
            out_ref[0, r, pl.ds(base, SUBLANES), :] = h[SUBLANES:]
        return carry

    jax.lax.fori_loop(0, S // SUBLANES, group, 0)


def gear_pallas(strip: jax.Array, version: int = 1,
                tile: int = TILE_W) -> jax.Array:
    """Windowed gear hash of every byte position over B parallel strips.

    strip: [B, tile + W] uint32 packed little-endian bytes, each row with
    ``tile`` leading pad words (history; zeros at stream start) — W data
    words, W % tile == 0, tile % TILE_W == 0.  Rows are independent
    streams (the offload engine fuses a burst of gear jobs into one
    launch by stacking them here); the grid runs (row, tile) so a single
    launch covers the whole batch.  ``tile`` is the BlockSpec width:
    larger tiles = fewer grid steps (bounded by the wrapper).
    ``version`` picks the tap construction (1 direct, 2 log-doubling,
    3 hybrid); all three give identical outputs.
    Returns [B, 4, W // 128, 128] uint32: h for row b's byte position
    4q + r at [b, r, q // 128, q % 128].  (Merging the last two dims is
    left to the host: on the device it is a relayout copy of the whole
    output.)
    """
    B, Wp = strip.shape
    W = Wp - tile
    assert W % tile == 0 and tile % TILE_W == 0, (W, tile)
    S = tile // LANES                       # rows per tile
    kernel = functools.partial(_gear_kernel, version=version)
    return layout.pallas_call(
        kernel,
        grid=(B, W // tile),
        in_specs=[
            pl.BlockSpec((1, SUBLANES, LANES),
                         lambda b, i: (b, (i + 1) * (S // SUBLANES) - 1, 0)),
            pl.BlockSpec((1, S, LANES), lambda b, i: (b, i + 1, 0)),
        ],
        out_specs=pl.BlockSpec((1, 4, S, LANES), lambda b, i: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 4, W // LANES, LANES),
                                       jnp.uint32),
        scratch_shapes=[pltpu.VMEM((S + SUBLANES, LANES), jnp.uint32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(*(2 * [strip.reshape(B, Wp // LANES, LANES)]))
