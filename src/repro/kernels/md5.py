"""Pallas TPU kernel: parallel Merkle-Damgard MD5 (direct hashing).

TPU adaptation of HashGPU's direct-hashing module (the paper's GPU design
assigns one *thread* per segment; here one *VPU lane* per segment):

  * layout is word-major — ``data[word, segment]`` — so each MD5 round is
    a vectorized uint32 op across TILE_N segment lanes, and the
    per-chunk message words are contiguous sublane rows;
  * the grid is (segment_tiles, chunk_tiles) with the chunk dimension
    innermost and 'arbitrary' (sequential): the digest state accumulates
    in the output block across chunk steps — the canonical Pallas
    reduction pattern — so VMEM holds only CHUNK_TILE * 16 message rows,
    never the whole segment (streaming HBM->VMEM like the paper's staged
    global->shared-memory pipeline);
  * MD5 padding (word-aligned messages) is generated in-register via
    vector selects, so lanes with different message lengths coexist in a
    tile (the GPU version's per-thread bounds checks, adapted to selects);
  * every value in the body is 2-D ``(1, TILE_N)`` and every chunk's 16
    message words come from one aligned ``(16, TILE_N)`` ref slice, the
    forms Mosaic lowers.

Hashing is integer-ALU work: it runs on the VPU (8x128 int32 ops/cycle),
not the MXU — the roofline for this kernel is VPU-issue-bound, which is
exactly the paper's 'compute-intensive primitive' premise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import layout
from repro.kernels.ref import MD5_INIT, md5_chunk_update

TILE_N = layout.LANES  # segments per tile (lane dim)
CHUNK_TILE = 4         # 64-byte chunks per grid step (16 words each)


def _md5_kernel(lens_ref, data_ref, out_ref, *, chunk_tile: int):
    """Chunks iterate via fori_loop (one 64-round body in the trace/IR
    regardless of segment length); rounds stay unrolled so message-word
    indices are static."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        for r, v in enumerate(MD5_INIT):
            out_ref[r:r + 1, :] = jnp.full((1, TILE_N), v, jnp.uint32)

    lens = lens_ref[...]                                    # (1, TILE_N)
    end = ((lens + 18) >> 4) << 4          # padded message end, in words
    bits_lo = lens.astype(jnp.uint32) << jnp.uint32(5)
    bits_hi = lens.astype(jnp.uint32) >> jnp.uint32(27)
    zero = jnp.zeros((1, TILE_N), jnp.uint32)

    def body(cc, state):
        a, b, c, d = state
        w0 = (j * chunk_tile + cc) * 16                     # global word
        rows = data_ref[pl.ds(pl.multiple_of(cc * 16, 16), 16), :]
        M = []
        for jj in range(16):
            w = w0 + jj
            m = jnp.where(w < lens, rows[jj:jj + 1, :], zero)
            m = jnp.where(w == lens, jnp.uint32(0x80), m)
            m = jnp.where(w == end - 2, bits_lo, m)
            m = jnp.where(w == end - 1, bits_hi, m)
            M.append(m)
        na, nb, nc_, nd = md5_chunk_update(a, b, c, d, M)
        active = w0 < end
        return (jnp.where(active, na, a), jnp.where(active, nb, b),
                jnp.where(active, nc_, c), jnp.where(active, nd, d))

    state = tuple(out_ref[r:r + 1, :] for r in range(4))
    state = jax.lax.fori_loop(0, chunk_tile, body, state)
    for r in range(4):
        out_ref[r:r + 1, :] = state[r]


def md5_pallas(data_T: jax.Array, lens_w: jax.Array,
               chunk_tile: int = CHUNK_TILE) -> jax.Array:
    """MD5 of N word-aligned messages.

    data_T: [max_words_padded, N] uint32 (word-major!), N % TILE_N == 0,
    max_words_padded % (16 * chunk_tile) == 0; lens_w: [N] int32.
    ``chunk_tile`` = 64-byte chunks per grid step (VMEM block is
    16 * chunk_tile * TILE_N words; the wrapper sizes it to bound grid
    steps for long segments).
    Returns [4, N] uint32 digest words.
    """
    W, N = data_T.shape
    assert N % TILE_N == 0, N
    assert W % (16 * chunk_tile) == 0, (W, chunk_tile)
    n_seg_tiles = N // TILE_N
    n_chunk_tiles = W // (16 * chunk_tile)

    kernel = functools.partial(_md5_kernel, chunk_tile=chunk_tile)
    return layout.pallas_call(
        kernel,
        grid=(n_seg_tiles, n_chunk_tiles),
        in_specs=[
            pl.BlockSpec((1, TILE_N), lambda i, j: (0, i)),
            pl.BlockSpec((16 * chunk_tile, TILE_N), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((4, TILE_N), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((4, N), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(lens_w.astype(jnp.int32).reshape(1, N), data_T)
