"""Pallas TPU kernel: sliding-window MD5 (paper-faithful CDC primitive).

HashGPU's content-based-chunking module hashes EVERY overlapping window of
the stream (LBFS-style) — the most compute-intensive primitive in the
paper (7-51 MB/s on a 2008 CPU; the GPU offload wins up to 190x).

TPU adaptation: one VPU lane per window offset.  A window of <= 52 bytes
pads to a single 64-byte MD5 chunk, so each offset costs exactly 64
vectorized rounds.  Each strip is laid out as rows of 128 words, so one
(8, 128) group of rows is 1024 consecutive window offsets and fills a
vreg.  Overlapping windows cannot be expressed as disjoint BlockSpec
tiles, so each grid step also reads the 8 rows after its tile (a halo
block); the kernel copies tile + halo into one VMEM workspace — the TPU
analogue of HashGPU's shared-memory workspace holding the window
neighbourhood — and forms the 12 (window/4) shifted word streams of each
group with lane rolls (``layout.shift_words``).

Byte-granularity offsets (stride 1, as in LBFS/the paper) are handled in
ops.py by hashing 4 byte-rotated word streams — each stream is
word-strided, which keeps every lane's message word-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import layout
from repro.kernels.layout import LANES, SUBLANES
from repro.kernels.ref import MD5_INIT, md5_chunk_update

TILE_W = SUBLANES * LANES  # minimum words per tile (one vreg of offsets)
HALO_W = SUBLANES * LANES  # trailing words each tile reads past its end


def _sliding_kernel(cur_ref, halo_ref, out_ref, ext_ref, *, w_words: int):
    S = cur_ref.shape[1]
    ext_ref[pl.ds(0, S), :] = cur_ref[0]
    ext_ref[pl.ds(S, SUBLANES), :] = halo_ref[0]
    shape = (SUBLANES, LANES)

    def group(g, carry):
        base = pl.multiple_of(g * SUBLANES, SUBLANES)
        x = ext_ref[pl.ds(base, 2 * SUBLANES), :]   # this group + next
        M = []
        for jj in range(16):
            if jj < w_words:
                M.append(layout.shift_words(x, jj)[:SUBLANES])
            elif jj == w_words:
                M.append(jnp.full(shape, 0x80, jnp.uint32))
            elif jj == 14:
                M.append(jnp.full(shape, w_words * 32, jnp.uint32))
            else:
                M.append(jnp.zeros(shape, jnp.uint32))
        init = [jnp.full(shape, v, jnp.uint32) for v in MD5_INIT]
        a, _, _, _ = md5_chunk_update(*init, M)
        out_ref[0, pl.ds(base, SUBLANES), :] = a
        return carry

    jax.lax.fori_loop(0, S // SUBLANES, group, 0)


def sliding_md5_pallas(strips: jax.Array, w_words: int,
                       tile: int = TILE_W) -> jax.Array:
    """MD5 of every word-offset window over R parallel strips.

    strips: [R, W + HALO_W] uint32 — R independent word streams, each
    padded with HALO_W trailing words; W % tile == 0, tile % TILE_W == 0;
    window is ``w_words`` words (w_words <= 13 so the padded message is a
    single chunk).
    Returns [R, W // 128, 128] uint32: digest word 'a' (the CDC
    fingerprint) of the window starting at word offset q of strip r at
    [r, q // 128, q % 128].
    """
    R, Wp = strips.shape
    W = Wp - HALO_W
    assert W % tile == 0 and tile % TILE_W == 0, (W, tile)
    assert 0 < w_words <= 13
    S = tile // LANES                       # rows per tile
    kernel = functools.partial(_sliding_kernel, w_words=w_words)
    return layout.pallas_call(
        kernel,
        grid=(R, W // tile),
        in_specs=[
            pl.BlockSpec((1, S, LANES), lambda r, i: (r, i, 0)),
            pl.BlockSpec((1, SUBLANES, LANES),
                         lambda r, i: (r, (i + 1) * (S // SUBLANES), 0)),
        ],
        out_specs=pl.BlockSpec((1, S, LANES), lambda r, i: (r, i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, W // LANES, LANES), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((S + SUBLANES, LANES), jnp.uint32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(*(2 * [strips.reshape(R, Wp // LANES, LANES)]))
