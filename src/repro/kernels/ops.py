"""jit'd host-facing wrappers around the Pallas hashing kernels.

All APIs take/return numpy-friendly arrays; padding, word packing, byte-
phase strip construction and output interleaving live here so the kernels
stay shape-regular.  A launch placed on a TPU lowers its kernel through
Mosaic; on any other backend (the CPU the tests run on) the same call
runs the Pallas interpreter (``layout.pallas_call``).

Two layers are exposed:
  * convenience wrappers (``direct_hash``, ``sliding_window_hash``,
    ``gear_hash``) that take host arrays and do prep + launch + finish;
  * device-resident entry points (``direct_hash_device``,
    ``sliding_hash_device``, ``gear_hash_device``) plus host-side finish
    helpers (``digest_bytes``, ``sliding_finish``, ``gear_finish``) used
    by the CrystalTPU offload engine, which manages its own staging
    buffers and ``device_put`` so data stays on the accelerator from
    transfer through kernel with no host round-trip.

Stream batching: ``sliding_hash_batch_device`` / ``gear_hash_batch_device``
take a padded [B, L] word matrix (B independent buffers) and execute the
whole batch as ONE kernel launch — the engine fuses bursts of same-config
stream jobs through these, then slices each job's rows out of the fused
phase-matrix output host-side (``sliding_finish`` / ``gear_finish`` per
row).  Rows are zero-padded to the widest buffer in the batch; window
hashes only ever read bytes inside their own job's valid prefix, so
padding never changes a returned hash.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import gear as gear_k
from repro.kernels import md5 as md5_k
from repro.kernels import sliding_md5 as slide_k

# --------------------------------------------------------------------------
# direct hashing
# --------------------------------------------------------------------------


@jax.jit
def direct_hash_device(words: jax.Array, lens_w: jax.Array) -> jax.Array:
    """Device-resident direct hashing: ``words`` [N, W] uint32 already on
    the target device, ``lens_w`` [N] int32 word lengths.  Returns the
    [N, 4] uint32 digest array *on device* (callers pull it with
    ``digest_bytes`` — 16 B/row, the only host transfer)."""
    N, W = words.shape
    n_pad = (-N) % md5_k.TILE_N
    # bound the chunk grid to ~8 steps per segment tile (grid dispatch
    # dominates on the interpreter; on TPU this is simply a larger VMEM
    # message block, capped at 16*512*TILE_N words = 4 MiB)
    n_chunks = (W + 3 + 15) // 16
    chunk_tile = min(512, max(md5_k.CHUNK_TILE, -(-n_chunks // 8)))
    w_pad = (-(W + 3)) % (16 * chunk_tile) + 3
    data = jnp.pad(words, ((0, n_pad), (0, w_pad)))
    lens = jnp.pad(lens_w.astype(jnp.int32), (0, n_pad))
    dig = md5_k.md5_pallas(data.T, lens, chunk_tile=chunk_tile)  # [4, Npad]
    return dig.T[:N]


def digest_bytes(dig) -> np.ndarray:
    """[N, 4] uint32 digests (device or host) -> [N, 16] uint8 host."""
    dig = np.ascontiguousarray(dig, dtype="<u4")
    return dig.view(np.uint8).reshape(dig.shape[0], 16)


def direct_hash(segments: np.ndarray, lens_bytes=None) -> np.ndarray:
    """MD5 digests of N word-aligned segments.

    segments: [N, seg_bytes/4] uint32 (or uint8 [N, seg_bytes]);
    lens_bytes: optional [N] actual byte lengths (multiples of 4).
    Returns [N, 16] uint8 digests (hashlib-identical).
    """
    segments = np.asarray(segments)
    if segments.dtype == np.uint8:
        assert segments.shape[1] % 4 == 0
        segments = segments.view("<u4") if segments.flags.c_contiguous \
            else np.ascontiguousarray(segments).view("<u4")
    N, W = segments.shape
    if lens_bytes is None:
        lens_w = np.full((N,), W, np.int32)
    else:
        lens_bytes = np.asarray(lens_bytes)
        assert np.all(lens_bytes % 4 == 0)
        lens_w = (lens_bytes // 4).astype(np.int32)
    dig = direct_hash_device(jnp.asarray(segments), jnp.asarray(lens_w))
    return digest_bytes(dig)


def hash_blocks(data: bytes, block_bytes: int) -> Tuple[np.ndarray, bytes]:
    """Fixed-size-block direct hashing of a buffer (paper's fixed-block
    content addressability).  Returns ([n_blocks, 16] digests, final
    digest bytes = md5 over the concatenated digests, computed host-side
    exactly like the paper's CPU post-processing stage)."""
    import hashlib
    n = (len(data) + block_bytes - 1) // block_bytes
    padded = data + b"\x00" * (n * block_bytes - len(data))
    arr = np.frombuffer(padded, np.uint8).reshape(n, block_bytes)
    lens = np.full((n,), block_bytes, np.int64)
    lens[-1] = len(data) - (n - 1) * block_bytes
    lens = ((lens + 3) // 4 * 4)                  # word-align tail
    digs = direct_hash(arr, lens)
    final = hashlib.md5(digs.tobytes()).digest()
    return digs, final


# --------------------------------------------------------------------------
# sliding-window MD5 (paper-faithful CDC)
# --------------------------------------------------------------------------
def _pick_tile(L: int, base: int) -> int:
    """Tile width bounding grid steps to ~64 (VMEM stays < ~0.5 MB/input
    block; the interpreter pays per grid step, so step count dominates
    its run time on CPU)."""
    t = base
    while L // t > 64 and t < (1 << 15):
        t *= 2
    return t


def sliding_hash_device(words: jax.Array, w_words: int,
                        phases: Tuple[int, ...]) -> jax.Array:
    """Device-resident sliding-window hashing: ``words`` [L] uint32 on
    the target device.  Returns the [R, Wc/128, 128] uint32 per-phase
    hash matrix on device; ``sliding_finish`` interleaves it host-side.
    (The B=1 case of the batched path — one strip builder and one jit
    cache.)"""
    return sliding_hash_batch_device(words[None], w_words, phases)[0]


def sliding_finish(out: np.ndarray, phases: Tuple[int, ...],
                   n_off: int) -> np.ndarray:
    """Interleave phase rows: offset o = 4q + phases[r] -> out[r, q]
    (``out`` [R, Wc/128, 128] as the kernel writes it)."""
    if n_off <= 0:                 # input shorter than one window
        return np.empty((0,), np.uint32)
    out = out.reshape(len(phases), -1)
    R, Wc = out.shape
    inter = np.empty((Wc * R,), np.uint32)
    for i, r in enumerate(phases):
        inter[i::R] = out[i]
    return inter[:n_off]


def _byte_phase_strips_batch(words: jax.Array, phases: Tuple[int, ...],
                             pad_words: int) -> jax.Array:
    """Batched strip construction: rows are independent buffers, so the
    cross-word carry shifts stay within each row."""
    B = words.shape[0]
    nxt = jnp.concatenate([words[:, 1:], jnp.zeros((B, 1), jnp.uint32)],
                          axis=1)
    strips = []
    for r in phases:
        if r == 0:
            s = words
        else:
            s = (words >> jnp.uint32(8 * r)) | (nxt << jnp.uint32(32 - 8 * r))
        strips.append(jnp.pad(s, ((0, 0), (0, pad_words))))
    return jnp.stack(strips, axis=1)                     # [B, R, L+pad]


@functools.partial(jax.jit, static_argnames=("w_words", "phases"))
def sliding_hash_batch_device(words: jax.Array, w_words: int,
                              phases: Tuple[int, ...]) -> jax.Array:
    """Fused multi-buffer sliding-window hashing: ``words`` [B, L] uint32
    on the target device, one row per job (rows zero-padded to the batch
    width).  ONE kernel launch covers all B*R strips; returns the
    [B, R, Wc/128, 128] uint32 per-job/per-phase hash matrix on device —
    callers slice row b and run ``sliding_finish`` with that job's own
    offset count."""
    B, L = words.shape
    T = _pick_tile(L, slide_k.TILE_W)
    w_cap = ((L + T - 1) // T) * T
    strips = _byte_phase_strips_batch(
        words, phases, w_cap - L + slide_k.HALO_W)  # [B, R, w_cap+HALO_W]
    R = len(phases)
    out = slide_k.sliding_md5_pallas(
        strips.reshape(B * R, w_cap + slide_k.HALO_W), w_words,
        tile=T)                                  # [B*R, w_cap/128, 128]
    return out.reshape(B, R, *out.shape[1:])


def sliding_window_hash(data: bytes | np.ndarray, window: int = 48,
                        stride: int = 1) -> np.ndarray:
    """MD5 (digest word 'a') of every ``window``-byte window at byte
    offsets 0, stride, 2*stride, ...  window % 4 == 0, window <= 52;
    stride in {1, 2, 4}.  Returns [n_off] uint32."""
    assert window % 4 == 0 and window <= 52 and stride in (1, 2, 4)
    buf = np.frombuffer(data, np.uint8) if isinstance(data, (bytes,
                                                             bytearray)) \
        else np.asarray(data, np.uint8)
    L = len(buf)
    if L < window:                 # no complete window: empty hash array
        return np.empty((0,), np.uint32)
    n_off = (L - window) // stride + 1
    pad = (-L) % 4
    words = jnp.asarray(np.pad(buf, (0, pad)).view("<u4"))
    phases = tuple(range(0, 4, stride))
    out = np.asarray(sliding_hash_device(words, window // 4, phases))
    return sliding_finish(out, phases, n_off)


# --------------------------------------------------------------------------
# gear rolling hash (beyond-paper CDC)
# --------------------------------------------------------------------------
def gear_hash_device(words: jax.Array, version: int = 1) -> jax.Array:
    """Device-resident gear hashing: ``words`` [L] uint32 on the target
    device.  Returns the [4, w_cap/128, 128] uint32 phase matrix on
    device; ``gear_finish`` flattens it host-side.  (The B=1 case of the
    batched path — one pad/launch wrapper and one jit cache.)"""
    return gear_hash_batch_device(words[None], version=version)[0]


@functools.partial(jax.jit, static_argnames=("version",))
def gear_hash_batch_device(words: jax.Array, version: int = 1) -> jax.Array:
    """Fused multi-buffer gear hashing: ``words`` [B, L] uint32 on the
    target device, one row per job (rows zero-padded to the batch width).
    ONE kernel launch covers the whole batch; returns the
    [B, 4, Wc/128, 128] uint32 phase matrices on device — callers slice
    row b and flatten it with ``gear_finish`` using that job's own byte
    length."""
    B, L = words.shape
    T = _pick_tile(L, gear_k.TILE_W)
    w_cap = ((L + T - 1) // T) * T
    strip = jnp.pad(words, ((0, 0), (T, w_cap - L)))   # per-row history 0s
    return gear_k.gear_pallas(strip, version=version, tile=T)


def gear_finish(out: np.ndarray, n_bytes: int) -> np.ndarray:
    """Flatten the [4, w_cap/128, 128] phase matrix to per-byte order
    (4q + r)."""
    return out.reshape(4, -1).T.reshape(-1)[:n_bytes]


def gear_hash(data: bytes | np.ndarray, version: int = 1) -> np.ndarray:
    """Windowed gear hash at every byte position.  Returns [L] uint32.
    Positions < 32 differ from ref (zero-history convention) — chunking
    never places boundaries inside the minimum chunk size anyway.
    ``version=2`` selects the log-doubling kernel (§Perf C2) — identical
    outputs, ~3x fewer VPU ops."""
    buf = np.frombuffer(data, np.uint8) if isinstance(data, (bytes,
                                                             bytearray)) \
        else np.asarray(data, np.uint8)
    L = len(buf)
    pad = (-L) % 4
    words = jnp.asarray(np.pad(buf, (0, pad)).view("<u4"))
    out = np.asarray(gear_hash_device(words, version=version))
    return gear_finish(out, L)


# ----------------------------------------------------------------------
# whale-job shard planning (host-side helpers for the engine mesh)
# ----------------------------------------------------------------------
# the gear hash at byte p is a 32-tap window over x[p-31..p] (each tap
# shifts out of the 32-bit accumulator after 32 doublings), so a shard
# that carries 32 bytes of left context reproduces the full-buffer
# output from its first owned byte onward
GEAR_HISTORY_BYTES = 32


def shard_row_ranges(n_rows: int, n_shards: int):
    """Balanced contiguous ``[start, stop)`` row ranges covering
    ``n_rows`` — the per-device sub-launch split of a whale direct-hash
    job (row digests are independent, so any row partition reassembles
    by concatenation in range order)."""
    k = max(1, min(int(n_shards), int(n_rows)))
    base, rem = divmod(int(n_rows), k)
    ranges = []
    start = 0
    for i in range(k):
        stop = start + base + (1 if i < rem else 0)
        ranges.append((start, stop))
        start = stop
    return ranges


def stream_shard_plan(n_bytes: int, kind: str, n_shards: int,
                      window: int = 48, stride: int = 4):
    """Byte-slice plan ``[(start, stop, n_drop), ...]`` splitting one
    stream buffer into sub-launches whose outputs — after dropping the
    first ``n_drop`` values of each shard — concatenate to exactly the
    unsharded kernel output.

    sliding: the offset grid ``o = f * stride`` partitions across
    shards; each shard's slice starts at its first owned offset (start
    is stride-aligned) and extends through the last owned window, so
    every window a shard owns lies fully inside its slice and nothing
    is dropped.

    gear: each shard k > 0 takes ``GEAR_HISTORY_BYTES`` of left
    context and drops that many leading outputs (they belong to the
    previous shard); the kernel's zero-history warm-up therefore only
    ever affects positions the previous shard already produced.

    Returns None when the buffer is too small to shard meaningfully.
    """
    n_bytes, k = int(n_bytes), int(n_shards)
    if kind == "sliding":
        n_off = (n_bytes - window) // stride + 1
        k = min(k, max(n_off // 2, 0))
        if k < 2:
            return None
        base, rem = divmod(n_off, k)
        plan = []
        f = 0
        for i in range(k):
            c = base + (1 if i < rem else 0)
            start = f * stride
            stop = min((f + c - 1) * stride + window, n_bytes)
            plan.append((start, stop, 0))
            f += c
        return plan
    if kind == "gear":
        h = GEAR_HISTORY_BYTES
        k = min(k, n_bytes // (4 * h))
        if k < 2:
            return None
        bounds = [n_bytes * i // k for i in range(k + 1)]
        plan = [(0, bounds[1], 0)]
        for i in range(1, k):
            plan.append((bounds[i] - h, bounds[i + 1], h))
        return plan
    return None
