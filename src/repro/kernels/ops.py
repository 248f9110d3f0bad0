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

Stream batching: ``sliding_hash_batch_device`` takes a padded [B, L]
word matrix and ``gear_hash_batch_device`` a padded [B, R, 128] byte
matrix (B independent buffers, one byte per element for gear); each
executes the whole batch as ONE kernel launch — the engine fuses bursts
of same-config stream jobs through these, then takes each job's row of
the fused output host-side: ``sliding_finish`` interleaves the sliding
kernel's phase matrix, ``gear_finish`` only views the gear kernel's
byte-order output.  Rows are zero-padded to the widest buffer in the
batch; window hashes only ever read bytes inside their own job's valid
prefix, so padding never changes a returned hash.
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
from repro.kernels.layout import LANES

# --------------------------------------------------------------------------
# direct hashing
# --------------------------------------------------------------------------


def md5_lane_rows(n_rows: int) -> int:
    """Rows the MD5 kernel computes for ``n_rows`` messages: one per
    lane, padded up to a whole lane tile."""
    return -(-n_rows // md5_k.TILE_N) * md5_k.TILE_N


@jax.jit
def direct_hash_device(words: jax.Array, lens_w: jax.Array) -> jax.Array:
    """Device-resident direct hashing: ``words`` [N, W] uint32 already on
    the target device, ``lens_w`` [N] int32 word lengths.  Returns the
    [N, 4] uint32 digest array *on device* (callers pull it with
    ``digest_bytes`` — 16 B/row, the only host transfer)."""
    N, W = words.shape
    n_pad = md5_lane_rows(N) - N
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


def row_bytes(block_bytes: int) -> int:
    """Width of a direct-kernel row for a block of ``block_bytes``: the
    block zero-padded to a word, its 4-byte length trailer, rounded up
    to a power of two (bounding the shapes a launch compiles for)."""
    return 1 << ((block_bytes + 3) // 4 * 4 + 4 - 1).bit_length()


def has_device_words(dtype) -> bool:
    """Whether the device can view an array of ``dtype`` as the words of
    its bytes (``_device_words``): bool, or a real 1-, 2- or 4-byte
    type whose bits fill its bytes.  Other arrays are hashed from a host
    copy."""
    dtype = np.dtype(dtype)
    if dtype == np.bool_:
        return True
    if dtype.itemsize not in (1, 2, 4):
        return False
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.finfo(dtype).bits == 8 * dtype.itemsize
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.iinfo(dtype).bits == 8 * dtype.itemsize
    return False


def _device_words(x: jax.Array) -> jax.Array:
    """The bytes of ``x`` (any shape, a dtype ``has_device_words``
    takes) as little-endian uint32 words, zero-padded to a whole word:
    what ``x.tobytes()`` viewed as ``<u4`` would give, made on the
    device."""
    if not has_device_words(x.dtype):
        raise ValueError(f"no device bytes for dtype {x.dtype}")
    if x.dtype == jnp.bool_:                    # one byte, 0 or 1
        x = x.astype(jnp.uint8)
    size = x.dtype.itemsize
    u = jax.lax.bitcast_convert_type(
        x, {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[size]).reshape(-1)
    per = 4 // size
    if per == 1:
        return u
    # word j takes elements per*j .. per*j+per-1; strided slices of rows
    # a lane tile wide keep every array's minor axis at LANES words (a
    # minor axis of `per` would pad to a whole tile on a TPU)
    n_words = -(-u.size // per)
    u = jnp.pad(u, (0, -u.size % (per * LANES))).reshape(-1, per * LANES)
    words = u[:, 0::per].astype(jnp.uint32)
    for k in range(1, per):
        words = words | (u[:, k::per].astype(jnp.uint32)
                         << jnp.uint32(8 * size * k))
    return words.reshape(-1)[:n_words]


@functools.partial(jax.jit, static_argnames=("block_bytes", "width",
                                             "rows"))
def pack_blocks_device(x: jax.Array, block_bytes: int, width: int,
                       rows: int) -> Tuple[jax.Array, jax.Array]:
    """The direct kernel's rows for the fixed blocks of ``x``'s bytes,
    built on the device ``x`` is on: block i (``block_bytes`` bytes, the
    last one shorter) zero-padded to a word, its u32 byte length after
    it, in a row of ``width`` bytes; ``rows`` rows (a power of two at
    least the block count, rows past it empty).  Returns ([rows,
    width/4] uint32 words, [rows] int32 word lengths), ready for
    ``direct_hash_device``: the same rows ``core.sai.pack_blocks`` makes
    on the host from the same bytes."""
    n_bytes = x.size * x.dtype.itemsize
    bw, ww = block_bytes // 4, width // 4
    n = -(-n_bytes // block_bytes)
    lens = np.full((n,), block_bytes, np.int64)
    lens[-1] = n_bytes - (n - 1) * block_bytes
    at = (lens + 3) // 4                       # trailer word of each row
    cw = int(at.max())                         # words of the widest block
    if cw >= ww or rows < n:
        raise ValueError(f"{n} blocks of up to {4 * cw} bytes do not fit "
                         f"{rows} rows of {width} bytes")
    words = _device_words(x)
    words = jnp.pad(words, (0, n * bw - words.size)).reshape(n, bw)
    out = jnp.pad(words[:, :cw], ((0, rows - n), (0, ww - cw)))
    out = out.at[np.arange(n), at].set(jnp.asarray(lens, jnp.uint32))
    lens_w = np.zeros((rows,), np.int32)
    lens_w[:n] = at + 1
    return out, jnp.asarray(lens_w)


@functools.partial(jax.jit, static_argnames=("block_bytes",))
def gather_blocks_device(x: jax.Array, idx: jax.Array,
                         block_bytes: int) -> jax.Array:
    """Whole blocks ``idx`` of ``x``'s fixed blocks (each below
    ``n_bytes // block_bytes``), gathered on the device into
    [len(idx), block_bytes / 4] uint32 rows, so one pull moves only
    them."""
    n_full = x.size * x.dtype.itemsize // block_bytes
    bw = block_bytes // 4
    words = _device_words(x)[:n_full * bw].reshape(n_full, bw)
    return jnp.take(words, idx, axis=0)


@functools.partial(jax.jit, static_argnames=("block_bytes",))
def tail_block_device(x: jax.Array, block_bytes: int) -> jax.Array:
    """The words of ``x``'s last fixed block when it is short (zero-
    padded to a whole word)."""
    n_full = x.size * x.dtype.itemsize // block_bytes
    return _device_words(x)[n_full * (block_bytes // 4):]


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
def _pick_tile(L: int, base: int, cap: int = 1 << 15) -> int:
    """Tile width bounding grid steps to ~64 (VMEM stays < ~0.5 MB/input
    block; the interpreter pays per grid step, so step count dominates
    its run time on CPU)."""
    t = base
    while L // t > 64 and t < cap:
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
# the tap construction the engine launches: the hybrid one took the least
# kernel time per 64 MiB on a TPU v5e
GEAR_VERSION = 3


def gear_hash_device(data: jax.Array,
                     version: int = GEAR_VERSION) -> jax.Array:
    """Device-resident gear hashing: ``data`` [R, 128] uint8 on the
    target device.  Returns the [R', 128] uint32 hashes on device, in
    byte order (R' >= R: rows padded to the kernel's tile);
    ``gear_finish`` flattens them host-side.  (The B=1 case of the
    batched path — one pad/launch wrapper and one jit cache.)"""
    return gear_hash_batch_device(data[None], version=version)[0]


@functools.partial(jax.jit, static_argnames=("version",))
def gear_hash_batch_device(data: jax.Array,
                           version: int = GEAR_VERSION) -> jax.Array:
    """Fused multi-buffer gear hashing: ``data`` [B, R, 128] uint8 on
    the target device, one job's bytes per row in row-major order (rows
    zero-padded to the batch width).  ONE kernel launch covers the whole
    batch; returns [B, R', 128] uint32 hashes on device, byte p of row b
    at [b, p // 128, p % 128] (R' >= R: zero rows pad R * 128 up to a
    multiple of the tile, which a power-of-two R of at least 32 never
    needs) — callers slice row b and flatten it
    with ``gear_finish`` using that job's own byte length."""
    B, R, _ = data.shape
    T = _pick_tile(R * LANES, gear_k.TILE, cap=1 << 17)
    r_cap = -(-R * LANES // T) * T // LANES
    if r_cap != R:
        data = jnp.pad(data, ((0, 0), (0, r_cap - R), (0, 0)))
    return gear_k.gear_pallas(data, version=version, tile=T)


def gear_finish(out: np.ndarray, n_bytes: int) -> np.ndarray:
    """The first ``n_bytes`` hashes of one row's [R, 128] output: a
    reshape and a slice, no copy (the kernel writes byte order)."""
    return out.reshape(-1)[:n_bytes]


def gear_hash(data: bytes | np.ndarray,
              version: int = GEAR_VERSION) -> np.ndarray:
    """Windowed gear hash at every byte position.  Returns [L] uint32.
    Positions < 32 differ from ref (zero-history convention: the 32
    bytes before the stream are zeros, hashed as ``gear(0)``) —
    chunking never places boundaries inside the minimum chunk size
    anyway.  ``version`` picks the tap construction (1 direct,
    2 log-doubling, 3 hybrid) — identical outputs."""
    buf = np.frombuffer(data, np.uint8) if isinstance(data, (bytes,
                                                             bytearray)) \
        else np.asarray(data, np.uint8)
    L = len(buf)
    rows = np.pad(buf, (0, -L % LANES)).reshape(-1, LANES)
    out = np.asarray(gear_hash_device(jnp.asarray(rows), version=version))
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
