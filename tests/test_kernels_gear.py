"""Gear rolling-hash kernel vs ref oracle + chunking-equivalence with the
sequential FastCDC recurrence."""
import jax.numpy as jnp
import numpy as np
import pytest
from _hypcompat import given, settings, strategies as st

from repro.core import CrystalTPU
from repro.kernels import ops, ref
from repro.core.sai import _cpu_gear


def _want(buf):
    """``ref.gear_ref`` at every position under the kernel's convention:
    the 32 bytes before a stream are zeros, hashed as gear(0)."""
    hist = np.zeros(32, np.uint8)
    return np.asarray(ref.gear_ref(jnp.asarray(
        np.concatenate([hist, buf]))))[32:]


@pytest.mark.parametrize("version", [1, 2, 3])
@pytest.mark.parametrize("L", [1, 31, 130, 4097, 9003])
def test_gear_kernel_every_position(rng, version, L):
    """Every position, the first 31 included, for each tap construction
    and for lengths that are not multiples of 4 or 128."""
    buf = rng.integers(0, 256, L, dtype=np.uint8)
    got = ops.gear_hash(buf.tobytes(), version=version)
    np.testing.assert_array_equal(got, _want(buf))


def test_gear_batch_rows_are_independent_streams(rng):
    """Each row of a fused batch hashes from zero history, and the last
    bytes of one row never reach the next."""
    rows = rng.integers(0, 256, (3, 64, 128), dtype=np.uint8)
    out = np.asarray(ops.gear_hash_batch_device(jnp.asarray(rows)))
    assert out.shape == (3, 64, 128) and out.dtype == np.uint32
    for b in range(3):
        np.testing.assert_array_equal(out[b].reshape(-1),
                                      _want(rows[b].reshape(-1)))


def test_engine_fuses_ragged_gear_jobs_in_byte_order(rng, monkeypatch):
    """A burst of ragged gear jobs runs as one launch with B > 1; each
    result is exact and is a view of the array pulled from the device
    (no host reorder)."""
    pulled = []
    real = ops.gear_finish

    def finish(out, n):
        res = real(out, n)
        pulled.append((out, res))
        return res
    monkeypatch.setattr(ops, "gear_finish", finish)
    eng = CrystalTPU(coalesce_window_s=0.2, max_batch=64)
    try:
        bufs = [rng.integers(0, 256, n, dtype=np.uint8)
                for n in (5001, 4099, 6143, 130)]
        jobs = [eng.submit("gear", b, {}) for b in bufs]
        for j, b in zip(jobs, bufs):
            np.testing.assert_array_equal(j.wait(), _want(b))
        stats = eng.snapshot_stats()
    finally:
        eng.shutdown()
    assert stats["launches"] < stats["jobs"] == len(bufs), stats
    assert len(pulled) == len(bufs)
    for j in jobs:
        out = next(o for o, r in pulled if r is j.result)
        assert np.shares_memory(j.result, out)


def test_gear_kernel_vs_ref(rng):
    L = 5000
    buf = rng.integers(0, 256, L, dtype=np.uint8)
    got = ops.gear_hash(buf.tobytes())
    want = np.asarray(ref.gear_ref(jnp.asarray(buf)))
    # positions < window differ (zero-history convention); beyond, exact
    np.testing.assert_array_equal(got[32:], want[32:])


def test_gear_kernel_vs_sequential_recurrence(rng):
    """The convolution form == the FastCDC h=(h<<1)+g recurrence."""
    L = 1000
    buf = rng.integers(0, 256, L, dtype=np.uint8)
    seq = _cpu_gear(buf.tobytes(), vectorized=False)
    vec = _cpu_gear(buf.tobytes(), vectorized=True)
    par = ops.gear_hash(buf.tobytes())
    np.testing.assert_array_equal(vec[32:], seq[32:])
    np.testing.assert_array_equal(par[32:], seq[32:])


def test_gear_window_property(rng):
    """h at position p depends only on bytes (p-31 .. p)."""
    L = 600
    a = rng.integers(0, 256, L, dtype=np.uint8)
    b = a.copy()
    b[:L - 64] = rng.integers(0, 256, L - 64, dtype=np.uint8)
    ha = ops.gear_hash(a.tobytes())
    hb = ops.gear_hash(b.tobytes())
    np.testing.assert_array_equal(ha[L - 32:], hb[L - 32:])


@settings(max_examples=10, deadline=None)
@given(st.binary(min_size=64, max_size=2048))
def test_gear_hypothesis_matches_ref(data):
    got = ops.gear_hash(data)
    want = np.asarray(ref.gear_ref(jnp.asarray(
        np.frombuffer(data, np.uint8))))
    np.testing.assert_array_equal(got[32:], want[32:])
