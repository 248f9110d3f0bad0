"""The plain references agree with the program at small sizes on the
CPU (the benchmark itself compares them with what the timed path
produced on the chip)."""
from __future__ import annotations

import numpy as np
import pytest

from bench.reference import digest as ref_digest
from bench.reference import gear as ref_gear
from repro.core import chunking
from repro.core.sai import _cpu_gear, block_digest_cpu


@pytest.mark.parametrize("seed", range(6))
def test_gear_boundaries_match_the_program(seed):
    rng = np.random.default_rng(seed)
    data = rng.bytes(int(rng.integers(20000, 300000)))
    avg, lo, hi = 1024, 256, 4096              # forced max cuts happen too
    want = chunking.select_boundaries(
        _cpu_gear(data), len(data), window=1, stride=1, avg_chunk=avg,
        min_chunk=lo, max_chunk=hi)
    assert ref_gear.boundaries(data, avg, lo, hi) == want
    assert np.array_equal(ref_gear.hashes(data), _cpu_gear(data))


def test_gear_boundaries_follow_content_not_position():
    rng = np.random.default_rng(9)
    data = rng.bytes(200000)
    shifted = b"xyz" + data
    a = ref_gear.boundaries(data, 1024, 256, 4096)
    b = ref_gear.boundaries(shifted, 1024, 256, 4096)
    assert set(e + 3 for e in a[3:-1]) & set(b)


@pytest.mark.parametrize("n", [0, 1, 3, 4, 5, 1000, 4099])
def test_block_digest_matches_the_program(n):
    data = np.random.default_rng(n).bytes(n)
    assert ref_digest.block_digest(data) == block_digest_cpu(data)
    assert ref_digest.digests(data + data, [n, 2 * n]) == \
        [block_digest_cpu(data)] * 2
