"""The traffic generator is a function of the seed: the same seed makes
the same objects, and each content kind keeps its shape."""
from __future__ import annotations

import pytest

from bench import generator

SEED = 2**31 + 12345                      # seeds exceed 32 bits


def _stamped(seed):
    mix = {"object_bytes": 1 << 16,
           "content": {"kind": "stamped", "stamp_every": 1 << 12}}
    return generator.stream_source(mix, seed)


def _versions(seed, size=1 << 16):
    mix = {"object_bytes": size,
           "content": {"kind": "versions", "rewrite_frac": 0.15,
                       "indel_min": 1, "indel_max": 4095}}
    return generator.stream_source(mix, seed)


def test_stamped_objects_repeat_per_seed_and_blocks_are_unique():
    a, b = _stamped(SEED), _stamped(SEED)
    assert a.obj(3, 7) == b.obj(3, 7)
    assert isinstance(a.obj(3, 7), bytes)
    assert a.obj(3, 7) != _stamped(SEED + 1).obj(3, 7)
    blocks = set()
    for s in range(2):
        for k in range(3):
            data = a.obj(s, k)
            blocks.update(data[o:o + 4096] for o in range(0, len(data), 4096))
    assert len(blocks) == 2 * 3 * 16
    assert not a.keeps_bytes


def test_versions_repeat_per_seed_and_stay_similar():
    a, b = _versions(SEED), _versions(SEED)
    va = [a.obj(0, k) for k in range(4)]
    assert va == [b.obj(0, k) for k in range(4)]
    assert va[0] != _versions(SEED + 1).obj(0, 0)
    assert all(len(v) == 1 << 16 for v in va)
    assert len(set(va)) == 4                        # no version repeats
    assert a.keeps_bytes
    with pytest.raises(ValueError):
        _versions(SEED).obj(0, 2)                   # versions come in order


def test_unknown_kind_and_naming_are_errors():
    with pytest.raises(ModuleNotFoundError):
        generator.stream_source({"object_bytes": 1,
                                 "content": {"kind": "nosuch"}}, SEED)
    with pytest.raises(ValueError):
        generator.stream_paths("c", {"naming": "nosuch"}, 0, 0)
