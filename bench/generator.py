"""The one traffic generator: every mix under ``bench/traffic/`` is data
that this module turns into objects, from a seed.

A mix's writer streams each send a sequence of objects.  What they send
is the mix's ``content``, whose ``kind`` names the module that makes it
(``bench/content/<kind>.py``); where they send it is its ``naming``: a
new path per object (``"new_path"``) or one path per stream whose
versions accumulate (``"same_path"``).
"""
from __future__ import annotations

from typing import Dict

import numpy as np


def rng(seed: int, *path: int) -> np.random.Generator:
    """An independent generator per (seed, path); seeds may exceed 32
    bits and are taken modulo 2**64."""
    return np.random.default_rng([seed % (1 << 64), *path])


def stream_source(traffic: Dict, seed: int):
    """The mix's object source: ``obj(stream, k)`` is the k-th object
    (0-based) of ``stream``, the same bytes for the same seed in every
    process; ``keeps_bytes`` says whether a run must keep what it wrote
    to check it, as objects it cannot make again cheaply."""
    from bench import spec
    return spec.module("content", traffic["content"]["kind"]).Source(
        traffic, seed)


def stream_paths(cell: str, traffic: Dict, stream: int, k: int) -> str:
    """Where stream ``stream`` writes its k-th object."""
    naming = traffic["naming"]
    if naming == "new_path":
        return f"/{cell}/s{stream}/o{k}"
    if naming == "same_path":
        return f"/{cell}/s{stream}"
    raise ValueError(f"unknown naming {naming!r}")
