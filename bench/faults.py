"""Faults planted under the timed path, to show that the comparison
deciding ``correct`` catches them.  Never applied in a measured run:
``bench/run.py`` takes ``--fault`` only for the control runs and the
tests under ``bench/tests/``.

* ``replicas2`` — the control: the store acknowledges writes with 2
  replicas where the configuration states 3 (the guarantee a cheaper
  store would tempt a change to weaken).
* ``digest`` — an answer altered where it is produced: the engine's
  first digest of every direct launch has one bit flipped.
* ``half_batch`` — half of each direct launch left out: its second
  half of rows is hashed as empty.
* ``fingerprint`` — gear fingerprints altered where they are produced:
  at every sixteenth of an object one hash reads as a boundary.
* ``unchanged`` — a step that leaves the state unchanged: storage
  nodes acknowledge puts without storing the block.
* ``stored_bytes`` — an answer altered where it is stored: every
  seventh block a node stores has its first byte flipped.
"""
from __future__ import annotations

from typing import Callable, Dict


def _replicas2(config: Dict) -> None:
    config["store"]["replication"] = int(config["store"]["replication"]) - 1


def _digest(config: Dict) -> None:
    from repro.kernels import ops
    real = ops.digest_bytes

    def flipped(dig):
        out = real(dig).copy()
        out[0, 0] ^= 1
        return out
    ops.digest_bytes = flipped


def _half_batch(config: Dict) -> None:
    from repro.kernels import ops
    real = ops.direct_hash_device

    def half(words, lens_w):
        n = lens_w.shape[0]
        return real(words, lens_w.at[n - n // 2:].set(0))
    ops.direct_hash_device = half


def _fingerprint(config: Dict) -> None:
    from repro.kernels import ops
    real = ops.gear_finish

    def altered(out, n):
        h = real(out, n).copy()
        step = max(h.size // 16, 1)
        h[step::step] = 0
        return h
    ops.gear_finish = altered


def _unchanged(config: Dict) -> None:
    from repro.core.castore import StorageNode

    def put(self, digest, data):
        self.put_count += 1
    StorageNode.put = put


def _stored_bytes(config: Dict) -> None:
    from repro.core.castore import StorageNode
    real = StorageNode.put

    def altered(self, digest, data):
        if data and self.put_count % 7 == 6:
            data = bytes([data[0] ^ 1]) + data[1:]
        real(self, digest, data)
    StorageNode.put = altered


FAULTS: Dict[str, Callable[[Dict], None]] = {
    "replicas2": _replicas2,
    "digest": _digest,
    "half_batch": _half_batch,
    "fingerprint": _fingerprint,
    "unchanged": _unchanged,
    "stored_bytes": _stored_bytes,
}


def apply(name: str, config: Dict) -> None:
    """Plant fault ``name`` (``config`` is the run's own copy of the
    cell's configuration)."""
    FAULTS[name](config)
