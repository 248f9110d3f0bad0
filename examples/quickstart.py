"""Quickstart: the paper's technique in one page.

Accelerator-offloaded hashing (HashTPU kernels via the CrystalTPU
runtime) feeding a content-addressable store: write two versions of a
file, watch CDC dedup the unchanged bytes, survive a node failure, and
catch a corruption.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro import compile_cache
from repro.core import CrystalTPU, SAI, SAIConfig, make_store
from repro.kernels import ops

compile_cache.enable()
rng = np.random.default_rng(0)

# 1) hashing primitives (Pallas kernels: Mosaic on a TPU, the interpreter
#    elsewhere)
data = rng.integers(0, 256, 64 << 10, dtype=np.uint8).tobytes()
digests, final = ops.hash_blocks(data, block_bytes=4096)
print(f"direct hashing: {len(digests)} block digests, "
      f"file digest {final.hex()[:16]}…")

window_hashes = ops.sliding_window_hash(data[:8192], window=48, stride=4)
print(f"sliding-window MD5: {len(window_hashes)} window hashes")

gear = ops.gear_hash(data[:8192])
print(f"gear rolling hash: {len(gear)} positions "
      f"(beyond-paper CDC primitive)")

# 2) the integrated system: CrystalTPU + content-addressable store
manager, nodes = make_store(n_nodes=4, replication=2)
crystal = CrystalTPU()                       # queues + manager threads
sai = SAI(manager, SAIConfig(ca="cdc-gear", avg_chunk=8 << 10,
                             min_chunk=2 << 10, max_chunk=32 << 10,
                             hasher="tpu"), crystal)

v1 = rng.integers(0, 256, 256 << 10, dtype=np.uint8).tobytes()
st = sai.write("/demo/file", v1)
print(f"v1 write: {st.new_blocks} new blocks, {st.new_bytes/1e3:.0f} KB")

v2 = v1[:100_000] + b"a small edit" + v1[100_000:]
st = sai.write("/demo/file", v2)
print(f"v2 write after a 12-byte insert: similarity "
      f"{100*st.similarity:.0f}% — only {st.new_bytes/1e3:.1f} KB stored")

# 3) fault tolerance + integrity
manager.handle_node_failure(0)
assert sai.read("/demo/file") == v2          # replicas serve the read
assert sai.read("/demo/file", version=0) == v1
print("read-after-node-failure OK; both versions intact")

digest = next(iter(manager.block_registry))
for nid in manager.block_registry[digest]:
    if not manager.nodes[nid].failed:
        blk = manager.nodes[nid].blocks[digest]
        manager.nodes[nid].blocks[digest] = bytes([blk[0] ^ 1]) + blk[1:]
try:
    sai.read("/demo/file") and sai.read("/demo/file", version=0)
    print("corruption NOT detected (bug!)")
except IOError as e:
    print(f"corruption detected by content-hash verify: {e}")

crystal.shutdown()
