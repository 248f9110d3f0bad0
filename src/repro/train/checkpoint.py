"""Content-addressable checkpointing — the paper's technique as a
first-class training-framework feature.

This is exactly the paper's *checkpoint workload* (§4.3, Figure 11: 100
successive BLCR checkpoint images, 76-90% CDC similarity) turned into the
framework's checkpoint subsystem: every parameter/optimizer leaf is
serialized and written through the SAI into the content-addressable store
with accelerator-offloaded hashing.  Successive checkpoints of a slowly-
moving training state dedup against each other, so incremental checkpoint
cost is proportional to *changed* bytes, not model size; restore verifies
content hashes (integrity) and survives storage-node failures via
replication.

``save`` streams every leaf through the SAI's async write pipeline in one
burst: all leaves are submitted up front (chunk/hash of leaf i+1 overlaps
the store of leaf i) and the offload engine coalesces the per-leaf hash
requests into fused batch kernel launches — one batched hash submission
instead of N synchronous per-leaf writes.

``async_save`` additionally offloads the whole save to a background
thread (the training loop keeps stepping), mirroring the paper's
observation that offloading frees the host CPU for the application.

A train state that lives on the accelerators is saved where it lies.
When the SAI takes device data (``SAI.takes_device_data``), each
``jax.Array`` leaf is saved shard by shard, as each rank of a sharded
job saves its own part (ByteCheckpoint, arXiv:2407.20143): one file
``<prefix>/<key>#<i>`` per addressable shard that is the first replica
of its index, written as a device-resident write, so the engine hashes
it in its device's memory and only the blocks the store lacks come to
the host.  The manifest records each shard's index and device and the
leaf's sharding, and ``restore`` puts each shard back on its own device
and rebuilds the array with that sharding, each shard read and checked
block by block as ``SAI.read`` does.  Leaves on the host, any leaf when
the SAI hashes on the host, leaves whose dtype the device cannot view
as words (``SAI.takes_device_array``: 8-byte and complex types) and
leaves whose sharding is not a ``NamedSharding`` or
``SingleDeviceSharding`` take the host path: the whole leaf as one
file.  Span ``ckpt/save`` {step, leaves, bytes}.
"""
from __future__ import annotations

import concurrent.futures as cf
import json
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from jax.sharding import SingleDeviceSharding

from repro.core.sai import SAI, WriteStats
from repro.obs import span

RESTORE_WORKERS = 4          # shards read and placed at once


def _flatten(tree) -> List[Tuple[str, Any]]:
    leaves = jax.tree.flatten_with_path(tree)[0]
    return [("/".join(str(getattr(p, "key", p)) for p in path), leaf)
            for path, leaf in leaves]


def _sharding_spec(sharding) -> Optional[dict]:
    """The manifest's record of a sharding ``restore`` can rebuild, or
    None."""
    if isinstance(sharding, SingleDeviceSharding):
        return {"kind": "single",
                "device": next(iter(sharding.device_set)).id}
    if isinstance(sharding, NamedSharding):
        mesh = sharding.mesh
        return {"kind": "named", "axes": list(mesh.axis_names),
                "mesh_shape": list(mesh.devices.shape),
                "devices": [d.id for d in mesh.devices.flat],
                "spec": [list(p) if isinstance(p, tuple) else p
                         for p in sharding.spec]}
    return None


def _rebuild_sharding(spec: dict):
    by_id = {d.id: d for d in jax.devices()}
    if spec["kind"] == "single":
        return SingleDeviceSharding(by_id[spec["device"]])
    devices = np.array([by_id[i] for i in spec["devices"]],
                       dtype=object).reshape(spec["mesh_shape"])
    return NamedSharding(Mesh(devices, tuple(spec["axes"])), PartitionSpec(
        *[tuple(p) if isinstance(p, list) else p for p in spec["spec"]]))


def _index(index, shape) -> List[List[int]]:
    """A shard's index (a tuple of slices) as [start, stop] per axis."""
    return [list(sl.indices(n)[:2]) for sl, n in zip(index, shape)]


def _snapshot(leaf):
    """A copy of a leaf that later steps cannot change: on its devices
    for a ``jax.Array``, on the host otherwise."""
    return leaf.copy() if isinstance(leaf, jax.Array) else np.asarray(leaf)


class CACheckpointer:
    def __init__(self, sai: SAI, prefix: str = "ckpt"):
        self.sai = sai
        self.prefix = prefix
        self._lock = threading.Lock()
        self._pending: Optional[threading.Thread] = None
        self.history: List[dict] = []

    # ------------------------------------------------------------------
    def save(self, step: int, params, opt_state=None,
             extra: Optional[dict] = None) -> dict:
        t0 = time.perf_counter()
        state = {"params": params}
        if opt_state is not None:
            state["opt"] = opt_state
        leaves = _flatten(state)
        with span("ckpt/save", step=int(step), leaves=len(leaves)) as sp:
            rec = self._save(step, leaves, extra)
            sp.set(bytes=rec["total_bytes"])
        rec["wall_s"] = time.perf_counter() - t0
        with self._lock:
            self.history.append(rec)
        return rec

    def _save(self, step: int, leaves, extra: Optional[dict]) -> dict:
        # submit the whole burst before gathering: the engine fuses the
        # queued per-leaf hash requests into batched launches, and the
        # pipeline overlaps chunk/hash of leaf i+1 with store of leaf i
        entries, writes = [], []
        for key, leaf in leaves:
            path = f"{self.prefix}/{key}"
            sharding = _sharding_spec(leaf.sharding) if (
                self.sai.takes_device_array(leaf)
                and leaf.is_fully_addressable) else None
            if sharding is None:
                leaf = np.asarray(leaf)
            entry = {"key": key, "shape": list(leaf.shape),
                     "dtype": str(leaf.dtype)}
            entries.append(entry)
            if sharding is not None:
                entry["sharding"] = sharding
                entry["shards"] = []
                for sh in leaf.addressable_shards:
                    if sh.replica_id != 0:
                        continue
                    rec = {"index": _index(sh.index, leaf.shape),
                           "device": sh.device.id}
                    entry["shards"].append(rec)
                    spath = f"{path}#{len(entry['shards']) - 1}"
                    writes.append((rec, spath,
                                   self.sai.write_async(spath, sh.data)))
            else:
                writes.append((entry, path,
                               self.sai.write_async(path, leaf.tobytes())))
        manifest = {"step": int(step), "leaves": entries,
                    "extra": extra or {}}
        totals = WriteStats()
        files = []
        for rec, path, fut in writes:
            st = fut.result()
            rec["version"] = self.sai.manager.num_versions(path) - 1
            files.append((path, st))
            totals.total_bytes += st.total_bytes
            totals.new_bytes += st.new_bytes
            totals.new_blocks += st.new_blocks
            totals.dup_blocks += st.dup_blocks
        mpath = f"{self.prefix}/MANIFEST"
        self.sai.write(mpath, json.dumps(manifest).encode())
        return {
            "step": int(step),
            "total_bytes": totals.total_bytes,
            "new_bytes": totals.new_bytes,
            "new_blocks": totals.new_blocks,
            "dup_blocks": totals.dup_blocks,
            "dedup_ratio": 1.0 - totals.new_bytes
            / max(totals.total_bytes, 1),
            "files": files,            # (path, WriteStats) of each file
            "manifest": manifest,
        }

    def async_save(self, step: int, params, opt_state=None,
                   extra: Optional[dict] = None) -> threading.Thread:
        """Non-blocking save: snapshot (``jax.Array`` leaves copied on
        their own devices, others on the host), then hash+store in
        background."""
        snap_p = jax.tree.map(_snapshot, params)
        snap_o = jax.tree.map(_snapshot, opt_state) \
            if opt_state is not None else None
        self.wait()
        t = threading.Thread(
            target=self.save, args=(step, snap_p, snap_o, extra),
            daemon=True, name=f"ca-ckpt-{step}")
        t.start()
        self._pending = t
        return t

    def wait(self):
        if self._pending is not None:
            self._pending.join()
            self._pending = None

    # ------------------------------------------------------------------
    def restore(self, version: int = -1):
        """Returns (step, state dict, extra) for the requested manifest
        version.

        Every host leaf is read through the SAI's pipelined
        ``read_async``: all reads are submitted up front, so the verify
        stage of leaf i (one fused engine hash request per leaf)
        overlaps the fetch of leaf i+1 and the per-leaf verify requests
        coalesce into batched kernel launches — the read-side mirror of
        ``save``'s burst.  A sharded leaf comes back as a ``jax.Array``
        with its saved sharding: each shard is read and checked by
        ``SAI.read`` and put on its own device, a few at a time."""
        raw = self.sai.read(f"{self.prefix}/MANIFEST", version=version)
        manifest = json.loads(raw.decode())
        host = [leaf for leaf in manifest["leaves"] if "shards" not in leaf]
        futs = [(leaf, self.sai.read_async(f"{self.prefix}/{leaf['key']}",
                                           version=leaf["version"]))
                for leaf in host]
        flat: Dict[str, Any] = {}
        with cf.ThreadPoolExecutor(RESTORE_WORKERS) as pool:
            sharded = [(leaf, [pool.submit(self._read_shard, leaf, i, sh)
                               for i, sh in enumerate(leaf["shards"])])
                       for leaf in manifest["leaves"] if "shards" in leaf]
            for leaf, fut in futs:
                flat[leaf["key"]] = np.frombuffer(
                    fut.result(), dtype=leaf["dtype"]).reshape(leaf["shape"])
            for leaf, parts in sharded:
                flat[leaf["key"]] = _assemble(
                    leaf, [p.result() for p in parts])
        return manifest["step"], _unflatten(flat), manifest["extra"]

    def _read_shard(self, leaf: dict, i: int, shard: dict) -> jax.Array:
        device = {d.id: d for d in jax.devices()}[shard["device"]]
        shape = [b - a for a, b in shard["index"]]
        data = self.sai.read(f"{self.prefix}/{leaf['key']}#{i}",
                             version=shard["version"])
        return jax.device_put(np.frombuffer(
            data, jnp.dtype(leaf["dtype"])).reshape(shape), device)


def _assemble(leaf: dict, parts: List[jax.Array]) -> jax.Array:
    """A sharded leaf from its saved shards, each already on the device
    it was saved from; devices that held a replica of an index get a
    copy of that index's shard."""
    sharding = _rebuild_sharding(leaf["sharding"])
    shape = tuple(leaf["shape"])
    by_index = {tuple(map(tuple, sh["index"])): part
                for sh, part in zip(leaf["shards"], parts)}
    arrays = []
    for dev, index in sharding.addressable_devices_indices_map(
            shape).items():
        part = by_index[tuple(map(tuple, _index(index, shape)))]
        arrays.append(part if part.devices() == {dev}
                      else jax.device_put(part, dev))
    return jax.make_array_from_single_device_arrays(shape, sharding,
                                                    arrays)


def _unflatten(flat: Dict[str, np.ndarray]):
    root: Dict[str, Any] = {}
    for key, arr in flat.items():
        parts = key.split("/")
        cur = root
        for p in parts[:-1]:
            cur = cur.setdefault(p, {})
        cur[parts[-1]] = arr
    return root
