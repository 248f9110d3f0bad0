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
"""
from __future__ import annotations

import json
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.sai import SAI, WriteStats


def _flatten(tree) -> List[Tuple[str, np.ndarray]]:
    leaves = jax.tree.flatten_with_path(tree)[0]
    out = []
    for path, leaf in leaves:
        key = "/".join(str(getattr(p, "key", p)) for p in path)
        out.append((key, np.asarray(leaf)))
    return out


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
        # submit the whole burst before gathering: the engine fuses the
        # queued per-leaf hash requests into batched launches, and the
        # pipeline overlaps chunk/hash of leaf i+1 with store of leaf i
        futs = [(key, arr, f"{self.prefix}/{key}",
                 self.sai.write_async(f"{self.prefix}/{key}",
                                      arr.tobytes()))
                for key, arr in leaves]
        manifest = {"step": int(step), "leaves": [], "extra": extra or {}}
        totals = WriteStats()
        for key, arr, path, fut in futs:
            st = fut.result()
            manifest["leaves"].append(
                {"key": key, "shape": list(arr.shape),
                 "dtype": str(arr.dtype),
                 "version": self.sai.manager.num_versions(path) - 1})
            totals.total_bytes += st.total_bytes
            totals.new_bytes += st.new_bytes
            totals.new_blocks += st.new_blocks
            totals.dup_blocks += st.dup_blocks
        mpath = f"{self.prefix}/MANIFEST"
        self.sai.write(mpath, json.dumps(manifest).encode())
        rec = {
            "step": int(step),
            "total_bytes": totals.total_bytes,
            "new_bytes": totals.new_bytes,
            "dedup_ratio": 1.0 - totals.new_bytes
            / max(totals.total_bytes, 1),
            "wall_s": time.perf_counter() - t0,
        }
        with self._lock:
            self.history.append(rec)
        return rec

    def async_save(self, step: int, params, opt_state=None,
                   extra: Optional[dict] = None) -> threading.Thread:
        """Non-blocking save: snapshot to host, hash+store in background."""
        snap_p = jax.tree.map(np.asarray, params)
        snap_o = jax.tree.map(np.asarray, opt_state) \
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
        """Returns (step, state dict) for the requested manifest version.

        Every leaf is read through the SAI's pipelined ``read_async``:
        all reads are submitted up front, so the verify stage of leaf i
        (one fused engine hash request per leaf) overlaps the fetch of
        leaf i+1 and the per-leaf verify requests coalesce into batched
        kernel launches — the read-side mirror of ``save``'s burst."""
        raw = self.sai.read(f"{self.prefix}/MANIFEST", version=version)
        manifest = json.loads(raw.decode())
        futs = [(leaf, self.sai.read_async(f"{self.prefix}/{leaf['key']}",
                                           version=leaf["version"]))
                for leaf in manifest["leaves"]]
        flat: Dict[str, np.ndarray] = {}
        for leaf, fut in futs:
            arr = np.frombuffer(fut.result(),
                                dtype=leaf["dtype"]).reshape(leaf["shape"])
            flat[leaf["key"]] = arr
        return manifest["step"], _unflatten(flat), manifest["extra"]


def _unflatten(flat: Dict[str, np.ndarray]):
    root: Dict[str, Any] = {}
    for key, arr in flat.items():
        parts = key.split("/")
        cur = root
        for p in parts[:-1]:
            cur = cur.setdefault(p, {})
        cur[parts[-1]] = arr
    return root
