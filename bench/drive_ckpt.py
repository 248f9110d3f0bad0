"""Closed-loop checkpoints of a sharded train state through
``CACheckpointer.save`` (mixes with entry ``ckpt``).

The state (the mix's ``content`` kind, ``bench/content/<k>.py``) is made
from the seed on the run's devices, one rank per device, and stays
there.  One saver keeps one save in flight: it takes a training step
(the trained experts rewritten on the device, every frozen leaf left as
it is), saves the whole state under a prefix of its own, waits for the
acknowledgement, and then retires the save that falls out of the last
``keep``: its files are deleted, the replicas of the blocks this orphans
are read, and the orphans are collected.  The first ``setup_saves``
saves (the whole base, whose launches and pulls compile every shape the
window uses) are set-up.  The saver stops issuing at the window's close
and drains.

Every save is checked against the plain reference
(``bench/reference/<r>.py``), which makes each (leaf, rank) file again
from the seed: the digest of every block of every save, the blocks each
save stored as new, the nodes each block is on, the bytes of every
replica (CRC-32 of each distinct replica object, read when its block is
orphaned or after the window), the bytes the nodes hold after the last
collection, failed saves, and the last save restored onto the devices by
``CACheckpointer.restore``, block by block.

The notes give the process's resident memory at each step of the run
(the first taken as set-up starts, with the devices up and nothing of
the program built), its peak, and how much the resident memory grew
inside the program's pulls of new blocks from the devices.
"""
from __future__ import annotations

import concurrent.futures as cf
import os
import resource
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench import spec
from bench.drive_sai import REPLICA_WORKERS, fingerprints
from bench.reference.digest import block_digest

# configuration keys this entry honours: what it builds the system and
# the state from, and statements the reference and the checks hold it to
MODEL_KEYS = {
    "model", "attention_bias", "first_k_dense_replace", "hidden_act",
    "hidden_size", "intermediate_size", "kv_lora_rank",
    "max_position_embeddings", "model_type", "moe_intermediate_size",
    "moe_layer_freq", "n_group", "n_routed_experts", "n_shared_experts",
    "norm_topk_prob", "num_attention_heads", "num_experts_per_tok",
    "num_hidden_layers", "num_key_value_heads", "q_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps", "rope_scaling",
    "rope_theta", "routed_scaling_factor", "scoring_func", "seq_aux",
    "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim",
    "vocab_size", "train_state", "published"}
CONFIG_KEYS = {"name", "source", "deployment", "store", "sai", "reference",
               "digest", "guarantees", "reduced", "assumed"} | MODEL_KEYS
STORE_KEYS = {"nodes", "replication", "durable"}


RESTORE_CHECK_WORKERS = 2   # restored shards pulled and hashed at once


def rss_bytes() -> int:
    """This process's resident memory now."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    return 0


def peak_rss_bytes() -> int:
    """This process's peak resident memory so far."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


class PullProbe:
    """Resident memory around the engine's pulls of claimed blocks
    (``CrystalTPU.pull_blocks``, which the SAI looks up on its engine at
    each call): the calls, and the growth of resident memory summed over
    them.  It reads memory only; the pull itself is the program's."""

    def __init__(self, engine):
        self.engine = engine
        self.inner = engine.pull_blocks
        self.calls = 0
        self.grown = 0
        engine.pull_blocks = self

    def __call__(self, *args, **kwargs):
        before = rss_bytes()
        try:
            return self.inner(*args, **kwargs)
        finally:
            self.calls += 1
            self.grown += rss_bytes() - before

    def close(self):
        del self.engine.pull_blocks


def check_config(config: Dict) -> None:
    """Refuse a configuration this entry would not run as stated."""
    extra = (set(config) - CONFIG_KEYS) | (set(config["store"])
                                           - STORE_KEYS)
    if extra:
        raise ValueError(f"configuration {config['name']!r}: keys "
                         f"{sorted(extra)} are not honoured")
    if config["store"].get("durable", False):
        raise ValueError(f"configuration {config['name']!r} states a "
                         f"durable store; this entry builds in-memory nodes")
    if config["sai"]["ca"] != "fixed":
        raise ValueError("this entry saves fixed blocks")


@dataclass
class FileRecord:
    leaf: object                # the content module's Leaf
    rank: int
    path: str
    version: object = None      # the committed FileVersion


@dataclass
class SaveRecord:
    k: int
    prefix: str
    in_window: bool
    t_ack: float = 0.0
    error: Optional[str] = None
    rec: Optional[dict] = None
    files: List[FileRecord] = field(default_factory=list)
    extra_files: List[str] = field(default_factory=list)  # its others
    replicas: list = field(default_factory=list)  # [(where, Future)]
    retired: bool = False


@dataclass
class CkptWindow:
    t0: float
    t_close: float
    t_end: float = 0.0
    saves: List[SaveRecord] = field(default_factory=list)


class Run:
    def __init__(self, cell, system_config: Dict, seed: int,
                 seconds: float, devices, annotate):
        check_config(cell.config)
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.devices = devices
        self.annotate = annotate
        self.traffic = cell.traffic
        self.config = system_config
        self.replication = int(cell.config["store"]["replication"])
        self.keep = int(self.traffic["keep"])
        self.ref = spec.module("reference", cell.config["reference"])
        self.content = spec.module("content",
                                   self.traffic["content"]["kind"])
        self.saves: List[SaveRecord] = []
        self.state = None
        self.restore_error: Optional[str] = None
        self.memory: List[tuple] = []     # (where, resident, peak)
        self.pulls: Optional[PullProbe] = None
        self.store_peak_bytes = 0
        self.replica_pool = cf.ThreadPoolExecutor(
            REPLICA_WORKERS, thread_name_prefix="bench-replicas")

    # -- set-up --------------------------------------------------------
    def setup(self):
        self._mark("devices up")
        # the first call into the program: device-resident block packing,
        # which a program that only hashes host bytes lacks, so such a
        # program fails here before any state is made
        from repro.kernels.ops import pack_blocks_device  # noqa: F401
        from repro.core import SAI, SAIConfig, CrystalTPU, make_store
        from repro.train.checkpoint import CACheckpointer
        store = self.config["store"]
        self.engine = CrystalTPU(devices=self.devices)
        self.pulls = PullProbe(self.engine)
        self.mgr, self.nodes = make_store(
            n_nodes=int(store["nodes"]),
            replication=int(store["replication"]))
        self.sai = SAI(self.mgr, SAIConfig(**self.config["sai"]),
                       self.engine)
        if not self.sai.takes_device_data:
            raise RuntimeError("the SAI does not hash device data")
        self.checkpointer = CACheckpointer
        per_rank = sum(leaf.nbytes
                       for leaf in self.content.layout(self.cell.config))
        if per_rank != int(self.traffic["object_bytes"]):
            raise ValueError(f"the mix states {self.traffic['object_bytes']}"
                             f" bytes per rank; the layout holds {per_rank}")
        self._mark("engine and store up")
        self.state = self.content.Source(self.cell.config, self.seed,
                                         self.devices)
        self._mark("state made")
        for _ in range(int(self.traffic["setup_saves"])):
            self._save_next(in_window=False)
            self._mark(f"set-up save {len(self.saves) - 1}")
        failed = [s.error for s in self.saves if s.error]
        if failed:
            raise RuntimeError(f"a set-up save failed: {failed[0]}")
        self._warm_manifest_shapes()

    def _warm_manifest_shapes(self):
        """Compile the host launches a later manifest can make: its
        rows, as long as the last set-up save's or twice that, in as
        many rows or twice that (a manifest grows by a digit or so)."""
        import jax
        import jax.numpy as jnp
        from repro.kernels import ops
        fv = self.mgr.get_blockmap(f"{self.saves[-1].prefix}/MANIFEST")
        rows = 1 << (len(fv.blocks) - 1).bit_length()
        width = ops.row_bytes(max(b.length for b in fv.blocks))
        top = ops.row_bytes(int(self.config["sai"]["block_size"]))
        for dev in self.engine.devices:
            for b in (rows, 2 * rows):
                for w in {width, min(2 * width, top)}:
                    words = jnp.zeros((b, w // 4), jnp.uint32, device=dev)
                    lens = jnp.full((b,), w // 4, jnp.int32, device=dev)
                    jax.block_until_ready(
                        ops.direct_hash_device(words, lens))

    # -- the window ----------------------------------------------------
    def window(self) -> CkptWindow:
        t0 = time.perf_counter()
        win = CkptWindow(t0=t0, t_close=t0 + self.seconds)
        self.engine_before = self.engine.snapshot_stats()
        while time.perf_counter() < win.t_close:
            self._save_next(in_window=True)
        self.engine_after = self.engine.snapshot_stats()
        self._mark("window")
        win.saves = [s for s in self.saves if s.in_window]
        acked = [s.t_ack for s in win.saves if s.error is None]
        win.t_end = max(acked) if acked else time.perf_counter()
        return win

    def _save_next(self, in_window: bool):
        k = len(self.saves)
        if k:
            with self.annotate("bench/step"):
                self.state.step()
        rec = SaveRecord(k, f"/{self.cell.name}/save{k}", in_window)
        params, opt = self.state.tree()
        ckpt = self.checkpointer(self.sai, prefix=rec.prefix)
        with self.annotate("bench/save"):
            try:
                rec.rec = ckpt.save(k, params, opt)
            except Exception as e:              # counted as failed
                rec.error = repr(e)
        rec.t_ack = time.perf_counter()
        if rec.error is None:
            self._record_files(rec)
        self.saves.append(rec)
        with self.annotate("bench/retire"):
            self._retire(k - self.keep)
        self.store_peak_bytes = max(self.store_peak_bytes,
                                    self._store_bytes())

    def _mark(self, where: str):
        self.memory.append((where, rss_bytes(), peak_rss_bytes()))

    def _record_files(self, rec: SaveRecord):
        """Each (leaf, rank) file of a save with its committed version,
        found from the save's manifest: a shard's rank is where its
        index starts on the leaf's first axis.  Files of no state leaf
        (and the manifest) are ``extra_files``."""
        leaves = {leaf.key: leaf for leaf in self.state.leaves}
        for entry in rec.rec["manifest"]["leaves"]:
            leaf = leaves.get(entry["key"])
            for i, sh in enumerate(entry.get("shards", ())):
                path = f"{rec.prefix}/{entry['key']}#{i}"
                if leaf is None:
                    rec.extra_files.append(path)
                    continue
                rank = sh["index"][0][0] // leaf.shape[0]
                rec.files.append(FileRecord(
                    leaf, rank, path,
                    self.mgr.get_blockmap(path, sh["version"])))
            if "shards" not in entry:
                rec.extra_files.append(f"{rec.prefix}/{entry['key']}")
        rec.extra_files.append(f"{rec.prefix}/MANIFEST")

    def _retire(self, k: int):
        """Delete save k, read the replicas of the blocks that orphans,
        then collect them."""
        if k < 0 or self.saves[k].error is not None:
            return
        old = self.saves[k]
        old.retired = True
        orphans = set()
        for path in [f.path for f in old.files] + old.extra_files:
            orphans.update(self.mgr.delete_file(path))
        where, rows, seen = [], [], set()
        for fi, f in enumerate(old.files):
            for j, b in enumerate(f.version.blocks):
                if b.digest in orphans and b.digest not in seen:
                    seen.add(b.digest)
                    where.append((fi, j))
                    rows.append(self._replicas(b))
        old.replicas.append((where, self.replica_pool.submit(fingerprints,
                                                             rows)))
        if orphans:
            self.mgr.gc_collect(list(orphans))

    def _replicas(self, b) -> list:
        row = []
        for nid in b.nodes:
            try:
                row.append(self.nodes[nid].get(b.digest))
            except KeyError:
                row.append(None)
        return row

    def _store_bytes(self) -> int:
        """Bytes of the distinct blocks the nodes hold."""
        held: Dict[bytes, int] = {}
        for node in self.nodes:
            for d, v in list(node.blocks.items()):
                held[d] = len(v)
        return sum(held.values())

    # -- what the metrics read -----------------------------------------
    def context(self, win: CkptWindow) -> Dict:
        ok = [s for s in win.saves if s.error is None]
        return {
            "ingest": {
                "t0": win.t0, "t_end": win.t_end,
                "user_bytes": sum(s.rec["total_bytes"] for s in ok),
                "blocks": sum(len(f.version.blocks) for s in ok
                              for f in s.files),
                "write_stats": [st for s in ok
                                for _, st in s.rec["files"]],
            },
            "engine_before": self.engine_before,
            "engine_after": self.engine_after,
        }

    def counts(self, win: CkptWindow):
        return len(win.saves), sum(s.error is not None for s in win.saves)

    def notes(self, win: CkptWindow) -> List[str]:
        ok = [s for s in win.saves if s.error is None]
        user = sum(s.rec["total_bytes"] for s in ok)
        new = sum(s.rec["new_bytes"] for s in ok)
        stats = [st for s in ok for _, st in s.rec["files"]]
        d2h = sum(getattr(st, "d2h_bytes", 0) for st in stats)
        h2d = [sum(row.get("h2d_bytes", 0) for row in snap["per_device"]
                   .values())
               for snap in (self.engine_before, self.engine_after)]
        rss = peak_rss_bytes()
        blocks = sum(len(f.version.blocks) for s in ok for f in s.files)
        return [f"saves in the window: {len(win.saves)} issued, {len(ok)} "
                f"acknowledged, {user} state bytes in "
                f"{win.t_end - win.t0:.3f} s; new bytes {new} "
                f"({new / max(user, 1):.4f} of the state)",
                f"bytes from the devices: {d2h} = {new} new + 16 x "
                f"{blocks} digests ({d2h / max(user, 1):.6f} per state "
                f"byte); to them {h2d[1] - h2d[0]} "
                f"({(h2d[1] - h2d[0]) / max(user, 1):.6f} per state byte)",
                f"host memory: peak RSS {rss} bytes; store holds "
                f"{self._store_bytes()} unique bytes after the window, "
                f"{self.store_peak_bytes} at most after a save",
                f"restore of the last save: {self.restore_error or 'ok'}",
                "host memory (resident, peak) at: " + "; ".join(
                    f"{w} {r} {p}" for w, r, p in self.memory),
                f"resident memory grown inside {self.pulls.calls} pulls "
                f"of new blocks: {self.pulls.grown} bytes"]

    def free_device(self):
        """Drop the live state (the restore check puts the last save
        back on the devices); the engine stays up for it."""
        if self.state is not None:
            self.state.free()

    # -- the comparison that decides `correct` ---------------------------
    def check(self) -> List[tuple]:
        acked = [s for s in self.saves if s.error is None]
        failed = sum(s.error is not None for s in self.saves)
        ranks = len(self.devices)
        ref = self.ref.TrainState(self.cell.config, self.seed, ranks)
        files = ref.files()
        workers = max(2, min(32, os.cpu_count() or 2))
        want: Dict[tuple, list] = {}
        with cf.ThreadPoolExecutor(workers) as ex:
            for group in (acked[:1], acked[1:]):   # frozen blocks once
                keys = [(s.k, leaf.uid, r) for s in group
                        for leaf, r in files]
                got = ex.map(lambda t: ref.blocks(t[1], t[2], t[0]),
                             [(s.k, leaf, r) for s in group
                              for leaf, r in files])
                want.update(zip(keys, got))
        self._mark("reference")
        self._read_live_replicas(acked)
        digests_off = short = replica_off = 0
        for s in acked:
            found = {(f.leaf.uid, f.rank): f for f in s.files}
            for leaf, r in files:
                ref_blocks = want[(s.k, leaf.uid, r)]
                f = found.get((leaf.uid, r))
                blocks = f.version.blocks if f is not None else []
                if [b.length for b in blocks] != [n for _, n, _ in
                                                   ref_blocks]:
                    digests_off += max(len(blocks), len(ref_blocks))
                else:
                    digests_off += sum(b.digest != d for b, (d, _, _)
                                       in zip(blocks, ref_blocks))
            for f in s.files:
                short += sum(len(set(b.nodes)) < self.replication
                             for b in f.version.blocks)
            for where, fut in s.replicas:
                for (fi, j), fps in zip(where, fut.result()):
                    f = s.files[fi]
                    _, n, crc = want[(s.k, f.leaf.uid, f.rank)][j]
                    replica_off += sum(fp != (n, crc) for fp in fps)
        self.mgr.gc_collect()
        stored = sum(n.used_bytes() for n in self.nodes)
        self._mark("before the restore")
        restore_off = self._restore_off(acked, want, files)
        self._mark("restore checked")
        return [
            ("failed_saves", failed, 0),
            ("digests_off", digests_off, 0),
            ("new_blocks_off", self._new_off(acked, want, files), 0),
            ("blocks_on_too_few_nodes", short, 0),
            ("replicas_with_wrong_bytes", replica_off, 0),
            ("stored_bytes_off", abs(stored - self.replication
                                     * self._live_bytes(acked, want,
                                                        files)), 0),
            ("restore_bytes_off", restore_off, 0),
        ]

    def _read_live_replicas(self, acked):
        """Replicas of the blocks still live, each distinct block once
        (under the newest save that holds it)."""
        seen = set()
        for s in reversed([s for s in acked if not s.retired]):
            where, rows = [], []
            for fi, f in enumerate(s.files):
                for j, b in enumerate(f.version.blocks):
                    if b.digest not in seen:
                        seen.add(b.digest)
                        where.append((fi, j))
                        rows.append(self._replicas(b))
            s.replicas.append((where, self.replica_pool.submit(
                fingerprints, rows)))

    def _new_off(self, acked, want, files) -> int:
        """Sum over saves of |blocks the store took as new - distinct
        blocks the reference finds new|: a block is new when its digest
        is in none of the saves live while it was written (the ``keep``
        saves before it)."""
        digests = {s.k: {want[(s.k, leaf.uid, r)][j][0]
                         for leaf, r in files
                         for j in range(len(want[(s.k, leaf.uid, r)]))}
                   for s in acked}
        off = 0
        for s in acked:
            seen = set()
            for j in range(1, self.keep + 1):
                seen |= digests.get(s.k - j, set())
            got = sum(st.new_blocks for path, st in s.rec["files"]
                      if path not in s.extra_files)
            off += abs(got - len(digests[s.k] - seen))
        return off

    def _live_bytes(self, acked, want, files) -> int:
        """Distinct bytes of the saves still live: the reference's state
        blocks and the program's other files (manifests)."""
        live: Dict[bytes, int] = {}
        for s in acked:
            if s.retired:
                continue
            for leaf, r in files:
                live.update((d, n) for d, n, _ in want[(s.k, leaf.uid, r)])
            for path in s.extra_files:
                fv = self.mgr.get_blockmap(path)
                if fv is not None:
                    live.update((b.digest, b.length) for b in fv.blocks)
        return sum(live.values())

    def _restore_off(self, acked, want, files) -> int:
        """Blocks of the last save that ``CACheckpointer.restore`` does
        not give back as the reference makes them, each shard on the
        device of its rank (every block of a shard counts when it is
        missing or elsewhere, or when the restore fails)."""
        if not acked:
            return 0
        last = acked[-1]
        total = sum(len(want[(last.k, leaf.uid, r)]) for leaf, r in files)
        try:
            _, tree, _ = self.checkpointer(self.sai,
                                           prefix=last.prefix).restore()
        except Exception as e:
            self.restore_error = repr(e)
            return total
        self._mark("restored")

        def leaf_of(key):
            cur = tree
            for p in key.split("/"):
                cur = cur.get(p) if isinstance(cur, dict) else None
            return cur

        def off(item):
            leaf, r = item
            ref_blocks = want[(last.k, leaf.uid, r)]
            arr = leaf_of(leaf.key)
            shard = next((sh for sh in getattr(arr, "addressable_shards",
                                               ())
                          if (sh.index[0].start or 0)
                          == r * leaf.shape[0]
                          and sh.device == self.devices[r]), None)
            if shard is None or tuple(shard.data.shape) != leaf.shape:
                return len(ref_blocks)
            data = memoryview(np.asarray(shard.data).reshape(-1)
                              .view(np.uint8))
            shard.data.delete()           # and the host copy JAX keeps
            bs = int(self.cell.config["sai"]["block_size"])
            return sum(block_digest(data[lo:lo + bs]) != d
                       for lo, (d, _, _) in zip(range(0, len(data), bs),
                                                ref_blocks)) \
                + abs(-(-len(data) // bs) - len(ref_blocks))

        with cf.ThreadPoolExecutor(RESTORE_CHECK_WORKERS) as ex:
            return sum(ex.map(off, files))

    def close(self):
        if self.pulls is not None:
            self.pulls.close()
        self.replica_pool.shutdown(wait=True, cancel_futures=True)
        if hasattr(self, "sai"):                # as far as set-up got
            self.sai.close()
        if hasattr(self, "engine"):
            self.engine.shutdown()
