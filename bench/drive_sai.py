"""Closed-loop ingest through ``SAI.write_async`` (mixes with entry
``sai``).

Each writer stream keeps one write in flight: it submits object k, makes
object k+1 while k is in flight, waits for k's acknowledgement, retires
the object that falls out of its live set (``keep`` objects) and
collects the orphans, then submits k+1.  Streams stop issuing at the
window's close and drain, so every write issued in the window is
acknowledged (or failed) and counted.

Every write is checked against the plain references: its chunk
boundaries, the digest of each block, the nodes each block is on, the
bytes of each block on each of its replicas, and the blocks it stored as
new against the reference's count of blocks not yet stored.  A replica's
bytes are read when the write is retired, before its orphans are
collected, or after the window for what is still live; a CRC-32 and the
length of each replica object are kept (taken by worker threads, off the
stream's path) and compared after the window with those of the bytes
the block stands for.
"""
from __future__ import annotations

import concurrent.futures as cf
import os
import threading
import time
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from bench import generator, spec, warmup

# configuration keys this entry honours: what it builds the system from,
# and statements the reference module and the checks hold it to.  Any
# other key, or a store it cannot build as stated, fails the run.
CONFIG_KEYS = {"name", "source", "deployment", "store", "sai", "reference",
               "digest", "fingerprint", "boundary_rule", "guarantees",
               "reduced", "assumed"}
STORE_KEYS = {"nodes", "replication", "durable"}
REPLICA_WORKERS = 2


def check_config(config: Dict) -> None:
    """Refuse a configuration this entry would not run as stated."""
    extra = set(config) - CONFIG_KEYS
    store_extra = set(config["store"]) - STORE_KEYS
    if extra or store_extra:
        raise ValueError(f"configuration {config['name']!r}: keys "
                         f"{sorted(extra | store_extra)} are not honoured")
    if config["store"].get("durable", False):
        raise ValueError(f"configuration {config['name']!r} states a "
                         f"durable store; this entry builds in-memory nodes")


@dataclass
class WriteRecord:
    stream: int
    k: int
    path: str
    nbytes: int
    in_window: bool
    t_ack: float = 0.0
    stats: object = None
    version: object = None          # the committed FileVersion
    data: Optional[bytes] = None    # kept where it cannot be made again
    error: Optional[str] = None
    replicas: object = None         # Future of the replica fingerprints
    retired: bool = False


@dataclass
class IngestWindow:
    t0: float
    t_close: float
    t_end: float = 0.0
    records: List[WriteRecord] = field(default_factory=list)


class Run:
    def __init__(self, cell, system_config: Dict, seed: int,
                 seconds: float, devices, annotate):
        check_config(cell.config)
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.devices = devices
        self.annotate = annotate          # name -> context manager
        self.traffic = cell.traffic
        self.config = system_config      # what the system is built from
        self.replication = int(cell.config["store"]["replication"])
        self.ref = spec.module("reference", cell.config["reference"])
        self.records: List[WriteRecord] = []
        self.by_key: Dict[tuple, WriteRecord] = {}
        self._lock = threading.Lock()
        self.replica_pool = cf.ThreadPoolExecutor(
            REPLICA_WORKERS, thread_name_prefix="bench-replicas")

    # -- set-up --------------------------------------------------------
    def setup(self):
        from repro.core import SAI, SAIConfig, CrystalTPU, make_store
        store = self.config["store"]
        self.engine = CrystalTPU(devices=self.devices)
        self.mgr, self.nodes = make_store(
            n_nodes=int(store["nodes"]),
            replication=int(store["replication"]))
        self.sai = SAI(self.mgr, SAIConfig(**self.config["sai"]),
                       self.engine)
        self.source = generator.stream_source(self.traffic, self.seed)
        lo, hi = self.ref.block_bytes(self.cell.config["sai"],
                                      self.traffic["object_bytes"])
        warmup.direct_shapes(self.engine, warmup.row_widths(lo, hi))
        # the cell's own traffic: each stream's first objects, so the
        # engine's cost model and fusion caps relearn before the window
        self.pending = {s: (0, self.source.obj(s, 0))
                        for s in range(int(self.traffic["streams"]))}
        self._run_streams(deadline=None,
                          per_stream=int(self.traffic["setup_objects"]),
                          in_window=False)

    # -- the window ----------------------------------------------------
    def window(self) -> IngestWindow:
        t0 = time.perf_counter()
        win = IngestWindow(t0=t0, t_close=t0 + self.seconds)
        self.engine_before = self.engine.snapshot_stats()
        self._run_streams(deadline=win.t_close, per_stream=None,
                          in_window=True)
        self.engine_after = self.engine.snapshot_stats()
        win.records = [r for r in self.records if r.in_window]
        acked = [r.t_ack for r in win.records if r.error is None]
        win.t_end = max(acked) if acked else time.perf_counter()
        return win

    def _run_streams(self, deadline, per_stream, in_window):
        errors: List[BaseException] = []
        threads = [threading.Thread(
            target=self._stream_main,
            args=(s, deadline, per_stream, in_window, errors),
            name=f"bench-stream-{s}", daemon=True)
            for s in range(int(self.traffic["streams"]))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

    def _stream_main(self, s, deadline, per_stream, in_window, errors):
        try:
            k, data = self.pending[s]
            done = 0
            while not ((deadline is not None
                        and time.perf_counter() >= deadline)
                       or (per_stream is not None and done >= per_stream)):
                path = generator.stream_paths(self.cell.name, self.traffic,
                                              s, k)
                rec = WriteRecord(s, k, path, len(data), in_window)
                if self.source.keeps_bytes:
                    rec.data = data
                with self.annotate("bench/write_async"):
                    fut = self.sai.write_async(path, data)
                nxt = self.source.obj(s, k + 1)
                with self.annotate("bench/wait_ack"):
                    try:
                        rec.stats = fut.result()
                    except Exception as e:      # counted as failed
                        rec.error = repr(e)
                rec.t_ack = time.perf_counter()
                if rec.error is None:
                    rec.version = self.mgr.get_read_plan(path)[0]
                with self._lock:
                    self.records.append(rec)
                    self.by_key[(s, k)] = rec
                with self.annotate("bench/retire"):
                    self._retire(s, k)
                k, data = k + 1, nxt
                done += 1
            self.pending[s] = (k, data)
        except BaseException as e:              # surfaced by the caller
            errors.append(e)

    def _retire(self, s: int, k: int):
        """After object k of stream s is acknowledged, retire object
        k - keep: take its replicas' fingerprints, then delete it (or its
        version) and collect the orphans."""
        keep = int(self.traffic["keep"])
        old = self.by_key.get((s, k - keep))
        if old is None:
            return
        old.retired = True
        if old.version is not None:
            old.replicas = self.replica_pool.submit(
                fingerprints, self._replicas(old.version.blocks))
        if self.traffic["naming"] == "new_path":
            orphans = self.mgr.delete_file(old.path)
        else:
            orphans = self.mgr.retire_versions(old.path, keep_latest=keep)
        if orphans:
            self.mgr.gc_collect(orphans)

    def _replicas(self, blocks) -> List[list]:
        """The object each node holds for each block, None where a node
        does not hold it."""
        out = []
        for b in blocks:
            row = []
            for nid in b.nodes:
                try:
                    row.append(self.nodes[nid].get(b.digest))
                except KeyError:
                    row.append(None)
            out.append(row)
        return out

    # -- what the metrics read -----------------------------------------
    def context(self, win: IngestWindow) -> Dict:
        ok = [r for r in win.records if r.error is None]
        return {
            "ingest": {
                "t0": win.t0, "t_end": win.t_end,
                "user_bytes": sum(r.nbytes for r in ok),
                "blocks": sum(len(r.version.blocks) for r in ok),
                "write_stats": [r.stats for r in ok],
            },
            "engine_before": self.engine_before,
            "engine_after": self.engine_after,
        }

    def counts(self, win: IngestWindow):
        attempted = len(win.records)
        failed = sum(r.error is not None for r in win.records)
        return attempted, failed

    def notes(self, win: IngestWindow) -> List[str]:
        ok = [r for r in win.records if r.error is None]
        sims = [r.stats.similarity for r in ok]
        acked = [r for r in self.records if r.error is None]
        retired = sum(r.retired for r in acked)
        return [f"writes in the window: {len(win.records)} issued, "
                f"{len(ok)} acknowledged, "
                f"{sum(r.nbytes for r in ok)} bytes in "
                f"{win.t_end - win.t0:.3f} s; mean similarity "
                f"{np.mean(sims) if sims else 0.0:.4f}",
                f"replicas read: of {retired} writes when retired, of "
                f"{len(acked) - retired} after the window"]

    def free_device(self):
        self.engine.shutdown()

    # -- the comparison that decides `correct` ---------------------------
    def check(self) -> List[tuple]:
        """(name, value, limit) for every number compared."""
        records = [r for r in self.records if r.error is None]
        failed = sum(r.error is not None for r in self.records)
        for r in records:                     # still live: read them now
            if r.replicas is None:
                r.replicas = self.replica_pool.submit(
                    fingerprints, self._replicas(r.version.blocks))
        workers = max(2, min(32, os.cpu_count() or 2))
        with cf.ThreadPoolExecutor(workers) as ex:
            got = list(ex.map(self._check_one, records))
        totals = {key: sum(g[key] for g in got)
                  for key in ("boundary", "digest", "short", "replica")}
        live_bytes: Dict[bytes, int] = {}
        for r, g in zip(records, got):
            if not r.retired:
                live_bytes.update(g["blocks"])
        self.mgr.gc_collect()
        stored = sum(n.used_bytes() for n in self.nodes)
        return [
            ("failed_writes", failed, 0),
            ("versions_with_wrong_boundaries", totals["boundary"], 0),
            ("blocks_with_wrong_digest", totals["digest"], 0),
            ("blocks_on_too_few_nodes", totals["short"], 0),
            ("replicas_with_wrong_bytes", totals["replica"], 0),
            ("new_blocks_off", self._dedup_off(records, got), 0),
            ("stored_bytes_off", abs(stored - self.replication
                                     * sum(live_bytes.values())), 0),
        ]

    def _dedup_off(self, records, got) -> int:
        """Sum over writes of |blocks the store took as new - blocks the
        reference finds new|.  A block is new when its digest is neither
        earlier in the same write nor in the stream's live objects at the
        time (the ``keep`` objects before it; streams share no content),
        so a block stored twice, or a duplicate missed, shows here."""
        keep = int(self.traffic["keep"])
        digests = {(r.stream, r.k): set(g["digests"])
                   for r, g in zip(records, got)}
        off = 0
        for r, g in zip(records, got):
            seen = set()
            for j in range(1, keep + 1):
                seen |= digests.get((r.stream, r.k - j), set())
            new = len(set(g["digests"]) - seen)
            off += abs(r.stats.new_blocks - new)
        return off

    def _check_one(self, rec: WriteRecord) -> Dict:
        """One write against the references: its boundaries and block
        digests, its replica count, and its bytes on every replica."""
        data = rec.data if rec.data is not None \
            else self.source.obj(rec.stream, rec.k)
        ends = self.ref.chunk_ends(data, self.cell.config["sai"])
        digs = self.ref.digests(data, ends)
        blocks = rec.version.blocks
        out = {"boundary": 0, "digest": 0, "digests": digs,
               "blocks": {b.digest: b.length for b in blocks}}
        if np.cumsum([b.length for b in blocks]).tolist() != ends:
            out["boundary"] = 1
            out["digest"] = max(len(blocks), len(digs))
        else:
            out["digest"] = sum(b.digest != d for b, d in zip(blocks, digs))
        out["short"] = sum(len(set(b.nodes)) < self.replication
                           for b in blocks)
        out["replica"] = replicas_differ(data, blocks,
                                         rec.replicas.result())
        return out

    def close(self):
        self.replica_pool.shutdown(wait=True, cancel_futures=True)
        self.sai.close()


def fingerprints(replicas: List[list]) -> List[list]:
    """(length, CRC-32) of every replica object, None where one is
    missing; an object that several nodes share is read once."""
    seen: Dict[int, tuple] = {}
    out = []
    for row in replicas:
        fps = []
        for obj in row:
            if obj is not None and id(obj) not in seen:
                seen[id(obj)] = (len(obj), zlib.crc32(obj))
            fps.append(None if obj is None else seen[id(obj)])
        out.append(fps)
    return out


def replicas_differ(data: bytes, blocks, fps: List[list]) -> int:
    """Replica copies of ``blocks`` that are missing or differ from the
    bytes of ``data`` they stand for."""
    view = memoryview(data)
    bad = start = 0
    for b, row in zip(blocks, fps):
        want = view[start:start + b.length]
        want_fp = (len(want), zlib.crc32(want))
        bad += sum(fp != want_fp for fp in row)
        start += b.length
    return bad
