"""Figure 4: per-stage time breakdown of sliding-window hashing WITHOUT
CrystalTPU optimizations (alloc/copy-in dominates the paper's GPU runs at
80-96%; we measure the same staged pipeline on this host), plus the
engine's request-coalescing ablations: a burst of small direct-hash
requests — and a burst of same-config sliding stream jobs (CDC chunking
burst) — dispatched per-request vs fused into batched launches."""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import Row, scaled, synth_data
from repro.core import CrystalTPU

BURST = scaled(16, 8)
BURST_SEG = scaled(16 << 10, 4 << 10)
STREAM_BURST = scaled(8, 4)
STREAM_LEN = scaled(64 << 10, 8 << 10)


def stages(timings: dict) -> dict:
    """The paper's Table-1 stages from a launch's five phases, as they
    fold under ``overlap=False`` (every stage ends synchronized): copy
    in = staging + ``device_put``, kernel = the call, copy out = the
    pull of the digests + handing them to the job."""
    return {"in": timings["stage"] + timings["put"],
            "kernel": timings["call"],
            "out": timings["wait"] + timings["finish"]}


def run() -> list:
    rows: list = []
    for size in scaled((256 << 10, 1 << 20), (64 << 10,)):
        c = CrystalTPU(buffer_reuse=False, overlap=False, n_slots=2)
        try:
            data = np.frombuffer(synth_data(size), np.uint8)
            # warmup (compile)
            c.submit("sliding", data, {"window": 48, "stride": 4}).wait()
            job = c.submit("sliding", data, {"window": 48, "stride": 4})
            job.wait()
            t = stages(job.timings)
            total = sum(t.values())
            for stage, sec in t.items():
                pct = 100 * sec / total
                rows.append((f"fig4/stage_{stage}/{size>>10}KB",
                             sec * 1e6, f"{pct:.1f}%_of_total"))
        finally:
            c.shutdown()

    # coalescing ablation: same burst of BURST small direct requests,
    # per-request launches vs fused batch launches
    bufs = [np.frombuffer(synth_data(BURST_SEG, seed=i), np.uint8)
            for i in range(BURST)]
    for coalesce in (False, True):
        c = CrystalTPU(coalesce=coalesce, coalesce_window_s=0.02)
        try:
            # warm both the per-request and the fused batch shapes
            for j in c.map_stream("direct", bufs, {"seg_bytes": 4096}):
                j.wait()
            s0 = c.snapshot_stats()
            t0 = time.perf_counter()
            jobs = c.map_stream("direct", bufs, {"seg_bytes": 4096})
            for j in jobs:
                j.wait()
            t = time.perf_counter() - t0
            s1 = c.snapshot_stats()
            launches = s1["launches"] - s0["launches"]
            njobs = s1["jobs"] - s0["jobs"]
            label = "fused" if coalesce else "per_request"
            rows.append((f"fig4/coalesce_{label}", t / BURST * 1e6,
                         f"launches={launches}_jobs={njobs}"))
        finally:
            c.shutdown()

    # stream-coalescing ablation: a CDC chunking burst of same-config
    # sliding jobs, per-request launches vs one fused [B, L] launch
    sbufs = [np.frombuffer(synth_data(STREAM_LEN, seed=100 + i), np.uint8)
             for i in range(STREAM_BURST)]
    meta = {"window": 48, "stride": 4}
    for coalesce in (False, True):
        c = CrystalTPU(coalesce=coalesce, coalesce_window_s=0.02)
        try:
            for j in c.map_stream("sliding", sbufs, meta):    # warm shapes
                j.wait()
            s0 = c.snapshot_stats()
            t0 = time.perf_counter()
            jobs = c.map_stream("sliding", sbufs, meta)
            for j in jobs:
                j.wait()
            t = time.perf_counter() - t0
            s1 = c.snapshot_stats()
            launches = s1["launches"] - s0["launches"]
            njobs = s1["jobs"] - s0["jobs"]
            label = "fused" if coalesce else "per_request"
            rows.append((f"fig4/stream_coalesce_{label}",
                         t / STREAM_BURST * 1e6,
                         f"launches={launches}_jobs={njobs}"))
        finally:
            c.shutdown()
    return rows
