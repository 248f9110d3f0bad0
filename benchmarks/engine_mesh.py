"""Engine-mesh ablation (ISSUE 6): 1 vs N devices on a whale job,
static vs adaptive fusion on a small-job burst, per-device dispatch
stats.

The measurements run in the calling process over ``jax.devices()``: a
chip belongs to one process, so a child process could not reach the
devices its parent already holds.  On a CPU-only host, give the process
several devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
(``make bench-mesh-smoke`` does); with a single device the module fails.

Digest checks: every mode — single-device whale, sharded whale, sharded
sliding/gear streams, both fusion bursts — is compared byte-for-byte
against the hashlib / ops reference and reported as ``digest_ok``;
``run()`` asserts it, so a sharding or fusion bug fails the bench run.
The 1-vs-N ``speedup`` is reported as a measured counter, not asserted:
forced host devices share the machine's cores, so on a single-core
container the shards serialize (speedup ~1x or below).  The
adaptive-vs-static contract IS asserted: at equal submitted ``jobs``,
the adaptive-fusion round must need no more ``launches`` than the
static-cap round.
"""
from __future__ import annotations

import hashlib
import time

import jax
import numpy as np

from benchmarks.common import mbps, scaled, timeit
from repro.core.crystal import CrystalTPU
from repro.kernels import ops

WHALE_ROWS = scaled(192, 32)          # whale direct job: rows x ROW_KB
WHALE_ROW_KB = scaled(64, 8)
SLIDE_KB = scaled(384, 96)            # sharded stream buffers
GEAR_KB = scaled(768, 192)
BURST_JOBS = scaled(48, 16)           # fusion-ablation burst
BURST_CHUNK_KB = scaled(8, 4)
STATIC_CAP_ROWS = 4                   # deliberately small static guess
STATIC_CAP_BYTES = 1 << 20


def run() -> list:
    devs = jax.devices()
    if len(devs) < 2:
        raise RuntimeError(
            f"engine_mesh needs >= 2 devices, found {len(devs)}: run it on "
            "a multi-chip host or under make bench-mesh-smoke")
    rng = np.random.default_rng(7)
    rows_arr = rng.integers(0, 256, (WHALE_ROWS, WHALE_ROW_KB << 10),
                            np.uint8)
    total = rows_arr.size
    ref = np.stack([np.frombuffer(hashlib.md5(rows_arr[i].tobytes())
                                  .digest(), np.uint8)
                    for i in range(WHALE_ROWS)])
    digest_ok = True
    rows: list = []

    def whale(devices, shard_min):
        nonlocal digest_ok
        eng = CrystalTPU(devices=devices, shard_min_bytes=shard_min)
        got = eng.submit("direct", rows_arr, {}).wait()   # warm + check
        digest_ok &= bool(np.array_equal(got, ref))
        sec = timeit(lambda: eng.submit("direct", rows_arr, {}).wait(),
                     repeats=3, warmup=0)
        stats = eng.snapshot_stats()
        eng.shutdown()
        return sec, stats

    sec1, _ = whale([devs[0]], 1 << 62)
    # shard threshold sized so the whale splits one shard per device
    padded = WHALE_ROWS * (1 << (rows_arr.shape[1] - 1).bit_length())
    secN, statsN = whale(list(devs), max(padded // len(devs), 1))
    speedup = sec1 / max(secN, 1e-12)
    rows.append(("mesh/whale_1dev", sec1 * 1e6,
                 f"mbps={mbps(total, sec1):.1f}"))
    rows.append((f"mesh/whale_{len(devs)}dev_sharded", secN * 1e6,
                 f"mbps={mbps(total, secN):.1f}_speedup={speedup:.2f}_"
                 f"sharded_jobs={statsN['sharded_jobs']}_"
                 f"shards={statsN['shards']}"))
    for i, ds in sorted(statsN["per_device"].items()):
        rows.append((f"mesh/device_{i}", ds["ewma_launch_s"] * 1e6,
                     f"jobs={ds['jobs']}_launches={ds['launches']}_"
                     f"bytes={ds['bytes']}_"
                     f"queue_depth={ds['queue_depth']}_"
                     f"restarts={ds['manager_restarts']}"))

    # sharded streams: digests must equal the unsharded ops oracle
    eng = CrystalTPU(devices=list(devs), shard_min_bytes=32 << 10)
    sbuf = rng.integers(0, 256, (SLIDE_KB << 10) + 17, dtype=np.uint8)
    gbuf = rng.integers(0, 256, (GEAR_KB << 10) + 5, dtype=np.uint8)
    t0 = time.perf_counter()
    sj = eng.submit("sliding", sbuf, {"window": 48, "stride": 4})
    gj = eng.submit("gear", gbuf, {})
    s_got, g_got = sj.wait(), gj.wait()
    stream_s = time.perf_counter() - t0
    digest_ok &= bool(np.array_equal(
        s_got, ops.sliding_window_hash(sbuf.tobytes(), 48, 4)))
    digest_ok &= bool(np.array_equal(g_got,
                                     ops.gear_hash(gbuf.tobytes())))
    st = eng.snapshot_stats()
    eng.shutdown()
    rows.append(("mesh/stream_shard", stream_s * 1e6,
                 f"ok={int(digest_ok)}_sharded_jobs={st['sharded_jobs']}"
                 f"_shards={st['shards']}"))

    # static vs adaptive fusion: identical two-round burst, round-2
    # launch counts compared at equal job counts
    chunk = rng.integers(0, 256, BURST_CHUNK_KB << 10, dtype=np.uint8)
    want = np.frombuffer(hashlib.md5(chunk.tobytes()).digest(), np.uint8)

    def burst(adaptive):
        nonlocal digest_ok
        eng = CrystalTPU(devices=[devs[0]],
                         max_fused_rows=STATIC_CAP_ROWS,
                         max_fused_bytes=STATIC_CAP_BYTES,
                         coalesce_window_s=0.05,
                         adaptive_fusion=adaptive)
        deltas = []
        for _ in range(2):               # round 1 warms model + caps
            before = eng.snapshot_stats()
            t0 = time.perf_counter()
            jobs = [eng.submit("direct", chunk, {})
                    for _ in range(BURST_JOBS)]
            for j in jobs:
                digest_ok &= bool(np.array_equal(j.wait()[0], want))
            sec = time.perf_counter() - t0
            after = eng.snapshot_stats()
            deltas.append((after["jobs"] - before["jobs"],
                           after["launches"] - before["launches"], sec))
        policy = eng.snapshot_stats()["policy"]
        eng.shutdown()
        return deltas[-1], policy

    (jobs_s, launches_s, sec_s), _ = burst(False)
    (jobs_a, launches_a, sec_a), pol = burst(True)
    rows.append(("mesh/fusion_static", sec_s * 1e6,
                 f"jobs={jobs_s}_launches={launches_s}"))
    rows.append(("mesh/fusion_adaptive", sec_a * 1e6,
                 f"jobs={jobs_a}_launches={launches_a}_"
                 f"cap_rows={pol['max_fused_rows']}_"
                 f"cap_bytes={pol['max_fused_bytes']}"))
    rows.append(("mesh/digest_ok", 0.0, f"ok={int(digest_ok)}"))
    assert digest_ok, \
        "sharded/fused digests diverged from the CPU reference"
    assert jobs_s == jobs_a, (jobs_s, jobs_a)
    assert launches_a <= launches_s, (launches_a, launches_s)
    return rows


if __name__ == "__main__":
    for r in run():
        print(f"{r[0]},{r[1]:.1f},{r[2]}")
