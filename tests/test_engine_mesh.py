"""Engine-mesh behaviours (ISSUE 6): whale-job sharding, load-aware
dispatch, adaptive fusion, manager crash recovery, per-device stats.

Most tests run in-process with the single host device duplicated
(``devices=[dev]*4`` gives four managers/queues over one physical
device — the scheduling logic is identical); one subprocess test forces
real multi-device scheduling with
``--xla_force_host_platform_device_count=4`` (SNIPPETS snippet 1).
"""
import hashlib
import os
import subprocess
import sys
import textwrap
import threading
import time

import jax
import numpy as np
import pytest

from repro.core.crystal import CrystalTPU
from repro.kernels import ops

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _mesh(n=4, **kw):
    dev = jax.devices()[0]
    return CrystalTPU(devices=[dev] * n, **kw)


def _md5_rows(rows):
    return np.stack([np.frombuffer(hashlib.md5(r.tobytes()).digest(),
                                   np.uint8) for r in rows])


# ---------------------------------------------------------------------
# sharding: digests must be byte-identical to the unsharded reference
# ---------------------------------------------------------------------

def test_sharded_direct_digest_equality():
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 256, (16, 8192), np.uint8)
    eng = _mesh(4, shard_min_bytes=32 << 10)
    try:
        got = eng.submit("direct", rows, {}).wait()
        assert np.array_equal(got, _md5_rows(rows))
        st = eng.snapshot_stats()
        assert st["sharded_jobs"] == 1
        assert st["shards"] >= 2
        busy = [d for d in st["per_device"].values() if d["jobs"]]
        assert len(busy) >= 2, st["per_device"]
    finally:
        eng.shutdown()


def test_sharded_stream_digest_equality():
    rng = np.random.default_rng(1)
    sbuf = rng.integers(0, 256, (64 << 10) + 17, np.uint8)
    gbuf = rng.integers(0, 256, (160 << 10) + 5, np.uint8)
    eng = _mesh(4, shard_min_bytes=16 << 10)
    try:
        sj = eng.submit("sliding", sbuf, {"window": 48, "stride": 4})
        gj = eng.submit("gear", gbuf, {})
        assert np.array_equal(
            sj.wait(), ops.sliding_window_hash(sbuf.tobytes(), 48, 4))
        assert np.array_equal(gj.wait(),
                              ops.gear_hash(gbuf.tobytes()))
        assert eng.snapshot_stats()["sharded_jobs"] == 2
    finally:
        eng.shutdown()


def test_small_jobs_do_not_shard():
    eng = _mesh(2, shard_min_bytes=1 << 20)
    try:
        rows = np.zeros((4, 1024), np.uint8)
        assert np.array_equal(eng.submit("direct", rows, {}).wait(),
                              _md5_rows(rows))
        assert eng.snapshot_stats()["sharded_jobs"] == 0
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------
# load-aware dispatch: a slow device receives less work
# ---------------------------------------------------------------------

def test_load_aware_dispatch_skews_away_from_slow_device():
    eng = _mesh(4, coalesce=False)
    eng._launch_hook = lambda idx, batch: (time.sleep(0.05)
                                           if idx == 0 else None)
    total = 30
    try:
        jobs = []
        for _ in range(total):
            jobs.append(eng.submit(
                "direct", np.ones((1, 4096), np.uint8), {}))
            time.sleep(0.01)       # pace so backlog signals can develop
        for j in jobs:
            j.wait()
        per = eng.snapshot_stats()["per_device"]
        assert sum(d["jobs"] for d in per.values()) == total
        assert per[0]["jobs"] < total / 3, {
            i: d["jobs"] for i, d in per.items()}
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------
# adaptive fusion: caps move in the direction the measurements demand
# ---------------------------------------------------------------------

def test_adaptive_caps_grow_under_launch_overhead():
    """Tiny same-size jobs + injected fixed launch latency = overhead-
    dominated regime: the policy should open the fusion caps."""
    eng = _mesh(1, adaptive_fusion=True, max_fused_rows=4,
                max_fused_bytes=64 << 10)
    eng._launch_hook = lambda idx, batch: time.sleep(0.008)
    try:
        for _ in range(12):
            eng.submit("direct", np.ones((1, 4096), np.uint8),
                       {}).wait()
        assert eng.max_fused_bytes > 64 << 10
        assert eng.max_fused_rows > 4
        pol = eng.snapshot_stats()["policy"]
        assert pol["adaptive"] == 1
        assert pol["max_fused_bytes"] == eng.max_fused_bytes
    finally:
        eng.shutdown()


def test_adaptive_caps_shrink_under_latency_target():
    """Varied job sizes + injected per-byte latency teach the cost model
    a real slope; the target launch latency then bounds the byte cap
    below the static guess."""
    eng = _mesh(1, adaptive_fusion=True, max_fused_rows=64,
                max_fused_bytes=1 << 20, target_launch_s=0.1)
    eng._launch_hook = lambda idx, batch: time.sleep(
        3e-6 * sum(j.padded_bytes for j in batch))
    try:
        for _ in range(8):
            for kb in (16, 32, 64):
                eng.submit("direct",
                           np.ones((1, kb << 10), np.uint8), {}).wait()
        assert eng.max_fused_bytes < 1 << 20, eng.max_fused_bytes
    finally:
        eng.shutdown()


def test_static_mode_caps_never_move():
    eng = _mesh(1, max_fused_rows=8, max_fused_bytes=1 << 20)
    try:
        for _ in range(6):
            eng.submit("direct", np.ones((1, 4096), np.uint8),
                       {}).wait()
        assert eng.max_fused_rows == 8
        assert eng.max_fused_bytes == 1 << 20
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------
# manager crash resilience
# ---------------------------------------------------------------------

def test_manager_crash_fails_batch_and_requeues_rest():
    eng = _mesh(2, coalesce=False)
    fired = threading.Event()

    def fault(idx, batch):
        if idx == 0 and not fired.is_set():
            fired.set()
            raise RuntimeError("injected manager crash")

    eng._fault_hook = fault
    data = np.ones((1, 4096), np.uint8)
    ref = _md5_rows(data)
    try:
        jobs = [eng.submit("direct", data, {}) for _ in range(12)]
        failures, successes = 0, 0
        for j in jobs:
            try:
                assert np.array_equal(j.wait(), ref)
                successes += 1
            except RuntimeError as e:
                assert "injected manager crash" in str(e)
                failures += 1
        assert fired.is_set()
        assert failures >= 1
        assert successes == 12 - failures
        st = eng.snapshot_stats()
        assert st["manager_restarts"] == 1
        assert sum(d["manager_restarts"]
                   for d in st["per_device"].values()) == 1
        # the restarted manager still serves its queue
        assert np.array_equal(eng.submit("direct", data, {}).wait(), ref)
        assert eng.queue_depth() == 0
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------
# octave classes: tiny and huge stream jobs must never share a launch
# ---------------------------------------------------------------------

def test_tiny_and_huge_stream_jobs_never_fuse():
    rng = np.random.default_rng(2)
    tiny = rng.integers(0, 256, 2048, np.uint8)
    huge = rng.integers(0, 256, 256 << 10, np.uint8)
    eng = _mesh(1, coalesce_window_s=0.25)
    try:
        assert (eng.policy.octave_class(tiny.size)
                != eng.policy.octave_class(huge.size))
        tj = eng.submit("gear", tiny, {})
        hj = eng.submit("gear", huge, {})
        assert np.array_equal(tj.wait(), ops.gear_hash(tiny.tobytes()))
        assert np.array_equal(hj.wait(), ops.gear_hash(huge.tobytes()))
        st = eng.snapshot_stats()
        assert st["jobs"] == 2
        assert st["launches"] == 2      # a fused pair would show 1
    finally:
        eng.shutdown()


def test_octave_class_is_true_power_of_two_octave():
    eng = _mesh(1)
    try:
        oc = eng.policy.octave_class
        assert oc(4096) == 13
        assert oc(8192) == 14           # adjacent octaves distinct
        assert oc(4096) != oc(8191 + 1)
        assert oc(6000) == oc(4097)     # same octave fuses
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------
# per-device stats + queue depth API
# ---------------------------------------------------------------------

def test_per_device_stats_and_queue_depth():
    eng = _mesh(2)
    try:
        data = np.ones((2, 4096), np.uint8)
        for _ in range(4):
            eng.submit("direct", data, {}).wait()
        st = eng.snapshot_stats()
        assert set(st["per_device"]) == {0, 1}
        for row in st["per_device"].values():
            for key in ("jobs", "launches", "bytes", "ewma_launch_s",
                        "queue_depth", "queued_bytes", "slowdown",
                        "manager_restarts"):
                assert key in row, key
        assert sum(d["jobs"] for d in st["per_device"].values()) == 4
        assert "policy" in st and "cost_model" in st
        assert eng.queue_depth() == 0
        assert eng.queue_depth("fg", device=0) == 0
        assert eng.queue_depth(device=1) == 0
        with pytest.raises(IndexError):
            eng.queue_depth(device=7)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("shard_min_bytes", [8 << 20, 32 << 10])
def test_launch_counters_sum_over_the_mesh(shard_min_bytes):
    """Row and lane counters add up across the mesh whether a job runs
    whole or in shards; shard children keep their parent's seq."""
    rows = np.random.default_rng(2).integers(0, 256, (16, 8192), np.uint8)
    eng = _mesh(4, shard_min_bytes=shard_min_bytes)
    try:
        job = eng.submit("direct", rows, {})
        job.wait()
        st = eng.snapshot_stats()
    finally:
        eng.shutdown()
    per = [d for d in st["per_device"].values() if d["launches"]]
    assert sum(d["md5_rows"] for d in per) == 16
    assert all(d["md5_lane_rows"] % 128 == 0 for d in per)
    assert sum(d["bytes"] for d in per) == 16 * 8192
    assert sum(d["h2d_bytes"] for d in per) >= 16 * 8192
    assert sum(d["launches"] for d in per) == max(st["shards"], 1)
    assert job.seq >= 0 and set(job.timings) == set(
        next(iter(per))["phase_s"]["direct"])


# ---------------------------------------------------------------------
# real multi-device scheduling (forced host devices, subprocess)
# ---------------------------------------------------------------------

def test_forced_multi_device_sharding_subprocess():
    code = textwrap.dedent("""
        import hashlib
        import jax, numpy as np
        from repro.core.crystal import CrystalTPU
        devs = jax.devices()
        assert len(devs) == 4, devs
        rng = np.random.default_rng(3)
        rows = rng.integers(0, 256, (16, 8192), np.uint8)
        ref = np.stack([np.frombuffer(
            hashlib.md5(r.tobytes()).digest(), np.uint8) for r in rows])
        eng = CrystalTPU(devices=list(devs), shard_min_bytes=32 << 10)
        got = eng.submit("direct", rows, {}).wait()
        assert np.array_equal(got, ref)
        st = eng.snapshot_stats()
        eng.shutdown()
        assert st["sharded_jobs"] == 1, st
        busy = [i for i, d in st["per_device"].items() if d["jobs"]]
        assert len(busy) >= 2, st["per_device"]
        print("MESH_OK", st["shards"], busy)
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_OK" in out.stdout
