"""CrystalTPU runtime: queueing, callbacks, ablation-equivalence, and
the per-device launch-phase counters."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CrystalTPU
from repro.core.crystal import PHASES
from repro.kernels import ops


@pytest.fixture(scope="module")
def crystal():
    c = CrystalTPU()
    yield c
    c.shutdown()


def test_stream_of_jobs(crystal, rng):
    bufs = [rng.integers(0, 256, 8192, dtype=np.uint8) for _ in range(6)]
    jobs = crystal.map_stream("direct", bufs, {"seg_bytes": 4096})
    for j, b in zip(jobs, bufs):
        got = j.wait()
        want = ops.direct_hash(b.reshape(2, 4096))
        np.testing.assert_array_equal(got, want)
    assert crystal.stats["jobs"] >= 6


def test_callbacks_fire(crystal, rng):
    done = threading.Event()
    res = {}

    def cb(job):
        res["r"] = job.result
        done.set()

    crystal.submit("gear", rng.integers(0, 256, 4096, dtype=np.uint8),
                   {}, callback=cb)
    assert done.wait(timeout=120)
    assert res["r"].shape == (4096,)


def test_error_propagation(crystal):
    job = crystal.submit("nonsense", np.zeros(4, np.uint8), {})
    with pytest.raises(ValueError):
        job.wait()


@pytest.mark.parametrize("reuse,overlap", [(True, True), (False, False),
                                           (True, False), (False, True)])
def test_ablations_equivalent_results(rng, reuse, overlap):
    """Optimization toggles change performance, never results."""
    c = CrystalTPU(buffer_reuse=reuse, overlap=overlap, n_slots=2)
    try:
        buf = rng.integers(0, 256, 8192, dtype=np.uint8)
        job = c.submit("sliding", buf, {"window": 48, "stride": 4})
        got = job.wait()
        want = ops.sliding_window_hash(buf.tobytes(), 48, 4)
        np.testing.assert_array_equal(got, want)
        assert set(job.timings) == {"stage", "put", "call", "wait",
                                    "finish"}
    finally:
        c.shutdown()


def _one_device_row(kind, data, meta, launches=1, **kw):
    """Per-device stats row after ``launches`` launches of one job."""
    c = CrystalTPU(devices=jax.devices()[:1], **kw)
    try:
        for _ in range(launches):
            c.submit(kind, data, meta).wait()
        return c.snapshot_stats()["per_device"][0]
    finally:
        c.shutdown()


@pytest.mark.parametrize("n", [1, 3, 130])
def test_direct_launch_counts_rows_lanes_and_h2d_bytes(rng, n):
    row = _one_device_row("direct",
                          rng.integers(0, 256, (n, 64), dtype=np.uint8), {})
    B = 1 << (n - 1).bit_length()              # rows bucket to a pow2
    assert row["md5_rows"] == n
    assert row["md5_lane_rows"] == -(-B // 128) * 128
    assert row["h2d_bytes"] == B * 64 + B * 4  # staging + int32 lens
    assert row["bytes"] == n * 64


# launches long enough (milliseconds here) that the engine's fixed
# per-launch bookkeeping, outside the phases, is a small share
@pytest.mark.parametrize("kind,size,meta", [
    ("direct", (16, 65536), {}),
    ("gear", 1 << 18, {}),
    ("sliding", 1 << 18, {"window": 48, "stride": 4}),
])
def test_phase_sums_account_for_the_launch_wall(rng, kind, size, meta):
    row = _one_device_row(kind, rng.integers(0, 256, size, dtype=np.uint8),
                          meta, launches=3)
    sums = row["phase_s"][kind]
    assert set(sums) == set(PHASES) and min(sums.values()) > 0.0
    wall = row["launch_hist"]["sum_s"]
    assert 0.9 * wall <= sum(sums.values()) <= wall
    assert row["queue_s"] >= 0.0
    if kind != "direct":                       # one pow2 row of words
        assert row["h2d_bytes"] == 3 * size
        assert row["md5_rows"] == row["md5_lane_rows"] == 0


def test_no_overlap_phases_are_the_table1_stages(monkeypatch):
    """Under ``overlap=False`` each stage ends synchronized, so the call
    phase holds the whole kernel and wait + finish only the copy out."""
    def slow_kernel(words, lens):
        def run(w):
            time.sleep(0.3)
            return np.zeros((w.shape[0], 4), np.uint32)
        return jax.pure_callback(
            run, jax.ShapeDtypeStruct((words.shape[0], 4), jnp.uint32),
            words)

    monkeypatch.setattr(ops, "direct_hash_device", slow_kernel)
    c = CrystalTPU(devices=jax.devices()[:1], overlap=False)
    try:
        job = c.submit("direct", np.ones((2, 64), np.uint8), {})
        job.wait()
    finally:
        c.shutdown()
    t = job.timings
    stages = {"in": t["stage"] + t["put"], "kernel": t["call"],
              "out": t["wait"] + t["finish"]}
    assert stages["kernel"] >= 0.3
    assert stages["out"] < 0.1 and stages["in"] < 0.3
