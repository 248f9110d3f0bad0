"""The per-layer metrics that read the program's launch-phase counters and
sub-stage seconds: the tiny traced cells report every one of them on the
CPU, and each reader stays silent where the program keeps no such
counter."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import spec

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402

SEED = 2**31 + 91
ENGINE = ["engine_queue_s_per_GB", "engine_stage_s_per_GB",
          "engine_dispatch_s_per_GB", "engine_wait_s_per_GB",
          "engine_finish_s_per_GB", "engine_h2d_bytes_per_user_byte",
          "md5_lane_share"]
STAGES = ["sai_pack_s_per_GB", "chunk_fingerprint_s_per_GB",
          "chunk_select_s_per_GB"]
BULK_ONLY_OUT = {"engine_finish_s_per_GB", "chunk_fingerprint_s_per_GB",
                 "chunk_select_s_per_GB"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return bench_tiny.make_copy(tmp_path_factory.mktemp("bench_copy_spans"))


@pytest.mark.parametrize("cell", ["tiny_fixed.tiny-bulk",
                                  "tiny_gear.tiny-versions"])
def test_traced_tiny_cell_reports_the_phase_metrics(copy, cell):
    rc, res, err = bench_tiny.run_cell(copy, cell, SEED, trace=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    want = set(ENGINE + STAGES)
    if cell.endswith("tiny-bulk"):
        want -= BULK_ONLY_OUT
    assert want <= set(got), sorted(want - set(got))
    assert got["engine_h2d_bytes_per_user_byte"] > 1
    assert 0 < got["md5_lane_share"] <= 100
    phases = sum(got.get(k, 0.0) for k in (
        "engine_stage_s_per_GB", "engine_dispatch_s_per_GB",
        "engine_wait_s_per_GB", "engine_finish_s_per_GB"))
    if "engine_finish_s_per_GB" in got:         # every phase reported
        assert phases <= got["engine_launch_s_per_GB"]
    assert got["sai_pack_s_per_GB"] <= got["sai_hash_s_per_GB"]
    if "chunk_select_s_per_GB" in got:
        assert (got["chunk_fingerprint_s_per_GB"]
                + got["chunk_select_s_per_GB"]) <= got["chunk_s_per_GB"]


@pytest.mark.parametrize("name", ENGINE + STAGES)
def test_reader_is_silent_without_the_counter(name):
    """A program that keeps neither the phase counters nor the sub-stage
    seconds (as before they existed) gives these readers nothing."""
    read = spec.load_reader(spec.ROOT / "bench" / "layer_metrics"
                            / f"{name}.py")
    row = {"jobs": 4, "launches": 2, "bytes": 1 << 20,
           "launch_hist": {"count": 2, "sum_s": 0.5}}
    old = SimpleNamespace(stage_s={"chunk": 0.2, "hash": 0.3,
                                   "store": 0.1})
    ctx = {"ingest": {"user_bytes": 1 << 20, "write_stats": [old]},
           "engine_before": {"per_device": {0: dict(row, launches=0)}},
           "engine_after": {"per_device": {0: row}}}
    assert read(ctx) is None
    assert read({}) is None
