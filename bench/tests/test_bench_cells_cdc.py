"""The tiny CDC ingest cell on the CPU: a sound run is correct; the
control and altered fingerprints are caught."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402

SEED = 2**31 + 83
CELL = "tiny_gear.tiny-versions"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return bench_tiny.make_copy(tmp_path_factory.mktemp("bench_copy_cdc"))


@pytest.mark.parametrize("fault,want", [
    (None, True), ("replicas2", False), ("fingerprint", False)])
def test_cdc_cell(copy, fault, want):
    rc, res, err = bench_tiny.run_cell(copy, CELL, SEED, fault=fault)
    assert rc == 0, err[-3000:]
    assert res["correct"] is want, res["checks"]
    assert set(res["metrics"]) == {"ingest_MBps", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
