"""Tiny cells, added from new files to a copy of the benchmark, run end to
end on the CPU through the whole harness (chip check skipped): sound
runs come out correct, and with a fault planted under the timed path
(or the control put in its place) they come out not correct.  This file
holds the fixed-block ingest cells; the CDC cell has a file of its own
so that test workers run them side by side."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402

SEED = 2**31 + 77


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return bench_tiny.make_copy(tmp_path_factory.mktemp("bench_copy"))


def _expect(copy, cell, fault, want_correct, trace=0):
    rc, result, err = bench_tiny.run_cell(copy, cell, SEED, fault=fault,
                                          trace=trace)
    assert rc == 0, err[-3000:]
    assert result["correct"] is want_correct, result["checks"]
    assert list(result)[-1] == "checks"
    # every compared number is also on the last lines of stderr
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert [line.split()[1] for line in tail] == list(result["checks"])
    return result


@pytest.mark.parametrize("cell,fault,want", [
    ("tiny_fixed.tiny-bulk", None, True),
    ("tiny_fixed.tiny-bulk", "replicas2", False),
    ("tiny_fixed.tiny-bulk", "digest", False),
    ("tiny_fixed.tiny-bulk", "half_batch", False),
    ("tiny_fixed.tiny-bulk", "unchanged", False),
    ("tiny_fixed.tiny-bulk", "stored_bytes", False),
])
def test_ingest_cell(copy, cell, fault, want):
    res = _expect(copy, cell, fault, want)
    assert set(res["metrics"]) == {"ingest_MBps", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    if fault == "stored_bytes":
        # only the replica check sees bytes altered in the store
        bad = {k for k, v in res["checks"].items()
               if v["value"] > v["limit"]}
        assert bad == {"replicas_with_wrong_bytes"}


def test_cell_from_new_modules_runs(copy):
    """A cell whose entry, reference and content kind are new files runs
    through the harness, and its retired writes' replicas are read."""
    rc, res, err = bench_tiny.run_cell(copy, "tiny_newref.tiny-newentry",
                                       SEED)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, res["checks"]
    assert "entry: tiny_entry" in err
    retired = int(err.split("replicas read: of ")[1].split()[0])
    assert retired > 0


def test_traced_run_reports_no_device_metric_on_a_cpu(copy):
    res = _expect(copy, "tiny_fixed.tiny-bulk", None, True, trace=1)
    assert "sai_hash_s_per_GB" in res["metrics"]
    # no device plane on a CPU: device metrics stay silent
    assert not {"device_idle_share.ingest", "md5_hbm_share"} \
        & set(res["metrics"])
