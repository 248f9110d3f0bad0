"""The tiny sharded-checkpoint cell with a fault planted under the timed
path (or the control in place of the configuration) comes out not
correct, each fault through the check that should see it."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import ckpt_tiny  # noqa: E402

SEED = 2**33 + 171


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return ckpt_tiny.make_copy(tmp_path_factory.mktemp("bench_copy_ckpt_f"))


@pytest.mark.parametrize("fault,sees", [
    ("digest", "digests_off"),
    ("half_batch", "digests_off"),
    ("unchanged", "replicas_with_wrong_bytes"),
    ("stored_bytes", "replicas_with_wrong_bytes"),
    ("replicas2", "blocks_on_too_few_nodes"),
])
def test_fault_is_caught(copy, fault, sees):
    rc, res, err = ckpt_tiny.run_cell(copy, SEED, fault=fault)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False, res["checks"]
    assert res["checks"][sees]["value"] > 0, res["checks"]
