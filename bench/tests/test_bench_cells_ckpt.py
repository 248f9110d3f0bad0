"""The sharded-checkpoint cell at a tiny size on four host devices,
through the whole harness (chip check skipped): a sound run is correct
with every check at 0, only the blocks the store claims cross to the
host, the traced run reports the cell's metrics, the state's jnp and
numpy generators agree byte for byte, and a program without device
writes fails at once."""
from __future__ import annotations

import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import spec

sys.path.insert(0, str(Path(__file__).resolve().parent))
import ckpt_tiny  # noqa: E402

SEED = 2**33 + 161
METRICS = {"sai_hash_s_per_GB", "sai_store_s_per_GB",
           "engine_launch_s_per_GB", "ckpt_d2h_bytes_per_user_byte",
           "ckpt_fetch_s_per_GB", "md5_lane_share",
           "engine_h2d_bytes_per_user_byte", "engine_queue_s_per_GB",
           "engine_dispatch_s_per_GB", "engine_wait_s_per_GB",
           "engine_finish_s_per_GB"}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return ckpt_tiny.make_copy(tmp_path_factory.mktemp("bench_copy_ckpt"))


def test_sound_run_is_correct_and_moves_only_new_blocks(copy):
    rc, res, err = ckpt_tiny.run_cell(copy, SEED)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, res["checks"]
    assert all(v["value"] == 0 for v in res["checks"].values())
    assert set(res["checks"]) == {
        "failed_saves", "digests_off", "new_blocks_off",
        "blocks_on_too_few_nodes", "replicas_with_wrong_bytes",
        "stored_bytes_off", "restore_bytes_off"}
    assert set(res["metrics"]) == {"ingest_MBps", "setup_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 4
    assert "compiles inside the window: 0" in err
    # what left the devices is the new blocks and a digest per block
    pulled = err.split("bytes from the devices: ")[1].split()
    d2h, new, blocks = int(pulled[0]), int(pulled[2]), int(pulled[7])
    assert 0 < new and d2h == new + 16 * blocks
    assert "restore of the last save: ok" in err


def test_traced_run_reports_the_cell_metrics(copy):
    rc, res, err = ckpt_tiny.run_cell(copy, SEED + 1, trace=1)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert METRICS <= set(got), sorted(METRICS - set(got))
    assert 0 < got["ckpt_d2h_bytes_per_user_byte"] < 1
    assert got["ckpt_fetch_s_per_GB"] <= got["sai_store_s_per_GB"]
    assert 0 < got["md5_lane_share"] <= 100


def test_parent_program_fails_at_once(copy, tmp_path):
    """A program without device writes stops at the entry's first call
    into it, before any state is made."""
    shutil.copytree(copy / "bench", tmp_path / "bench")
    shutil.copy(copy / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ckpt_tiny.ROOT / "src", tmp_path / "src")
    ops = tmp_path / "src" / "repro" / "kernels" / "ops.py"
    ops.write_text(ops.read_text().replace("def pack_blocks_device",
                                           "def _gone"))
    rc, res, err = ckpt_tiny.run_cell(tmp_path, SEED, timeout=120)
    assert rc != 0 and res is None
    assert "pack_blocks_device" in err


def test_jnp_and_numpy_states_agree_byte_for_byte():
    """The state made on a device equals the reference's bytes, at
    steps 0 to 2, for every leaf (the sound run above holds all four
    ranks to the reference)."""
    import jax
    import numpy as np
    from bench.content import trainstate as content
    from bench.reference import trainstate as ref
    conf = ckpt_tiny.tiny_config()
    state = content.Source(conf, SEED, jax.devices()[:1])
    for step in range(3):
        if step:
            state.step()
        for leaf in state.leaves:
            shards = content.by_rank(state.arrays[leaf.key])
            for r, sh in enumerate(shards):
                got = np.asarray(sh)
                assert np.isfinite(got.astype(np.float32)).all()
                assert got.tobytes() == ref.block_bytes_of(
                    leaf, SEED, r, step, 0, leaf.nbytes), (leaf.key, r)


def test_frozen_leaves_never_change():
    import numpy as np
    from bench.content import trainstate as content
    import jax
    conf = ckpt_tiny.tiny_config()
    state = content.Source(conf, SEED, jax.devices()[:1])
    before = {k: np.asarray(v).tobytes() for k, v in state.arrays.items()}
    state.step()
    for leaf in state.leaves:
        same = np.asarray(state.arrays[leaf.key]).tobytes() \
            == before[leaf.key]
        assert same == (leaf.kind == "frozen"), leaf.key


@pytest.mark.parametrize("name", ["ckpt_d2h_bytes_per_user_byte",
                                  "ckpt_fetch_s_per_GB"])
def test_reader_is_silent_without_the_counter(name):
    """A program whose writes keep no d2h bytes or fetch seconds (as
    before they existed) gives these readers nothing."""
    read = spec.load_reader(spec.ROOT / "bench" / "layer_metrics"
                            / f"{name}.py")
    old = SimpleNamespace(stage_s={"chunk": 0.2, "hash": 0.3,
                                   "store": 0.1})
    assert read({"ingest": {"user_bytes": 1 << 20,
                            "write_stats": [old]}}) is None
    assert read({}) is None
