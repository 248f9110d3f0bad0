"""Device-resident writes: a ``jax.Array`` handed to the SAI is hashed on
its own device, only the blocks the store claims leave it, and a sharded
train state saved by ``CACheckpointer`` comes back on the same devices,
bit for bit.

The in-process tests run on the one host device; the sharded ones run
four forced host devices in a subprocess (as tests/test_engine_mesh.py
does), once for the module, and each test checks one of its findings.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import SAI, SAIConfig, CrystalTPU, make_store
from repro.core.crystal import DEVICE_PHASES
from repro.core.sai import block_digest_cpu
from repro.kernels import ops

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
BLOCK = 4096


def _sai(engine, ca="fixed", hasher="tpu"):
    mgr, nodes = make_store(4, replication=3)
    return SAI(mgr, SAIConfig(ca=ca, block_size=BLOCK, hasher=hasher),
               engine), mgr, nodes


def _blocks(data: bytes, size: int = BLOCK):
    return [data[i:i + size] for i in range(0, len(data), size)]


@pytest.fixture(scope="module")
def engine():
    eng = CrystalTPU(devices=[jax.devices()[0]])
    yield eng
    eng.shutdown()


@pytest.mark.parametrize("dtype,shape", [
    (jnp.bfloat16, (37, 300)),           # a short last block
    (jnp.float32, (8, 512)),             # whole blocks
    (jnp.uint8, (4097,)),                # not a whole word
    (jnp.uint8, (3,)),                   # one short block
    (jnp.bool_, (5000,)),                # one byte each, 0 or 1
])
def test_device_write_digests_match_host_path_and_hashlib(engine, dtype,
                                                          shape):
    rng = np.random.default_rng(1)
    host = np.asarray(rng.integers(0, 255, shape), np.float32).astype(dtype)
    x = jax.device_put(host, jax.devices()[0])
    sai, mgr, _ = _sai(engine)
    dev_stats = sai.write_async("/dev", x).result()
    host_stats = sai.write("/host", host.tobytes())
    dev_blocks = mgr.get_blockmap("/dev").blocks
    assert [b.digest for b in dev_blocks] == \
        [b.digest for b in mgr.get_blockmap("/host").blocks] == \
        [block_digest_cpu(c) for c in _blocks(host.tobytes())]
    assert [b.length for b in dev_blocks] == \
        [len(c) for c in _blocks(host.tobytes())]
    assert dev_stats.new_blocks == len(dev_blocks)
    assert host_stats.new_blocks == 0 and host_stats.dup_blocks == \
        len(dev_blocks)
    assert sai.read("/dev") == host.tobytes()


def test_only_claimed_blocks_leave_the_device(engine):
    rng = np.random.default_rng(2)
    base = rng.integers(0, 255, 10 * BLOCK, np.uint8)
    sai, mgr, nodes = _sai(engine)
    before = engine.snapshot_stats()["per_device"][0]
    first = sai.write_async("/a", jax.device_put(base)).result()
    assert first.d2h_bytes == 10 * BLOCK + 16 * 10
    changed = base.copy()
    changed[3 * BLOCK + 5] ^= 1                  # one block differs
    changed[7 * BLOCK] ^= 1                      # and another
    second = sai.write_async("/b", jax.device_put(changed)).result()
    assert (second.new_blocks, second.dup_blocks) == (2, 8)
    assert second.d2h_bytes == second.new_bytes + 16 * 10 == \
        2 * BLOCK + 160
    assert "fetch" in second.stage_s
    after = engine.snapshot_stats()["per_device"][0]
    # nothing handed to device_put for a device-resident job
    assert after["h2d_bytes"] == before["h2d_bytes"]
    assert after["device_jobs"] - before["device_jobs"] == 2
    assert after["device_bytes"] - before["device_bytes"] == 20 * BLOCK
    assert set(after["phase_s"]["device"]) == set(DEVICE_PHASES)
    assert sai.read("/b") == changed.tobytes()
    stored = {d for n in nodes for d in n.blocks}
    assert len(stored) == 12


def test_a_short_last_block_leaves_at_its_own_length(engine):
    rng = np.random.default_rng(7)
    base = rng.integers(0, 255, 6 * BLOCK + 100, np.uint8)
    sai, _, _ = _sai(engine)
    first = sai.write("/t", jax.device_put(base))
    assert first.d2h_bytes == 6 * BLOCK + 100 + 16 * 7
    changed = base.copy()
    changed[-1] ^= 1                             # the short last block
    changed[2 * BLOCK] ^= 1                      # and a whole one
    second = sai.write("/t", jax.device_put(changed))
    assert (second.new_blocks, second.new_bytes) == (2, BLOCK + 100)
    assert second.d2h_bytes == BLOCK + 100 + 16 * 7
    assert sai.read("/t") == changed.tobytes()


def test_device_write_needs_fixed_blocks_and_the_engine(engine):
    x = jax.device_put(np.zeros(16, np.uint8))
    for ca, hasher in (("cdc-gear", "tpu"), ("fixed", "cpu")):
        sai, _, _ = _sai(engine, ca=ca, hasher=hasher)
        assert not sai.takes_device_data
        with pytest.raises(ValueError, match="device-resident"):
            sai.write_async("/x", x)


@pytest.mark.parametrize("dtype,ok", [
    (jnp.bool_, True), (jnp.uint8, True), (jnp.int8, True),
    (jnp.uint16, True), (jnp.int16, True), (jnp.float16, True),
    (jnp.bfloat16, True), (jnp.uint32, True), (jnp.int32, True),
    (jnp.float32, True), (jnp.float8_e4m3fn, True),
    (jnp.int64, False), (jnp.float64, False), (jnp.complex64, False),
    (jnp.int4, False),
])
def test_has_device_words_takes_whole_bytes_of_1_2_4_byte_types(dtype, ok):
    assert ops.has_device_words(dtype) is ok


def test_device_write_of_a_type_without_device_words_is_refused(engine):
    x = jax.device_put(np.arange(8, dtype=np.complex64))
    sai, _, _ = _sai(engine)
    with pytest.raises(ValueError, match="complex64"):
        engine.submit("direct", x, {"block_bytes": BLOCK})
    with pytest.raises(ValueError, match="write its bytes"):
        sai.write_async("/c", x)


def test_pulls_of_new_block_counts_compile_nothing_after_the_first(engine):
    """The base write pulls every block of its shape; later writes that
    claim other counts of blocks reuse the gathers it compiled, and
    move only the blocks they claim."""
    compiles = []

    def on(event, duration_s, *args, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    rng = np.random.default_rng(6)
    base = rng.integers(0, 255, (11, BLOCK // 2), np.uint16)
    sai, _, _ = _sai(engine)
    sai.write("/w", jax.device_put(base))
    assert sai.read("/w") == base.tobytes()     # the read's launch shape
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        for k, n_new in enumerate((3, 6, 11)):
            data = base.copy()
            data[:n_new, 0] += k + 1            # n_new blocks change
            st = sai.write("/w", jax.device_put(data))
            assert st.new_blocks == n_new
            assert st.d2h_bytes == n_new * BLOCK + 16 * 11
            assert sai.read("/w") == data.tobytes()
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert compiles == []


def test_pack_blocks_device_rows_equal_the_host_rows():
    from repro.core.sai import pack_blocks
    rng = np.random.default_rng(4)
    host = rng.integers(0, 255, 3 * BLOCK + 10, np.uint8)
    rows, lens = pack_blocks(_blocks(host.tobytes()))
    words, lens_w = ops.pack_blocks_device(
        jnp.asarray(host), block_bytes=BLOCK, width=rows.shape[1], rows=8)
    words, lens_w = np.asarray(words), np.asarray(lens_w)
    assert np.array_equal(words[:4].view(np.uint8), rows)
    assert np.array_equal(lens_w[:4] * 4, lens)
    assert not words[4:].any() and not lens_w[4:].any()


# ---------------------------------------------------------------------
# four devices: device jobs stay home; sharded save and restore
# ---------------------------------------------------------------------
MESH_SCRIPT = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import SAI, SAIConfig, CrystalTPU, make_store
    from repro.core.sai import block_digest_cpu
    from repro.train.checkpoint import CACheckpointer
    devs = jax.devices()
    assert len(devs) == 4, devs
    out = {}
    # a device job lands on its own device's manager, unsharded, even
    # when it is far past the whale threshold
    eng = CrystalTPU(devices=devs, shard_min_bytes=4096)
    rng = np.random.default_rng(5)
    per = []
    for d in devs:
        host = rng.integers(0, 255, 16 * 4096, np.uint8)
        job = eng.submit("direct", jax.device_put(host, d),
                         {"block_bytes": 4096})
        want = [block_digest_cpu(host[i:i + 4096].tobytes())
                for i in range(0, host.size, 4096)]
        per.append(job.device_index == devs.index(d)
                   and [r.tobytes() for r in job.wait()] == want)
    st = eng.snapshot_stats()
    out["jobs_stay_home"] = per
    out["sharded_jobs"] = st["sharded_jobs"]
    out["device_jobs"] = [st["per_device"][i]["device_jobs"]
                          for i in range(4)]
    out["h2d"] = sum(r["h2d_bytes"] for r in st["per_device"].values())
    eng.shutdown()

    # a sharded state saved shard by shard and restored in place
    eng = CrystalTPU(devices=devs)
    mgr, nodes = make_store(4, replication=3)
    sai = SAI(mgr, SAIConfig(ca="fixed", block_size=4096), eng)
    mesh = Mesh(np.array(devs), ("r",))
    rows = NamedSharding(mesh, P("r"))
    params = {
        "w": jax.device_put(jnp.asarray(rng.standard_normal((64, 300)),
                                        jnp.bfloat16), rows),
        "b": jax.device_put(jnp.asarray(rng.standard_normal((8, 1000)),
                                        jnp.float32),
                            NamedSharding(mesh, P(None, "r"))),
        "rep": jax.device_put(jnp.arange(10, dtype=jnp.float32),
                              NamedSharding(mesh, P())),
        "one": jax.device_put(jnp.ones((5, 5)), devs[2]),
        "mask": jax.device_put(jnp.arange(4000) % 3 == 0, rows),
        "cplx": jax.device_put(jnp.arange(8, dtype=jnp.complex64)
                               * (1 + 2j), rows),
        "host": np.arange(7, dtype=np.float32)}
    with jax.enable_x64(True):
        params["i64"] = jax.device_put(
            jnp.arange(8, dtype=jnp.int64) << 40, rows)
    ck = CACheckpointer(sai, prefix="ckpt")
    rec = ck.save(1, params)
    files = sorted(mgr.list_files())
    out["files"] = files
    out["new_blocks"] = rec["new_blocks"]
    step, state, _ = ck.restore()
    same = {}
    for k in ("w", "b", "rep", "one", "mask"):
        got, want = state["params"][k], params[k]
        same[k] = (got.sharding == want.sharding
                   and [s.device for s in got.addressable_shards]
                   == [s.device for s in want.addressable_shards]
                   and all(np.asarray(a.data).tobytes()
                           == np.asarray(b.data).tobytes()
                           for a, b in zip(got.addressable_shards,
                                           want.addressable_shards)))
    # leaves of 8-byte types are saved whole from a host copy
    for k in ("host", "cplx", "i64"):
        got = state["params"][k]
        same[k] = (isinstance(got, np.ndarray)
                   and got.dtype == params[k].dtype
                   and got.tobytes() == np.asarray(params[k]).tobytes())
    out["restored"] = same
    out["step"] = step
    # a second save of the same state stores nothing new
    out["resave_new"] = ck.save(2, params)["new_bytes"]
    # async_save snapshots on the device: a later step cannot change it
    w0 = params["w"]
    ck.async_save(3, {"w": w0})
    params["w"] = (w0.astype(jnp.float32) + 1).astype(jnp.bfloat16)
    ck.wait()
    _, snap, _ = ck.restore()
    out["async_snapshot"] = (np.asarray(snap["params"]["w"]).tobytes()
                             == np.asarray(w0).tobytes())
    eng.shutdown()
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def mesh_run():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_device_job_stays_on_its_device_and_is_never_sharded(mesh_run):
    assert mesh_run["jobs_stay_home"] == [True] * 4
    assert mesh_run["sharded_jobs"] == 0
    assert mesh_run["device_jobs"] == [1, 1, 1, 1]
    assert mesh_run["h2d"] == 0


def test_save_writes_one_file_per_shard(mesh_run):
    files = mesh_run["files"]
    assert [f for f in files if f.startswith("ckpt/params/w#")] == \
        [f"ckpt/params/w#{i}" for i in range(4)]
    assert [f for f in files if f.startswith("ckpt/params/b#")] == \
        [f"ckpt/params/b#{i}" for i in range(4)]
    # a replicated leaf is saved once; a single-device leaf is one shard
    assert "ckpt/params/rep#0" in files and "ckpt/params/rep#1" not in files
    assert "ckpt/params/one#0" in files
    assert [f for f in files if f.startswith("ckpt/params/mask#")] == \
        [f"ckpt/params/mask#{i}" for i in range(4)]
    for key in ("host", "cplx", "i64"):
        assert f"ckpt/params/{key}" in files
    assert "ckpt/MANIFEST" in files
    assert mesh_run["resave_new"] == 0


def test_restore_puts_every_shard_back_on_its_device(mesh_run):
    assert mesh_run["step"] == 1
    assert mesh_run["restored"] == {
        "w": True, "b": True, "rep": True, "one": True, "mask": True,
        "host": True, "cplx": True, "i64": True}


def test_async_save_snapshots_on_the_device(mesh_run):
    assert mesh_run["async_snapshot"] is True
