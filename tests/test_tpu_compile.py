"""Ahead-of-time compiles of the hashing kernels for a TPU v5e chip.

Nothing runs: each test lowers one engine entry point (``ops``) for a
described v5e device at a width the engine launches and checks that the
compiled program holds a Mosaic kernel (``tpu_custom_call``) — a kernel
the chip's compiler refuses, or one that silently fell back to the
Pallas interpreter, fails here without a chip.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

MiB = 1 << 20

# (entry point, rows, bytes per row, static args)
CASES = {
    "direct-64x2MiB": ("direct", 64, 2 * MiB, {}),
    "direct-8x8MiB": ("direct", 8, 8 * MiB, {}),
    "sliding-8x1MiB": ("sliding", 8, MiB, {}),
    "sliding-1x256MiB": ("sliding", 1, 256 * MiB, {}),
    "sliding-stride1-2x1MiB": ("sliding", 2, MiB, {"phases": (0, 1, 2, 3)}),
    "gear-8x1MiB": ("gear", 8, MiB, {}),
    "gear-1x256MiB": ("gear", 1, 256 * MiB, {}),
    "gear-v2-8x1MiB": ("gear", 8, MiB, {"version": 2}),
    "gear-v3-8x1MiB": ("gear", 8, MiB, {"version": 3}),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _lower(one_chip, kind, rows, row_bytes, static):
    words = jax.ShapeDtypeStruct((rows, row_bytes // 4), jnp.uint32,
                                 sharding=one_chip)
    if kind == "direct":
        lens = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
        return ops.direct_hash_device.lower(words, lens)
    if kind == "sliding":
        return ops.sliding_hash_batch_device.lower(
            words, 12, static.get("phases", (0,)))
    return ops.gear_hash_batch_device.lower(words,
                                            version=static.get("version", 1))


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_to_mosaic(one_chip, case):
    compiled = _lower(one_chip, *CASES[case]).compile()
    assert "tpu_custom_call" in compiled.as_text(), case
