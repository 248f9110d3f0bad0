"""HLO analyzer: loop scaling, dot FLOPs, collective wire bytes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.roofline.hlo_analysis import analyze_hlo
from repro.roofline.analysis import model_flops, HW


def test_scan_flops_scaled_by_trip_count():
    """cost_analysis counts a while body once; the analyzer must scale
    by trip count (the whole point of the module)."""
    def scanned(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        c, _ = jax.lax.scan(body, x, ws)
        return c

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    wN = jax.ShapeDtypeStruct((10, 256, 256), jnp.float32)
    compiled = jax.jit(scanned).lower(x, wN).compile()
    an = analyze_hlo(compiled.as_text())
    one_matmul = 2 * 128 * 256 * 256
    assert an["flops"] == pytest.approx(10 * one_matmul, rel=0.01)
    xla_flops = compiled.cost_analysis()["flops"]
    assert xla_flops == pytest.approx(one_matmul, rel=0.01)


def test_single_dot_flops():
    f = lambda a, b: a @ b
    a = jax.ShapeDtypeStruct((64, 32), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((32, 16), jnp.bfloat16)
    an = analyze_hlo(jax.jit(f).lower(a, b).compile().as_text())
    assert an["flops"] == pytest.approx(2 * 64 * 32 * 16, rel=0.01)


def test_dynamic_slice_bytes_not_full_operand():
    """A scan that slices one row per step must charge slice-sized reads,
    not the full stacked array each iteration."""
    def scanned(x, ws):
        def body(c, w):
            return c * 1.0 + jnp.sum(w), None
        c, _ = jax.lax.scan(body, x, ws)
        return c

    x = jax.ShapeDtypeStruct((8,), jnp.float32)
    ws = jax.ShapeDtypeStruct((100, 1024, 1024), jnp.float32)
    an = analyze_hlo(jax.jit(scanned).lower(x, ws).compile().as_text())
    full = 100 * 1024 * 1024 * 4
    # floor must be ~ 2x the data read once (slice read+write per step),
    # far below trips x full-array
    assert an["bytes_accessed"] < 4 * full
    assert an["bytes_accessed"] > 0.5 * full


def test_nested_scan_multiplies():
    def nested(x, ws):
        def outer(c, w):
            def inner(ci, wi):
                return jnp.tanh(ci @ wi), None
            ci, _ = jax.lax.scan(inner, c, w)
            return ci, None
        c, _ = jax.lax.scan(outer, x, ws)
        return c

    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 3, 64, 64), jnp.float32)
    an = analyze_hlo(jax.jit(nested).lower(x, ws).compile().as_text())
    assert an["flops"] == pytest.approx(15 * 2 * 32 * 64 * 64, rel=0.01)


def test_model_flops_conventions():
    t = model_flops("llama3-8b", "train_4k")
    assert t == pytest.approx(6 * 8.03e9 * 256 * 4096, rel=0.02)
    d = model_flops("llama3-8b", "decode_32k")
    assert d == pytest.approx(2 * 8.03e9 * 128, rel=0.02)
    m = model_flops("mixtral-8x7b", "train_4k")     # active, not total
    assert m < 6 * 46.7e9 * 256 * 4096 * 0.5
