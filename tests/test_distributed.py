"""Distributed behaviours that need >1 device: run in a subprocess with
forced host devices so the main pytest process keeps 1 device."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_runs_small_mesh():
    """A real sharded train step (4x2 mesh) runs and matches the
    single-device step numerically."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke_config
        from repro.models.model import build_model
        from repro.models.sharding import ShardCtx
        from repro.optim import make_optimizer, make_schedule
        from repro.train.trainstep import make_train_step

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        ctx = ShardCtx(mesh=mesh, dp_axes=("data",))
        cfg = get_smoke_config("llama3-8b")
        model_s = build_model(cfg, ctx)
        model_1 = build_model(cfg)
        params = model_1.init(jax.random.PRNGKey(0))
        opt = make_optimizer("adamw", make_schedule("cosine", 1e-3, 10))
        ostate = opt.init(params)
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab_size)}

        # single device
        s1 = jax.jit(make_train_step(model_1, opt))
        p1, o1, m1 = s1(params, ostate, batch, jnp.int32(0))

        # sharded
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           model_s.param_pspecs(),
                           is_leaf=lambda x: isinstance(x, P))
        osh = opt.state_spec_like(psh)
        params_s = jax.device_put(params, psh)
        ostate_s = jax.device_put(ostate, osh)
        batch_s = jax.device_put(
            batch, {"tokens": NamedSharding(mesh, P("data", None))})
        with mesh:
            s2 = jax.jit(make_train_step(model_s, opt),
                         in_shardings=(psh, osh,
                                       {"tokens": NamedSharding(
                                           mesh, P("data", None))}, None))
            p2, o2, m2 = s2(params_s, ostate_s, batch_s, jnp.int32(0))
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4, (
            float(m1["loss"]), float(m2["loss"]))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=3e-3)
        print("SHARDED_OK", float(m1["loss"]), float(m2["loss"]))
    """)
    out = _run(code)
    assert "SHARDED_OK" in out


def test_grad_compression_cross_pod():
    """int8 compressed psum across a 'pod' axis approximates the mean and
    error feedback keeps the bias bounded over steps."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.grad_compress import (make_cross_pod_sync,
                                               init_error_state)
        mesh = jax.make_mesh((2, 4), ("pod", "data"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        specs = {"w": P(None, None)}
        sync = make_cross_pod_sync(mesh, specs)
        rng = np.random.default_rng(0)
        accum_true = np.zeros((8, 16), np.float32)
        accum_q = np.zeros((8, 16), np.float32)
        err = init_error_state(
            {"w": jax.ShapeDtypeStruct((8, 16), jnp.float32)})
        for step in range(20):
            g = rng.standard_normal((8, 16)).astype(np.float32)
            grads = {"w": jnp.asarray(g)}
            out, err = sync(grads, err)
            accum_true += g            # pods hold identical grads here
            accum_q += np.asarray(out["w"])
        rel = np.abs(accum_q - accum_true).max() / np.abs(
            accum_true).max()
        assert rel < 0.05, rel
        print("COMPRESS_OK", rel)
    """)
    out = _run(code)
    assert "COMPRESS_OK" in out


def test_production_mesh_shapes():
    code = textwrap.dedent("""
        from repro.launch.mesh import make_production_mesh, make_shard_ctx
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        assert m1.shape == {"data": 16, "model": 16}
        assert m2.shape == {"pod": 2, "data": 16, "model": 16}
        ctx = make_shard_ctx(m2)
        assert ctx.dp_axes == ("pod", "data")
        print("MESH_OK")
    """)
    out = _run(code, devices=512)
    assert "MESH_OK" in out
