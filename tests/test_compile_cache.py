"""The persistent compile cache goes where the environment says, else to
the repo's fixed ``.jax_cache`` directory (each case in a fresh process:
JAX's cache settings are process-wide)."""
import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = textwrap.dedent("""
    import json, sys
    import jax, jax.numpy as jnp
    from repro import compile_cache
    path = compile_cache.enable()
    if "--compile" in sys.argv:
        from repro.kernels import ops
        ops.gear_hash_batch_device(jnp.zeros((1, 32, 128), jnp.uint8))
    print(json.dumps({"path": path,
                      "config": jax.config.jax_compilation_cache_dir}))
""")


def _probe(env_dir, *args):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", PROBE, *args], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_receives_entries(tmp_path):
    got = _probe(tmp_path, "--compile")
    assert got["path"] == got["config"] == str(tmp_path)
    assert any(tmp_path.iterdir()), "no cache entry was written"


def test_default_is_the_repo_cache_dir():
    got = _probe(None)
    want = os.path.join(ROOT, ".jax_cache")
    assert got["path"] == got["config"] == want
    with open(os.path.join(ROOT, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()
