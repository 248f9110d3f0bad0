"""A tiny sharded-checkpoint cell, added from new files to a copy of the
benchmark (``bench_tiny.make_copy``), run on four forced host devices.

The tiny configuration keeps the shape of ``ckpt-hbm`` (a dense layer and
a MoE layer, experts held 2 of 8 per rank, one trained per rank) at small
widths, with 3 KiB blocks so that trained experts share blocks with
frozen ones; the tiny mix is ``frozen-base`` with the bytes per rank of
that layout.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import bench_tiny

ROOT = bench_tiny.ROOT
CELL = "tiny_ckpt.tiny-frozen"
TINY = {"hidden_size": 64, "num_attention_heads": 2, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 4, "v_head_dim": 8, "kv_lora_rank": 16,
        "vocab_size": 256, "intermediate_size": 96,
        "moe_intermediate_size": 32, "n_routed_experts": 8,
        "num_hidden_layers": 2}
BLOCK = 3072


def tiny_config() -> dict:
    conf = json.loads((ROOT / "bench/configs/ckpt-hbm.json").read_text())
    conf.update(TINY, name="tiny_ckpt")
    conf["sai"]["block_size"] = BLOCK
    return conf


def make_copy(dest: Path) -> Path:
    """``bench_tiny``'s copy plus the tiny checkpoint cell, which reports
    the metrics of ``ckpt-hbm.frozen-base``."""
    bench_tiny.make_copy(dest)
    sys.path.insert(0, str(ROOT))
    from bench.content.trainstate import layout
    conf = tiny_config()
    (dest / "bench/configs/tiny_ckpt.json").write_text(json.dumps(conf))
    mix = json.loads((ROOT / "bench/traffic/frozen-base.json").read_text())
    mix["object_bytes"] = sum(leaf.nbytes for leaf in layout(conf))
    (dest / "bench/traffic/tiny-frozen.json").write_text(json.dumps(mix))
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny_ckpt", "source": "test",
                             "file": "bench/configs/tiny_ckpt.json",
                             "reduced": ["num_hidden_layers"],
                             "why": "CPU test"})
    bench["workloads"].append({"name": CELL, "config": "tiny_ckpt",
                               "traffic": "tiny-frozen", "chips": 4,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "ckpt-hbm.frozen-base" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


def run_cell(copy: Path, seed: int, seconds: float = 2, trace: int = 0,
             fault=None, timeout: float = 600):
    """Run the tiny cell of the copy on four host devices in a fresh
    process; returns (exit code, parsed last stdout line or None,
    stderr)."""
    args = ["--workload", CELL, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    if fault:
        args += ["--fault", fault]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
               PYTHONPATH=str(copy / "src"))
    proc = subprocess.run(
        [sys.executable, "-c", bench_tiny.RUNNER.format(root=str(copy))]
        + args, cwd=copy, env=env, capture_output=True, text=True,
        timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines else None
    return proc.returncode, result, proc.stderr
