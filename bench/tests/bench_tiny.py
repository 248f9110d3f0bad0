"""A copy of the benchmark with tiny cells added from new files only, for
the CPU tests.

The copy holds ``BENCHMARK.json``, ``bench/`` and a link to ``src/``.
Its tiny cells come from new configuration and traffic files, new
modules (an entry, a reference and a content kind) and new entries in
``BENCHMARK.json``; no file the benchmark has is edited, as a later
change that adds a cell would do it.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_CONFIGS = {
    "tiny_fixed": ("fixed1m", {"block_size": 16384}, {}),
    "tiny_gear": ("gearcdc", {"avg_chunk": 16384, "min_chunk": 8192,
                              "max_chunk": 65536}, {}),
    # a configuration whose plain reference is a new file
    "tiny_newref": ("fixed1m", {"block_size": 16384},
                    {"reference": "tiny_blocks"}),
}
TINY_TRAFFIC = {
    "tiny-bulk": ("bulk", {"streams": 2, "object_bytes": 65536,
                           "content": {"kind": "stamped",
                                       "stamp_every": 16384}}),
    "tiny-versions": ("ckpt-versions", {"streams": 2,
                                        "object_bytes": 262144}),
    # a mix whose entry and content kind are new files
    "tiny-newentry": ("bulk", {"streams": 2, "object_bytes": 40000,
                               "entry": "tiny_entry",
                               "content": {"kind": "tiny_random"}}),
}
TINY_CELLS = [("tiny_fixed.tiny-bulk", "tiny_fixed", "tiny-bulk"),
              ("tiny_gear.tiny-versions", "tiny_gear", "tiny-versions"),
              ("tiny_newref.tiny-newentry", "tiny_newref",
               "tiny-newentry")]
# cells that report the metrics of a cell of the benchmark
LIKE = {"tiny-bulk": "fixed1m.bulk", "tiny-newentry": "fixed1m.bulk",
        "tiny-versions": "gearcdc.ckpt-versions"}

# new modules, as a later change would add them
NEW_FILES = {
    "bench/drive_tiny_entry.py": '''
from bench import drive_sai


class Run(drive_sai.Run):
    def notes(self, win):
        return super().notes(win) + ["entry: tiny_entry"]
''',
    "bench/reference/tiny_blocks.py": '''
from bench.reference.digest import digests  # noqa: F401


def chunk_ends(data, sai):
    size, ends = int(sai["block_size"]), []
    while len(ends) * size + size < len(data):
        ends.append((len(ends) + 1) * size)
    return ends + [len(data)]


def block_bytes(sai, object_bytes):
    n = min(int(sai["block_size"]), int(object_bytes))
    return n, n
''',
    "bench/content/tiny_random.py": '''
from bench.generator import rng


class Source:
    keeps_bytes = False

    def __init__(self, traffic, seed):
        self.size, self.seed = int(traffic["object_bytes"]), int(seed)

    def obj(self, stream, k):
        return rng(self.seed, 9, stream, k).bytes(self.size)
''',
}

# the CPU runs the kernels in the Pallas interpreter: warm fewer shapes
RUNNER = """
import sys
sys.path.insert(0, {root!r})
from bench import warmup
warmup.MAX_ROWS = 4
from bench import run
sys.exit(run.main(sys.argv[1:], require_tpu=False))
"""


def make_copy(dest: Path) -> Path:
    """Copy the benchmark to ``dest`` and add the tiny cells."""
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    os.symlink(ROOT / "src", dest / "src")
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    for name, (base, sai, top) in TINY_CONFIGS.items():
        conf = json.loads((ROOT / "bench" / "configs"
                           / f"{base}.json").read_text())
        conf["name"] = name
        conf["sai"].update(sai)
        conf.update(top)
        path = dest / "bench" / "configs" / f"{name}.json"
        assert not path.exists()
        path.write_text(json.dumps(conf))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "CPU test"})
    for name, (base, over) in TINY_TRAFFIC.items():
        mix = json.loads((ROOT / "bench" / "traffic"
                          / f"{base}.json").read_text())
        mix.update(over)
        path = dest / "bench" / "traffic" / f"{name}.json"
        assert not path.exists()
        path.write_text(json.dumps(mix))
    for rel, text in NEW_FILES.items():
        assert not (dest / rel).exists()
        (dest / rel).write_text(text)
    for cell, conf, mix in TINY_CELLS:
        bench["workloads"].append({"name": cell, "config": conf,
                                   "traffic": mix, "chips": 1,
                                   "why": "CPU test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if LIKE[mix] in m.get("workloads", ()):
                m["workloads"].append(cell)
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


def run_cell(copy: Path, cell: str, seed: int, seconds: float = 2,
             trace: int = 0, fault=None, timeout: float = 600):
    """Run one cell of the copy on the CPU in a fresh process; returns
    (exit code, parsed last stdout line or None, stderr)."""
    args = ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
    if fault:
        args += ["--fault", fault]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"),
               PYTHONPATH=str(copy / "src"))
    proc = subprocess.run(
        [sys.executable, "-c", RUNNER.format(root=str(copy))] + args,
        cwd=copy, env=env, capture_output=True, text=True, timeout=timeout)
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1]) if lines else None
    return proc.returncode, result, proc.stderr
