"""BENCHMARK.json against the format and limits it must keep, and every
cell and metric resolved to the files that define it."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(Path(__file__).resolve().parent))
import bench_tiny  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
LAYER = {m["name"]: m for m in BENCH["per_layer"]}


def _line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert isinstance(BENCH["run_seconds"], int)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 << 10


def test_names_units_and_lines():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"]) and m["moves"] in E2E
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [x["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in BENCH[key]]
    assert len(names) == len(set(names))
    assert "setup_s" in E2E


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_and_reports_enough(cell):
    c = spec.resolve(BENCH, cell)
    e2e = [m.name for m in c.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        # a per-layer metric's cells all report the metric it moves
        assert LAYER[m.name]["moves"] in e2e
    entry = spec.module("", c.traffic["entry"], prefix="drive_")
    entry.check_config(c.config)
    ref = spec.module("reference", c.config["reference"])
    lo, hi = ref.block_bytes(c.config["sai"], c.traffic["object_bytes"])
    assert 0 < lo <= hi
    assert spec.module("content", c.traffic["content"]["kind"]).Source


@pytest.mark.parametrize("name", sorted(E2E) + sorted(LAYER))
def test_metric_has_a_reader(name):
    sub = "end_to_end" if name in E2E else "layer_metrics"
    assert callable(spec.load_reader(ROOT / "bench" / sub / f"{name}.py"))


def test_configs_state_source_guarantees_and_cuts():
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert _line(conf["source"]) and conf["guarantees"]
        assert conf["reduced"] == c["reduced"] and "assumed" in conf
        assert conf["store"]["replication"] >= 1


def test_unknown_device_is_an_error():
    assert spec.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks_for("cpu")


def test_cell_added_from_new_files_only(tmp_path):
    copy = bench_tiny.make_copy(tmp_path)
    for rel in [c["file"] for c in BENCH["configs"]] + [
            f"bench/traffic/{w['traffic']}.json" for w in BENCH["workloads"]]:
        assert (copy / rel).read_bytes() == (ROOT / rel).read_bytes()
    for rel in bench_tiny.NEW_FILES:
        assert not (ROOT / rel).exists()
    bench = spec.load_benchmark(copy)
    for cell, _, _ in bench_tiny.TINY_CELLS:
        c = spec.resolve(bench, cell, root=copy)
        assert c.config["name"].startswith("tiny_")
        assert c.end_to_end and c.per_layer


@pytest.mark.parametrize("change,match", [
    (lambda c: c["store"].update(durable=True), "durable"),
    (lambda c: c["store"].update(fsync=True), "not honoured"),
    (lambda c: c.update(wal_dir="x"), "not honoured"),
])
def test_config_the_entry_cannot_honour_is_refused(change, match):
    from bench import drive_sai
    conf = json.loads((ROOT / "bench/configs/fixed1m.json").read_text())
    drive_sai.check_config(conf)
    change(conf)
    with pytest.raises(ValueError, match=match):
        drive_sai.check_config(conf)


def test_names_in_data_cannot_leave_the_benchmark():
    with pytest.raises(ValueError):
        spec.module("reference", "../src")


def _run_cli(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fixed1m.bulk",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu_and_names_it():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "platform 'cpu'" in proc.stderr


def test_run_fails_with_only_the_benchmark(tmp_path):
    """Without the program beside it the run fails and prints no
    result, even with the chip check skipped."""
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, "-c", bench_tiny.RUNNER.format(root=str(tmp_path)),
         "--workload", "fixed1m.bulk", "--seed", "1", "--seconds", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "repro" in proc.stderr
