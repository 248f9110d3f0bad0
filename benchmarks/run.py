"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Select subsets with
``python -m benchmarks.run fig5 fig11``.  Pipeline-stage rows
(``.../stage_*`` from ``WriteStats.stage_s``) and engine
launch/coalesce counter rows (``.../engine_*``) ride along with their
figure's throughput rows so fused-launch regressions are visible in the
perf trajectory.  ``BENCH_SMOKE=1`` (the ``make bench-smoke`` CI target)
shrinks every module's sizes so the whole harness runs on each PR.

``BENCH_JSON=<path>`` additionally writes a machine-readable summary:
every CSV row, per-module pass/fail, and a flat ``counters`` map parsed
from the ``k=v`` pairs embedded in the derived column (engine/gateway
launch, coalesce, rejection counters ...) — the artifact CI uploads so
the perf trajectory is trackable PR-over-PR.
"""
from __future__ import annotations

import json
import os
import re
import sys
import traceback

MODULES = [
    "benchmarks.fig4_stages",
    "benchmarks.fig5_sliding",
    "benchmarks.fig6_direct",
    "benchmarks.fig7_10_workloads",
    "benchmarks.fig11_checkpoint",
    "benchmarks.read_path",
    "benchmarks.scrub_interference",
    "benchmarks.recovery",
    "benchmarks.gateway_saturation",
    "benchmarks.engine_mesh",
    "benchmarks.fig12_17_competing",
    "benchmarks.sec4_2_cpu_vs_accel",
    "benchmarks.kernel_roofline",
]

# k=v pairs are '_'-separated in derived strings and keys are
# lower_snake_case; anchoring at the separator keeps unit suffixes of
# the previous value (``0.5MBps_completed=4``) out of the key
_KV = re.compile(r"(?:^|_)([a-z]\w*)=(-?[0-9]+(?:\.[0-9]+)?)")


def _write_json(path: str, rows, modules) -> None:
    counters = {}
    for name, us, derived in rows:
        for key, val in _KV.findall(str(derived)):
            counters[f"{name}.{key}"] = float(val)
    # latency-distribution rows (gateway request p50/p95/p99, WAL fsync
    # percentiles) folded into their own block so dashboards don't have
    # to regex the row names back apart
    obs = {name: us for name, us, _ in rows
           if "/latency_p" in name or "/fsync_p" in name}
    summary = {
        "schema": 1,
        "smoke": os.environ.get("BENCH_SMOKE", "0") not in ("", "0"),
        "modules": modules,
        "rows": [{"name": n, "us_per_call": u, "derived": d}
                 for n, u, d in rows],
        "counters": counters,
        "obs": obs,
    }
    with open(path, "w") as fh:
        json.dump(summary, fh, indent=1, sort_keys=True)
        fh.write("\n")


def main() -> None:
    from repro import compile_cache
    compile_cache.enable()
    want = sys.argv[1:]
    print("name,us_per_call,derived")
    failed = 0
    all_rows = []
    modules = {}
    for modname in MODULES:
        short = modname.split(".")[-1]
        if want and not any(w in short for w in want):
            continue
        try:
            mod = __import__(modname, fromlist=["run"])
            for name, us, derived in mod.run():
                print(f"{name},{us:.1f},{derived}", flush=True)
                all_rows.append((name, us, derived))
            modules[short] = "ok"
        except Exception:
            failed += 1
            modules[short] = "error"
            print(f"{short},ERROR,see_stderr", flush=True)
            traceback.print_exc()
    json_path = os.environ.get("BENCH_JSON")
    if json_path:
        _write_json(json_path, all_rows, modules)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
