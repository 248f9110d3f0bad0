#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/``) and a traffic mix (``bench/traffic/``), and the
mix's ``entry`` names the module that drives it (``bench/drive_<e>.py``,
whose ``Run`` class this script drives).  The run builds the store from
the configuration, makes its data from ``--seed``,
warms every launch shape the mix uses, measures for ``--seconds``, and
then compares what the window produced with the plain references in
``bench/reference/``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device`` and, last,
``checks``: each number compared with its limit.  The same numbers are
the last lines of standard error.  Without a TPU, or with fewer chips
than the cell asks for, the run prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.time()        # set-up is timed from here

import argparse              # noqa: E402
import contextlib            # noqa: E402
import copy                  # noqa: E402
import json                  # noqa: E402
import shutil                # noqa: E402
import sys                   # noqa: E402
import tempfile              # noqa: E402
from pathlib import Path     # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCount:
    """Backend compiles, counted from JAX's monitoring events."""

    def __init__(self, jax):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_s, *args, **kwargs):
        if event == BACKEND_COMPILE:
            self.count += 1


class Tracing:
    """The profiler around the window (``--trace 1``), with the
    benchmark's own annotations around its calls into the program."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = None

    def annotate(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        if not self.on:
            yield
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            with self.annotate("bench/window"):
                yield
        finally:
            jax.profiler.stop_trace()

    def cleanup(self):
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None,
                    help="plant a fault (control runs and tests only)")
    return ap.parse_args(argv)


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def _plain(x):
    """A numpy scalar as the Python number JSON takes."""
    return x.item() if hasattr(x, "item") else x


def main(argv=None, require_tpu: bool = True) -> int:
    args = parse_args(argv)
    from bench import spec
    cell = spec.resolve(spec.load_benchmark(), args.workload)

    import jax
    devs = jax.devices()
    d0 = devs[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    if require_tpu and d0.platform != "tpu":
        log(f"bench/run.py needs a TPU; JAX found platform {d0.platform!r}")
        return 2
    if len(devs) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips; JAX found {len(devs)}")
        return 2
    devices = devs[:cell.chips]
    peaks = spec.peaks_for(d0.device_kind) if require_tpu else {}

    from repro import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    compiles = CompileCount(jax)
    from bench import faults, trace_reduce
    # the system is built from a copy of the configuration; the checks
    # hold it to the configuration as stated
    system_config = copy.deepcopy(cell.config)
    if args.fault:
        faults.apply(args.fault, system_config)
        log(f"FAULT PLANTED: {args.fault}")
    tracing = Tracing(bool(args.trace))
    entry = spec.module("", cell.traffic["entry"], prefix="drive_")
    run = entry.Run(cell, system_config, args.seed, args.seconds, devices,
                  tracing.annotate)
    try:
        run.setup()
        setup_s = time.time() - T_START
        c0 = compiles.count
        with tracing.window():
            win = run.window()
        window_compiles = compiles.count - c0
        device = {"platform": d0.platform, "kind": d0.device_kind,
                  "count": len(devices),
                  "memory_peak_bytes": memory_peak(devices)}
        ctx = run.context(win)
        ctx.update(setup_s=setup_s, peaks=peaks, trace=None,
                   kernels=spec.kernel_names())
        run.free_device()
        breakdown = None
        if tracing.on:
            red = trace_reduce.reduce_dir(tracing.dir, ctx["kernels"],
                                          n_devices=len(devices))
            tracing.cleanup()
            ctx["trace"] = red
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["top_ops"],
                         "idle_gaps": red["idle_gaps"]}
        checks = [(name, _plain(value), limit)
                  for name, value, limit in run.check()]
        attempted, failed = (int(n) for n in run.counts(win))
    finally:
        tracing.cleanup()
        run.close()

    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = m.read(ctx)
        if value is not None:
            metrics[m.name] = {"value": _plain(value), "unit": m.unit}
    for line in run.notes(win):
        log(line)
    log(f"compiles inside the window: {window_compiles}")
    correct = all(value <= limit for _, value, limit in checks)
    for name, value, limit in checks:
        log(f"check {name} = {value} (limit {limit})")
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
