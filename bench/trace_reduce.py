"""Reduce a profiler trace of the window to the numbers the per-layer
metrics and the breakdown read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
In it, each chip is a plane named ``/device:TPU:<i>``: its ``XLA Ops``
line holds one event per device operation, named by its HLO text, and
its ``XLA Modules`` line one event per program run, named after the
jitted function (``jit_<name>(<hash>)``).  Host threads are lines of
the ``/host:CPU`` plane, where the benchmark's own annotations
(``bench/...``; ``bench/window`` spans the window) sit beside the
runtime's.  All planes share one clock.

* busy: the union of a chip's operation intervals inside the window,
  averaged over the cell's chips; idle share = 1 - busy / window.
* kernel time: the summed device time, inside the window and over the
  cell's chips, of the operations that a kernel's entry in
  ``bench/kernels.json`` names: an operation whose HLO text holds
  ``op`` inside a program whose name holds ``module``.  A Pallas kernel
  is a ``tpu_custom_call``; its own name is not in the trace.
* top operations: device time by program and operation.
* idle gaps on the first chip, each labelled by the host event that
  overlaps it most, else "unattributed".  The benchmark's own spans
  that only wait (``bench/wait...``) and the window label nothing.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

WINDOW = "bench/window"
WAITS = "bench/wait"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def load(trace_dir: str):
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(paths[-1])


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, ev


def op_label(module: str, op: str) -> str:
    """``jit_f:%name`` (plus `` tpu_custom_call`` for a kernel) from a
    program name and an operation's HLO text."""
    short = op.split(" = ", 1)[0]
    if "tpu_custom_call" in op:
        short += " tpu_custom_call"
    return f"{module.split('(', 1)[0]}:{short}"


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def reduce(pd, kernels: Dict[str, Dict[str, str]], n_devices: int) -> Dict:
    """``pd`` is a ``jax.profiler.ProfileData`` (or anything with the
    same planes/lines/events shape)."""
    host: List[Tuple[str, float, float]] = []
    devices: Dict[int, object] = {}
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            devices[int(m.group(1))] = plane
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, a, b, _ in _events(line):
                    if b > a:
                        host.append((name, a, b))
    win = [(a, b) for name, a, b in host if name == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    w0, w1 = win[0]
    used = sorted(devices)[:n_devices]       # none on a CPU
    busy_ns = []
    kernel_ns = defaultdict(float)
    op_ns = defaultdict(float)
    first_busy: List[Tuple[float, float]] = []
    for i in used:
        spans = []
        lines = {line.name: line for line in devices[i].lines}
        modules = sorted((a, b, name) for name, a, b, _ in
                         _events(lines[MODULES_LINE])) \
            if MODULES_LINE in lines else []
        starts = [m[0] for m in modules]
        ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else ()
        for name, a, b, _ in ops:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            spans.append((a, b))
            k = bisect.bisect_right(starts, a) - 1
            module = modules[k][2] if k >= 0 and a < modules[k][1] else ""
            op_ns[op_label(module, name)] += b - a
            for kernel, sel in kernels.items():
                if sel["module"] in module and sel["op"] in name:
                    kernel_ns[kernel] += b - a
        merged = _union(spans)
        busy_ns.append(sum(b - a for a, b in merged))
        if i == used[0]:
            first_busy = merged
    gaps = []
    prev = w0
    for a, b in first_busy + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    labelled = [[_label(host, a, b), (b - a) / 1e9] for a, b in gaps]
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": (sum(busy_ns) / len(busy_ns) / 1e9) if busy_ns else 0.0,
        "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
        "top_ops": [[name, ns / 1e9] for name, ns in top],
        "idle_gaps": labelled,
        "devices": len(used),
    }


def _label(host, a: float, b: float) -> str:
    best, best_ns = "unattributed", 0.0
    for name, ha, hb in host:
        if name == WINDOW or name.startswith(WAITS):
            continue
        ov = min(b, hb) - max(a, ha)
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def reduce_dir(trace_dir: str, kernels: Dict[str, Dict[str, str]],
               n_devices: int) -> Dict:
    return reduce(load(trace_dir), kernels, n_devices)
