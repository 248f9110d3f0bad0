"""``bench/trace_reduce.py`` on a synthetic trace with known answers and
on a trace recorded on a TPU v5e (``data/bulk_window.xplane.pb``: an
8-second window of ``fixed1m.bulk``)."""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import spec, trace_reduce

DATA = Path(__file__).resolve().parent / "data"
KERNELS = spec.kernel_names()
CUSTOM = 'custom-call(u32[8]), custom_call_target="tpu_custom_call"'


def _line(name, events):
    return NS(name=name, events=[NS(name=n, start_ns=a, duration_ns=b - a)
                                 for n, a, b in events])


def _synthetic():
    host = NS(name="/host:CPU", lines=[
        _line("python3", [("bench/window", 0, 100),
                          ("bench/wait_ack", 0, 100),
                          ("shard_args", 10, 30)]),
        _line("pjrt", [("XlaLinearize", 44, 58)])])
    dev = NS(name="/device:TPU:0", lines=[
        _line("XLA Modules", [("jit_direct_hash_device(1)", 5, 50),
                              ("jit_gear_hash_batch_device(2)", 60, 90),
                              ("jit_other(3)", 140, 170)]),
        _line("XLA Ops", [(f"%k = u32[4] {CUSTOM}", 10, 40),
                          ("%copy = u32[4] copy(u32[4] %x)", 35, 45),
                          (f"%g = u32[4] {CUSTOM}", 60, 80),
                          (f"%late = u32[4] {CUSTOM}", 150, 160)])])
    other = NS(name="/device:TPU:1", lines=[
        _line("XLA Ops", [("%copy = u32[4] copy(u32[4] %x)", 0, 100)])])
    return NS(planes=[host, dev, other])


def test_synthetic_trace_reduces_exactly():
    red = trace_reduce.reduce(_synthetic(), KERNELS, n_devices=1)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(55e-9)     # [10,45] + [60,80]
    assert red["kernel_s"] == {"md5": pytest.approx(30e-9),
                               "gear": pytest.approx(20e-9)}
    assert red["devices"] == 1
    assert [g[0] for g in red["idle_gaps"]] == [
        "unattributed", "XlaLinearize", "unattributed"]
    assert [round(g[1] * 1e9) for g in red["idle_gaps"]] == [20, 15, 10]
    assert red["top_ops"][0] == [
        "jit_direct_hash_device:%k tpu_custom_call", pytest.approx(30e-9)]


def test_busy_averages_over_the_cells_chips():
    red = trace_reduce.reduce(_synthetic(), KERNELS, n_devices=2)
    assert red["busy_s"] == pytest.approx((55e-9 + 100e-9) / 2)


def test_no_window_annotation_is_an_error():
    pd = _synthetic()
    pd.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError):
        trace_reduce.reduce(pd, KERNELS, n_devices=1)


@pytest.fixture(scope="module")
def recorded():
    return trace_reduce.load(str(DATA))


def test_recorded_chip_trace(recorded):
    red = trace_reduce.reduce(recorded, KERNELS, n_devices=1)
    # what the reduction read from this trace when it was recorded
    assert red["window_s"] == pytest.approx(10.672201647, rel=1e-9)
    assert red["busy_s"] == pytest.approx(1.648349419, rel=1e-9)
    assert red["kernel_s"] == {"md5": pytest.approx(1.562423808, rel=1e-9)}
    # the same numbers worked out another way: a 1 us busy mask
    dev = next(p for p in recorded.planes if p.name == "/device:TPU:0")
    host = next(p for p in recorded.planes if p.name == "/host:CPU")
    w0, w1 = next((e.start_ns, e.start_ns + e.duration_ns)
                  for line in host.lines for e in line.events
                  if e.name == "bench/window")
    ops = [e for line in dev.lines if line.name == "XLA Ops"
           for e in line.events]
    mask = np.zeros(int((w1 - w0) / 1000) + 1, bool)
    kernel = 0.0
    for e in ops:
        a = max(e.start_ns, w0)
        b = min(e.start_ns + e.duration_ns, w1)
        if b > a:
            mask[int((a - w0) / 1000):int(np.ceil((b - w0) / 1000))] = True
            if "tpu_custom_call" in e.name:
                kernel += b - a
    assert red["busy_s"] == pytest.approx(mask.sum() * 1e-6, rel=1e-3)
    assert red["kernel_s"]["md5"] == pytest.approx(kernel / 1e9, rel=1e-9)
    gaps = [g[1] for g in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) == 10
    assert sum(gaps) <= red["window_s"] - red["busy_s"] + 1e-9
    assert all(label != "bench/window" and not label.startswith(
        "bench/wait") for label, _ in red["idle_gaps"])
