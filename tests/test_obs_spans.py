"""Program spans on the JAX profiler's clock (``repro.obs.span``).

A traced ``SAI.write_async`` puts the engine's five launch phases and the
SAI's pack/select/split/store spans on the profiler's host plane with
their ids; the phases of one launch tile its wall time; with the
profiler off a span records nothing and formats nothing; and
``repro.obs`` imports without JAX."""
from __future__ import annotations

import glob
import os
import subprocess
import sys
import textwrap
import time
import warnings
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import SAI, CrystalTPU, SAIConfig, make_store
from repro.core.crystal import PHASES
from repro.obs import Trace, span

SRC = Path(__file__).resolve().parents[1] / "src"


def _host_events(trace_dir):
    """(name, start_ns, end_ns, stats) of every event on the host plane."""
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    with warnings.catch_warnings():     # the stats type's own warning
        warnings.simplefilter("ignore", DeprecationWarning)
        return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                 dict(ev.stats))
                for plane in pd.planes if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events]


def _profile(trace_dir, fn):
    """Run ``fn`` under the profiler; returns (its result, host events,
    perf_counter -> profiler clock offset bounds in ns)."""
    marks = []
    jax.profiler.start_trace(str(trace_dir))
    try:
        for _ in range(8):
            a = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation("test/mark"):
                b = time.perf_counter_ns()
            marks.append((a, b))
        out = fn()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(trace_dir)
    starts = sorted(e[1] for e in events if e[0] == "test/mark")
    # each mark began between a and b on perf_counter: offset in
    # [start - b, start - a]; keep the tightest
    a, b = min(marks, key=lambda m: m[1] - m[0])
    k = marks.index((a, b))
    return out, events, (starts[k] - b, starts[k] - a)


def _names(events):
    return {e[0] for e in events}


@pytest.mark.parametrize("ca,kinds,sai_spans", [
    ("fixed", ("direct",), {"sai/pack", "chunk/split", "sai/store"}),
    ("cdc-gear", ("direct", "gear"),
     {"sai/pack", "chunk/select", "chunk/split", "sai/store"}),
])
def test_traced_write_async_puts_program_spans_on_the_host_plane(
        tmp_path, rng, ca, kinds, sai_spans):
    eng = CrystalTPU(devices=jax.devices()[:1])
    mgr, _ = make_store(4, replication=2)
    sai = SAI(mgr, SAIConfig(ca=ca, block_size=16384, avg_chunk=16384,
                             min_chunk=8192, max_chunk=65536), eng)
    data = [rng.integers(0, 256, 65536, dtype=np.uint8).tobytes()
            for _ in range(2)]
    try:
        sai.write_async("/warm", data[0]).result(timeout=300)  # compiles
        _, events, _ = _profile(
            tmp_path, lambda: sai.write_async("/f", data[1]).result(
                timeout=300))
    finally:
        sai.close()
        eng.shutdown()
    names = _names(events)
    for kind in kinds:
        assert {f"engine/{p}:{kind}" for p in PHASES} <= names, names
    assert sai_spans <= names, names
    engine = [e for e in events if e[0].startswith("engine/")]
    assert all({"device", "launch", "rows", "padded_bytes", "seq"}
               <= set(e[3]) for e in engine)
    sai_events = [e for e in events if e[0] in sai_spans]
    assert all("write" in e[3] for e in sai_events)
    # sai/pack names the jobs it submitted; a launch ran each of them
    launched = {str(s) for e in engine for s in str(e[3]["seq"]).split()}
    packed = {str(e[3]["seq"]) for e in events if e[0] == "sai/pack"}
    assert packed and packed <= launched


@pytest.mark.parametrize("kind", ["direct", "gear"])
def test_phases_of_one_launch_tile_its_wall_time(tmp_path, rng, kind):
    eng = CrystalTPU(devices=jax.devices()[:1])
    data = rng.integers(0, 256, (4, 4096) if kind == "direct" else 8192,
                        dtype=np.uint8)
    try:
        eng.submit(kind, data, {}).wait()                      # compiles

        def one_launch():
            job = eng.submit(kind, data, {})
            job.wait()
            return job

        job, events, (off_lo, off_hi) = _profile(tmp_path, one_launch)
    finally:
        eng.shutdown()
    phases = sorted((e for e in events if e[0].startswith("engine/")
                     and str(job.seq) in str(e[3]["seq"]).split()),
                    key=lambda e: e[1])
    assert [e[0] for e in phases] == [f"engine/{p}:{kind}" for p in PHASES]
    for (_, _, end, _), (_, start, _, _) in zip(phases, phases[1:]):
        assert end <= start                       # no two overlap
    assert len({(e[3]["device"], e[3]["launch"]) for e in phases}) == 1
    # inside [wall0, wall1] on the profiler's clock, up to how well the
    # two clocks could be aligned
    wall0 = int(job.t_exec0 * 1e9)
    wall1 = int(job.t_exec1 * 1e9)
    assert phases[0][1] >= wall0 + off_lo
    assert phases[-1][2] <= wall1 + off_hi
    assert set(job.timings) == set(PHASES)
    assert sum(job.timings.values()) <= job.t_exec1 - job.t_exec0


class _Probe:
    """An id that counts how often it is formatted."""
    formatted = 0

    def __str__(self):
        _Probe.formatted += 1
        return "probe"

    __repr__ = __str__


def test_span_with_the_profiler_off_records_and_formats_nothing(tmp_path):
    _Probe.formatted = 0
    probe = _Probe()
    with span("test/off", ids=[probe]) as sp:
        pass
    assert sp.duration_s >= 0.0 and _Probe.formatted == 0
    opened_off = span("test/off-then-on", ids=[probe])
    opened_off.__enter__()             # the profiler starts inside it

    def body():
        with span("test/on", ids=[probe], n=3):
            pass
        opened_off.__exit__(None, None, None)

    _, events, _ = _profile(tmp_path, body)
    on = [e for e in events if e[0] == "test/on"]
    assert len(on) == 1 and on[0][3] == {"ids": "probe", "n": 3}
    assert not {"test/off", "test/off-then-on"} & _names(events)
    assert _Probe.formatted == 1              # by the span that recorded


def test_span_feeds_a_request_trace():
    trace = Trace(7, "write")
    with span("sai/pack", trace, write=2) as sp:
        sp.set(seq=11)
    (got,) = trace.spans
    assert (got.name, got.t0, got.t1) == ("sai/pack", sp.t0, sp.t1)
    assert got.meta == {"write": 2, "seq": 11}


def test_obs_imports_and_spans_without_jax():
    code = textwrap.dedent("""
        import sys

        class Block:
            def find_spec(self, name, path=None, target=None):
                if name.split(".")[0] in ("jax", "jaxlib"):
                    raise ImportError("blocked: " + name)
                return None

        sys.meta_path.insert(0, Block())
        import repro.obs
        from repro.obs import Trace, span
        trace = Trace(1, "write")
        with span("sai/store", trace, write=3):
            pass
        assert [s.meta for s in trace.spans] == [{"write": 3}]
        assert "jax" not in sys.modules
        print("ok")
    """)
    env = dict(os.environ, PYTHONPATH=str(SRC))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "ok"
