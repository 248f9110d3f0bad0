"""Kernel-level roofline for the hashing kernels (the paper-technique
§Perf hillclimb's measurement harness).

VPU-op counts are MEASURED from the compiled HLO via the repo's analyzer
(XLA's 'flops' metric ignores most integer ops); the v5e projection is
peak-int-ops / measured-ops-per-byte vs the HBM streaming bound."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import V5E_HBM_BW, V5E_INT_OPS, synth_data
from repro.roofline.hlo_analysis import analyze_hlo


def run() -> list:
    rows: list = []
    size = 512 << 10   # 128 x 4KB segments = full lane tile
    buf = np.frombuffer(synth_data(size), np.uint8)
    words = jnp.asarray(buf.view("<u4"))

    from repro.kernels.ops import (direct_hash_device,
                                   gear_hash_batch_device,
                                   sliding_hash_batch_device)
    segs = jnp.asarray(np.ascontiguousarray(buf.reshape(-1, 4096)).view(
        "<u4"))
    lens = jnp.full((segs.shape[0],), segs.shape[1], jnp.int32)

    batch = words[None]                # B=1 row of the fused entry points
    rows = jnp.asarray(buf.reshape(1, -1, 128))     # gear: one byte each
    cases = [
        ("sliding_md5_stride1", sliding_hash_batch_device.lower(
            batch, w_words=12, phases=(0, 1, 2, 3))),
        ("sliding_md5_stride4", sliding_hash_batch_device.lower(
            batch, w_words=12, phases=(0,))),
        ("gear_v1", gear_hash_batch_device.lower(rows, version=1)),
        ("gear_v2_doubling", gear_hash_batch_device.lower(rows, version=2)),
        ("gear_v3_hybrid", gear_hash_batch_device.lower(rows, version=3)),
        ("direct_md5_4k", direct_hash_device.lower(segs, lens)),
    ]
    for name, lowered in cases:
        an = analyze_hlo(lowered.compile().as_text())
        opb = an["int_ops"] / size
        t_comp = opb / V5E_INT_OPS                 # s/byte compute
        t_mem = 1.0 / V5E_HBM_BW                   # s/byte stream
        bound = "vpu" if t_comp > t_mem else "hbm"
        thr = 1.0 / max(t_comp, t_mem)
        rows.append((f"kernel_roofline/{name}", 1e6 * size * max(t_comp,
                                                                 t_mem),
                     f"opsPerByte={opb:.1f}_v5e={thr/1e6:.0f}MBps_"
                     f"bound={bound}"))
    return rows
