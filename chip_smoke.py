#!/usr/bin/env python3
"""Smoke run of the storage system's hashing path on a TPU.

    python chip_smoke.py [--seed N]            # one chip: phases A-C
    python chip_smoke.py --chips 4 [--seed N]  # four chips: mesh phase only

One chip, through the entry points a user calls (``make_store``,
``SAI``/``SAIConfig``, ``CrystalTPU``, ``StorageGateway`` +
``GatewayServer`` + ``GatewayClient``), with data made from ``--seed``:

  mosaic     each kernel entry point compiles to a Mosaic custom call
  A          integrity: 1 GiB as 8 files of 128 MiB, fixed 1 MiB blocks,
             written with ``write_async`` over 4 nodes x 2 replicas, read
             back verified, every block digest equal to hashlib's
  B          content-addressable dedup over two versions of a checkpoint
             image: gear CDC on 256 MiB, sliding-window MD5 CDC on 32 MiB
             (its reference hashes every window with hashlib); chunk
             boundaries and similarity equal the cpu hasher's, both
             versions read back byte-identical
  C          serving: 4 client threads each write and read back 4 objects
             of 8 MiB over TCP with token auth; the engine fuses launches

``--chips 4`` runs only the engine mesh: a 256 x 1 MiB direct job and a
256 MiB gear stream sharded over four devices, against the same jobs on
one device and against hashlib / the cpu gear hash.

Each phase prints its bytes, wall seconds, engine jobs and launches,
compile seconds and every device's interpret flag (all must be False).
The last line is one JSON object: ``{"ok": true, "device": {...}}``.
The script exits non-zero without it when JAX finds no TPU, or when any
check or phase fails.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import threading
import time

MiB = 1 << 20
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Backend-compile seconds, summed from JAX's monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration_s, *args, **kwargs):
        if event == BACKEND_COMPILE:
            self.seconds += duration_s
            self.count += 1


def run_phase(name, fn, engine, clock):
    """Run one phase on ``engine`` and print what it did."""
    s0 = engine.snapshot_stats()
    c0, n0 = clock.seconds, clock.count
    t0 = time.perf_counter()
    nbytes = fn(engine)
    wall = time.perf_counter() - t0
    s1 = engine.snapshot_stats()
    interp = {i: bool(row["interpret"])
              for i, row in s1["per_device"].items()}
    check(not any(interp.values()), f"{name}: a device interprets: {interp}")
    log(f"phase {name}: ok bytes={nbytes} wall_s={wall:.3f} "
        f"jobs={s1['jobs'] - s0['jobs']} "
        f"launches={s1['launches'] - s0['launches']} "
        f"compile_s={clock.seconds - c0:.3f} compiles={clock.count - n0} "
        f"interpret={interp}")
    return s1


# ----------------------------------------------------------------------
# one chip
# ----------------------------------------------------------------------
def phase_mosaic(dev):
    """Every kernel entry point, lowered for ``dev``, is a Mosaic call."""
    import jax
    import numpy as np
    from repro.kernels import ops
    words = jax.device_put(np.zeros((8, 2048), np.uint32), dev)
    lens = jax.device_put(np.full((8,), 2048, np.int32), dev)
    lowered = {
        "direct": ops.direct_hash_device.lower(words, lens),
        "sliding": ops.sliding_hash_batch_device.lower(words, 12, (0,)),
        "gear": ops.gear_hash_batch_device.lower(words, version=1),
    }
    for name, low in lowered.items():
        check("tpu_custom_call" in low.compile().as_text(),
              f"mosaic: {name} kernel is not a Mosaic custom call")
    log(f"phase mosaic: ok kernels={sorted(lowered)}")


def phase_integrity(rng):
    from repro.core import SAI, SAIConfig, make_store
    from repro.core.sai import block_digest_cpu

    def run(engine):
        files = {f"/integrity/{i}": rng.bytes(128 * MiB) for i in range(8)}
        mgr, _ = make_store(n_nodes=4, replication=2)
        sai = SAI(mgr, SAIConfig(ca="fixed", block_size=MiB, hasher="tpu"),
                  engine)
        try:
            futs = [sai.write_async(p, b) for p, b in files.items()]
            for f in futs:
                f.result(timeout=900)
            for path, data in files.items():
                check(sai.read(path) == data, f"A: {path} read back differs")
                fv, _ = mgr.get_read_plan(path)
                want = [block_digest_cpu(data[o:o + MiB])
                        for o in range(0, len(data), MiB)]
                check([b.digest for b in fv.blocks] == want,
                      f"A: {path} block digests differ from hashlib")
        finally:
            sai.close()
        return sum(len(b) for b in files.values())
    return run


def phase_cdc(ca, size, seed, **cfg):
    from benchmarks.common import checkpoint_series
    from repro.core import SAI, SAIConfig, make_store

    def run(engine):
        v1, v2 = checkpoint_series(2, size, seed=seed)
        seen, wall = {}, {}
        for hasher in ("tpu", "cpu"):
            t0 = time.perf_counter()
            mgr, _ = make_store(n_nodes=4, replication=2)
            sai = SAI(mgr, SAIConfig(ca=ca, hasher=hasher, **cfg), engine)
            try:
                sai.write_async("/img", v1).result(timeout=900)
                st = sai.write_async("/img", v2).result(timeout=900)
                chunks = [[(b.digest, b.length)
                           for b in mgr.get_read_plan("/img", v)[0].blocks]
                          for v in (0, 1)]
                if hasher == "tpu":
                    check(sai.read("/img", version=0) == v1,
                          f"B/{ca}: version 1 read back differs")
                    check(sai.read("/img", version=1) == v2,
                          f"B/{ca}: version 2 read back differs")
            finally:
                sai.close()
            seen[hasher] = (chunks, st.similarity)
            wall[hasher] = time.perf_counter() - t0
        check(seen["tpu"][0] == seen["cpu"][0],
              f"B/{ca}: chunk boundaries differ from the cpu hasher")
        check(seen["tpu"][1] == seen["cpu"][1],
              f"B/{ca}: similarity differs from the cpu hasher")
        log(f"  {ca}: chunks={len(seen['tpu'][0][1])} "
            f"similarity={seen['tpu'][1]:.4f} "
            f"tpu_hasher_s={wall['tpu']:.3f} cpu_hasher_s={wall['cpu']:.3f}")
        return len(v1) + len(v2)
    return run


def phase_gateway(rng):
    from repro.core import SAIConfig, make_store
    from repro.serve import (GatewayClient, GatewayConfig, GatewayServer,
                             StorageGateway, TokenAuthenticator)
    n_clients, n_objects, size = 4, 4, 8 * MiB

    def run(engine):
        secrets = {f"t{i}": rng.bytes(16) for i in range(n_clients)}
        blobs = {(i, j): rng.bytes(size)
                 for i in range(n_clients) for j in range(n_objects)}
        mgr, _ = make_store(n_nodes=4, replication=2)
        gw = StorageGateway(mgr, engine=engine, config=GatewayConfig(
            sai=SAIConfig(ca="fixed", block_size=MiB, hasher="tpu"),
            auth=TokenAuthenticator(secrets),
            max_queued_bytes=n_objects * size))
        server = GatewayServer(gw, host="127.0.0.1", port=0)
        errors = []

        def client_main(i):
            try:
                c = GatewayClient(f"127.0.0.1:{server.address[1]}", f"t{i}",
                                  secret=secrets[f"t{i}"])
                pending = [c.submit_write(f"/c{i}/{j}", blobs[i, j])
                           for j in range(n_objects)]
                for p in pending:
                    p.result(900)
                for j in range(n_objects):
                    if c.read(f"/c{i}/{j}", timeout=900) != blobs[i, j]:
                        errors.append(f"client {i} object {j} differs")
                c.close()
            except Exception as e:      # reported below, fails the phase
                errors.append(f"client {i}: {e!r}")

        try:
            threads = [threading.Thread(target=client_main, args=(i,),
                                        daemon=True)
                       for i in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=1200)
            check(not any(t.is_alive() for t in threads),
                  "C: a client did not finish")
            check(not errors, f"C: {errors}")
            st = gw.snapshot_stats()
            check(st["launches"] < st["jobs"],
                  f"C: no fusion (launches={st['launches']} "
                  f"jobs={st['jobs']})")
        finally:
            server.close()
            gw.close()
        return 2 * n_clients * n_objects * size
    return run


def one_chip(jax, seed, clock):
    import numpy as np
    from repro.core import CrystalTPU
    dev = jax.devices()[0]
    phase_mosaic(dev)
    phases = [
        ("A", phase_integrity(np.random.default_rng([seed, 1])), {}),
        ("B/cdc-gear", phase_cdc("cdc-gear", 256 * MiB, seed), {}),
        ("B/cdc", phase_cdc("cdc", 32 * MiB, seed + 1, window=48,
                            stride=4), {}),
        ("C", phase_gateway(np.random.default_rng([seed, 3])),
         {"coalesce_window_s": 0.05}),
    ]
    for name, fn, engine_kw in phases:
        engine = CrystalTPU(devices=[dev], **engine_kw)
        try:
            run_phase(name, fn, engine, clock)
        finally:
            engine.shutdown()


# ----------------------------------------------------------------------
# four chips
# ----------------------------------------------------------------------
def four_chips(jax, seed, clock):
    import numpy as np
    from repro.core import CrystalTPU
    from repro.core.sai import _cpu_gear
    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    rng = np.random.default_rng([seed, 4])
    rows = np.frombuffer(rng.bytes(256 * MiB), np.uint8).reshape(256, MiB)
    lens = np.full((256,), MiB, np.int64)
    stream = np.frombuffer(rng.bytes(256 * MiB), np.uint8)
    want = {}

    def references():                   # numpy and hashlib drop the GIL:
        want["dig"] = np.stack([        # this overlaps the device runs
            np.frombuffer(hashlib.md5(r).digest(), np.uint8) for r in rows])
        want["gear"] = _cpu_gear(stream.tobytes())
    ref_thread = threading.Thread(target=references, daemon=True)
    ref_thread.start()
    got = {}
    for label, mesh in (("sharded", devs[:4]), ("single", devs[:1])):
        engine = CrystalTPU(devices=mesh)

        def run(eng):
            dj = eng.submit("direct", rows, {"lens": lens})
            gj = eng.submit("gear", stream)
            got[label] = (dj.wait(), gj.wait())
            return rows.nbytes + stream.nbytes
        try:
            st = run_phase(f"mesh/{label}", run, engine, clock)
        finally:
            engine.shutdown()
        if label == "sharded":
            per_dev = {i: row["launches"]
                       for i, row in st["per_device"].items()}
            log(f"  sharded_jobs={st['sharded_jobs']} shards={st['shards']} "
                f"launches_per_device={per_dev}")
            check(st["sharded_jobs"] == 2, "mesh: the whale jobs were not "
                  "sharded")
            check(len(per_dev) == 4 and all(per_dev.values()),
                  f"mesh: a device ran no launch: {per_dev}")
    ref_thread.join()
    (d4, g4), (d1, g1) = got["sharded"], got["single"]
    check(np.array_equal(d4, d1), "mesh: sharded digests differ from single")
    check(np.array_equal(g4, g1), "mesh: sharded gear differs from single")
    check(np.array_equal(d1, want["dig"]), "mesh: digests differ from hashlib")
    check(np.array_equal(g1[32:], want["gear"][32:]),
          "mesh: gear hashes differ from the cpu gear hash")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)}")
    if d0.platform != "tpu":
        log(f"chip_smoke: needs a TPU, JAX found platform {d0.platform!r}")
        return 2

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro import compile_cache
    log(f"compile cache: {compile_cache.enable()}")
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(jax, args.seed, clock)
    else:
        one_chip(jax, args.seed, clock)
    log(f"total: wall_s={time.perf_counter() - t0:.3f} "
        f"compile_s={clock.seconds:.3f} compiles={clock.count}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
