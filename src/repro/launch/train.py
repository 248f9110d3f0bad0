"""End-to-end training driver.

Examples:
  # ~65M-param llama3-family model, 200 steps, CA-checkpointing every 50
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
      --preset 100m --steps 200 --batch 8 --seq 256

  # tiny smoke for any assigned arch
  PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b \
      --preset smoke --steps 20

The driver wires every substrate together: config -> model -> optimizer ->
deterministic data pipeline -> jit'd train step -> TrainSupervisor (fault
tolerance + stragglers) -> content-addressable checkpointing with
accelerator-offloaded hashing (the paper's technique).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import get_config, get_smoke_config
from repro.core import CrystalTPU, SAI, SAIConfig, make_store
from repro.data import make_pipeline
from repro.models.model import build_model
from repro.optim import make_optimizer, make_schedule
from repro.train.checkpoint import CACheckpointer
from repro.train.fault import TrainSupervisor
from repro.train.trainstep import make_train_step


def preset_config(arch: str, preset: str):
    if preset == "full":
        return get_config(arch)
    if preset == "smoke":
        return get_smoke_config(arch)
    if preset == "100m":
        cfg = get_config(arch)
        moe = cfg.moe
        if moe is not None:
            moe = dataclasses.replace(moe, num_experts=min(
                8, moe.num_experts), top_k=2, d_ff_expert=512)
        ssm = cfg.ssm
        if ssm is not None:
            ssm = dataclasses.replace(ssm, state_dim=64, head_dim=32)
        period = cfg.hybrid_period or 1
        return dataclasses.replace(
            cfg, num_layers=max(16 // period, 1) * period, d_model=512,
            num_heads=8 if cfg.num_heads else 0,
            kv_heads=min(cfg.kv_heads, 4) if cfg.num_heads else 0,
            head_dim=64 if cfg.num_heads else 0,
            d_ff=2048 if cfg.d_ff else 0,
            vocab_size=32768, moe=moe, ssm=ssm,
            frontend_embeds=min(cfg.frontend_embeds, 16),
            param_dtype="float32", compute_dtype="float32")
    raise ValueError(preset)


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--preset", default="100m",
                    choices=["smoke", "100m", "full"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-chunking", default="cdc-gear",
                    choices=["fixed", "cdc", "cdc-gear"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="inject one failure at this step (fault demo)")
    args = ap.parse_args()

    cfg = preset_config(args.arch, args.preset)
    model = build_model(cfg)
    print(f"arch={cfg.name} preset={args.preset} "
          f"params={cfg.param_count()/1e6:.1f}M")

    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng)
    lr_fn = make_schedule(cfg.lr_schedule, args.lr, args.steps)
    opt = make_optimizer(cfg.optimizer, lr_fn)
    opt_state = opt.init(params)

    pipeline = make_pipeline(cfg, args.seq, args.batch, seed=args.seed)
    step_fn = jax.jit(make_train_step(model, opt,
                                      microbatches=args.microbatches),
                      donate_argnums=(0, 1))

    # content-addressable checkpoint store (the paper's technique)
    mgr, _ = make_store(n_nodes=4, replication=2)
    crystal = CrystalTPU()
    sai = SAI(mgr, SAIConfig(ca=args.ckpt_chunking, avg_chunk=256 << 10,
                             min_chunk=64 << 10, max_chunk=1 << 20,
                             hasher="tpu"), crystal)
    ckpt = CACheckpointer(sai)

    fail = {args.fail_at: 1} if args.fail_at >= 0 else None
    sup = TrainSupervisor(step_fn, pipeline, ckpt,
                          ckpt_every=args.ckpt_every,
                          fail_at_steps=fail)
    t0 = time.time()
    params, opt_state = sup.run(params, opt_state, 0, args.steps)
    wall = time.time() - t0

    losses = [r["loss"] for r in sup.log]
    print(f"steps={len(sup.log)} wall={wall:.1f}s "
          f"first_loss={losses[0]:.4f} last_loss={losses[-1]:.4f}")
    print(f"restarts={sup.restarts} stragglers={len(sup.stragglers)}")
    tok_s = args.batch * args.seq * len(sup.log) / wall
    print(f"throughput={tok_s:.0f} tok/s (CPU container)")
    for rec in ckpt.history:
        print(f"  ckpt step={rec['step']:4d} total={rec['total_bytes']/1e6:.1f}MB "
              f"new={rec['new_bytes']/1e6:.1f}MB "
              f"dedup={100*rec['dedup_ratio']:.1f}% "
              f"wall={rec['wall_s']:.2f}s")
    print("store:", json.dumps(mgr.stats()))
    crystal.shutdown()
    assert losses[-1] < losses[0], "loss must decrease"


if __name__ == "__main__":
    main()
