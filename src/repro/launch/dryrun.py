import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST run before any jax import: jax locks the device count on first init.

_DOC = """Multi-pod dry-run driver.

For every (architecture x input shape) cell this lowers + compiles the
appropriate step (train / prefill / decode) against the production mesh —
16x16 single-pod and 2x16x16 multi-pod — using ShapeDtypeStruct stand-ins
(no device allocation), then records:

  * ``compiled.memory_analysis()``  (per-device bytes: proves it fits)
  * ``compiled.cost_analysis()``    (HLO FLOPs / bytes for §Roofline)
  * collective byte totals parsed from ``compiled.as_text()`` (while-loop
    bodies scaled by trip count)

Results are written to ``results/dryrun/<arch>__<shape>__<mesh>.json`` so
the roofline analysis and EXPERIMENTS.md tables are reproducible.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all   # every cell
"""

import argparse
import json
import sys
import time
import traceback

import jax

import jax.numpy as jnp

from repro import compile_cache
from repro.configs import ARCH_NAMES, get_config, get_shape
from repro.launch.mesh import (auto_mesh, make_production_mesh,
                               make_shard_ctx)
from repro.models.model import build_model
from repro.models.sharding import zero1_spec
from repro.optim import make_optimizer, make_schedule
from repro.roofline.hlo_analysis import analyze_hlo
from repro.train.trainstep import make_train_step
from repro.serve.servestep import make_decode_step, make_prefill_step

from jax.sharding import NamedSharding, PartitionSpec as P

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "..", "..", "..", "results", "dryrun")


def input_specs(cfg, shape, ctx):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    model = build_model(cfg, ctx)
    B, S = shape.global_batch, shape.seq_len
    dp = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
    batch_spec = P(dp, None) if B > 1 else P(None, None)
    if shape.kind in ("train", "prefill"):
        F = cfg.frontend_embeds
        tokens = jax.ShapeDtypeStruct((B, S - F), jnp.int32)
        specs = {"tokens": tokens}
        shardings = {"tokens": NamedSharding(ctx.mesh, batch_spec)}
        if F:
            specs["embeds"] = jax.ShapeDtypeStruct(
                (B, F, cfg.d_model), jnp.bfloat16)
            eb = P(dp, None, None) if B > 1 else P(None, None, None)
            shardings["embeds"] = NamedSharding(ctx.mesh, eb)
        return specs, shardings
    # decode: one token against a cache of S
    cache = model.cache_shapes(B, S)
    cache_sh = jax.tree.map(lambda s: NamedSharding(ctx.mesh, s),
                            model.cache_pspecs(B),
                            is_leaf=lambda x: isinstance(x, P))
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    return ({"tokens": tokens, "cache": cache},
            {"tokens": NamedSharding(ctx.mesh, batch_spec),
             "cache": cache_sh})


def _named(ctx, tree_of_specs):
    return jax.tree.map(lambda s: NamedSharding(ctx.mesh, s),
                        tree_of_specs, is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, mesh_kind: str,
               zero1: bool = False, remat: str = "nothing_saveable",
               dp: int = 0, tp: int = 0, uneven: bool = False,
               score_dtype: str = "float32", microbatches: int = 1):
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    if dp and tp:
        # §Perf axis-rebalance variant: same chip count, different split
        if mesh_kind == "multi":
            mesh = auto_mesh((2, dp, tp), ("pod", "data", "model"))
        else:
            mesh = auto_mesh((dp, tp), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    ctx = make_shard_ctx(mesh)
    if uneven:
        import dataclasses as _dc
        ctx = _dc.replace(ctx, uneven=True)
    model = build_model(cfg, ctx, remat_policy=remat,
                        attn_score_dtype=score_dtype)
    pspecs = model.param_pspecs()
    psh = _named(ctx, pspecs)
    params_sds = model.param_shapes()

    specs, shardings = input_specs(cfg, shape, ctx)

    with mesh:
        if shape.kind == "train":
            lr_fn = make_schedule(cfg.lr_schedule, 3e-4, 10000)
            opt = make_optimizer(cfg.optimizer, lr_fn)
            opt_sds = jax.eval_shape(opt.init, params_sds)
            ospec = opt.state_spec_like(pspecs)
            if zero1:
                dp_size = 1
                for a in ctx.dp_axes:
                    dp_size *= mesh.shape[a]
                ospec = jax.tree.map(
                    lambda sp, sd: zero1_spec(sp, sd.shape, ctx.dp_axes,
                                              dp_size),
                    ospec, jax.eval_shape(opt.init, params_sds),
                    is_leaf=lambda x: isinstance(x, P))
            osh = _named(ctx, ospec)
            step_fn = make_train_step(model, opt,
                                      microbatches=microbatches)
            step_sds = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(
                step_fn,
                in_shardings=(psh, osh, shardings, None),
                out_shardings=(psh, osh, None),
                donate_argnums=(0, 1))
            lowered = jitted.lower(params_sds, opt_sds, specs, step_sds)
        elif shape.kind == "prefill":
            step_fn = make_prefill_step(model)
            args = [params_sds, specs["tokens"]]
            in_sh = [psh, shardings["tokens"]]
            if "embeds" in specs:
                args.append(specs["embeds"])
                in_sh.append(shardings["embeds"])
            jitted = jax.jit(step_fn, in_shardings=tuple(in_sh))
            lowered = jitted.lower(*args)
        else:  # decode
            step_fn = make_decode_step(model)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            jitted = jax.jit(
                step_fn,
                in_shardings=(psh, shardings["cache"], shardings["tokens"],
                              None),
                donate_argnums=(1,))
            lowered = jitted.lower(params_sds, specs["cache"],
                                   specs["tokens"], pos)
    return cfg, shape, mesh, lowered


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             zero1: bool = False, remat: str = "nothing_saveable",
             tag: str = "", dp: int = 0, tp: int = 0, uneven: bool = False,
             score_dtype: str = "float32", microbatches: int = 1) -> dict:
    t0 = time.time()
    cfg, shape, mesh, lowered = lower_cell(arch, shape_name, mesh_kind,
                                           zero1=zero1, remat=remat,
                                           dp=dp, tp=tp, uneven=uneven,
                                           score_dtype=score_dtype,
                                           microbatches=microbatches)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_d = {}
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                mem_d[k] = int(v)
    cost = compiled.cost_analysis() or {}
    cost_d = {k: float(v) for k, v in cost.items()
              if isinstance(v, (int, float)) and k in
              ("flops", "bytes accessed", "transcendentals",
               "optimal_seconds")}
    hlo = compiled.as_text()
    an = analyze_hlo(hlo)
    coll = {"wire_bytes": an["wire_bytes"], "op_counts": an["op_counts"],
            "total_wire_bytes": an["total_wire_bytes"]}
    n_dev = mesh.devices.size

    rec = dict(
        arch=arch, shape=shape_name, mesh=mesh_kind, zero1=zero1,
        remat=remat, kind=shape.kind, n_devices=int(n_dev),
        seq_len=shape.seq_len, global_batch=shape.global_batch,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory=mem_d, cost=cost_d, collectives=coll,
        flops_scaled=an["flops"], bytes_scaled=an["bytes_accessed"],
        bytes_upper=an["bytes_upper"],
        top_collectives=an["top_collectives"], top_bytes=an["top_bytes"],
        params=cfg.param_count(), active_params=cfg.active_param_count(),
        hlo_bytes=len(hlo),
    )
    # persist the HLO so analyzer improvements can re-derive terms without
    # recompiling
    hlo_dir = os.path.join(RESULTS_DIR, "..", "hlo")
    os.makedirs(hlo_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    import gzip
    with gzip.open(os.path.join(
            hlo_dir, f"{arch}__{shape_name}__{mesh_kind}{suffix}.hlo.gz"),
            "wt") as f:
        f.write(hlo)
    return rec


def save(rec: dict, tag: str = ""):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(
        RESULTS_DIR,
        f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return path


def all_cells():
    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        for shape in cfg.shapes():
            for mesh_kind in ("single", "multi"):
                yield arch, shape.name, mesh_kind


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--remat", default="nothing_saveable")
    ap.add_argument("--tag", default="")
    ap.add_argument("--dp", type=int, default=0)
    ap.add_argument("--tp", type=int, default=0)
    ap.add_argument("--uneven-heads", action="store_true")
    ap.add_argument("--score-dtype", default="float32")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    cells = list(all_cells()) if args.all else \
        [(args.arch, args.shape, args.mesh)]
    failures = 0
    for arch, shape, mesh_kind in cells:
        suffix = f"__{args.tag}" if args.tag else ""
        out = os.path.join(RESULTS_DIR,
                           f"{arch}__{shape}__{mesh_kind}{suffix}.json")
        if args.skip_existing and os.path.exists(out):
            print(f"[skip] {arch} {shape} {mesh_kind}")
            continue
        try:
            rec = run_cell(arch, shape, mesh_kind, zero1=args.zero1,
                           remat=args.remat, tag=args.tag, dp=args.dp,
                           tp=args.tp, uneven=args.uneven_heads,
                           score_dtype=args.score_dtype,
                           microbatches=args.microbatches)
            path = save(rec, args.tag)
            print(f"[ok] {arch} {shape} {mesh_kind} "
                  f"compile={rec['compile_s']}s flops={rec['cost'].get('flops')}"
                  f" -> {path}", flush=True)
        except Exception:
            failures += 1
            print(f"[FAIL] {arch} {shape} {mesh_kind}", flush=True)
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
