"""Batched serving driver: prefill a batch of prompts, decode new tokens.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b \
      --preset 100m --batch 4 --prompt-len 64 --new-tokens 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.launch.train import preset_config
from repro.models.model import build_model


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--preset", default="100m",
                    choices=["smoke", "100m", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = preset_config(args.arch, args.preset)
    model = build_model(cfg)
    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng)
    B, S = args.batch, args.prompt_len
    prompts = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)

    capacity = model.capacity_for(S + args.new_tokens)
    prefill = jax.jit(lambda p, t: model.prefill(p, t, capacity=capacity))
    decode = jax.jit(model.decode_step, donate_argnums=(1,))

    jax.block_until_ready(prefill(params, prompts))     # compile warmup
    t0 = time.time()
    cache, logits = prefill(params, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    tok = jnp.argmax(logits, axis=-1)[:, None]
    out = [tok]
    t0 = time.time()
    for i in range(args.new_tokens - 1):
        pos = jnp.asarray(S + i, jnp.int32)
        cache, logits = decode(params, cache, tok, pos)
        tok = jnp.argmax(logits, axis=-1)[:, None]
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    toks = jnp.concatenate(out, axis=1)
    print(f"arch={cfg.name} batch={B} prompt={S} new={args.new_tokens}")
    print(f"prefill: {t_prefill*1e3:.1f} ms "
          f"({B*S/t_prefill:.0f} tok/s)")
    print(f"decode:  {t_decode*1e3:.1f} ms "
          f"({B*(args.new_tokens-1)/max(t_decode,1e-9):.0f} tok/s)")
    print("sample continuation:", toks[0, :16].tolist())


if __name__ == "__main__":
    main()
