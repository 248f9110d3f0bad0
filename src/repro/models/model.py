"""Composable decoder-LM covering all 10 assigned architectures.

The layer stack is expressed as a repeating *pattern* of ``period`` sub-
layers (period 1 for homogeneous archs, 8 for Jamba's 1:7 attention:mamba
interleave).  Parameters of each pattern position are stacked over
``n_super = L / period`` superblocks and the stack is applied with
``lax.scan`` — the compiled HLO contains one superblock body regardless of
depth, which keeps 61-layer x 384-expert dry-run compiles tractable and is
the production pattern (MaxText-style scanned layers).

Remat: the superblock body is wrapped in ``jax.checkpoint``; the policy is
configurable (baseline ``nothing_saveable`` = full remat; §Perf iterates).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.sharding import ShardCtx, cache_spec, constrain, param_specs

Pytree = Any


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


class LMModel:
    def __init__(self, cfg: ArchConfig, ctx: Optional[ShardCtx] = None,
                 remat_policy: str = "nothing_saveable",
                 attn_score_dtype: str = "float32"):
        self.cfg = cfg
        self.ctx = ctx
        self.remat_policy = remat_policy
        self.score_dtype = _dtype(attn_score_dtype)
        # §Perf B1 (head padding): MHA head counts that do not divide the
        # model axis (minicpm 36H, musicgen 24H) replicate attention under
        # the baseline rules.  With ctx.uneven, pad H (and K, MHA only) to
        # the next axis multiple: +pad/H attention compute for axis-wide
        # TP.  jax rejects non-divisible input shardings, so padding is
        # done in the parameter shapes themselves.
        ms = ctx.model_size if ctx is not None else 1
        H, K = cfg.num_heads, cfg.kv_heads
        if ctx is not None and getattr(ctx, "uneven", False) and H \
                and H == K and H % ms:
            H = K = -(-H // ms) * ms
        self.n_heads = H
        self.n_kv = K
        period = cfg.hybrid_period
        if not period:
            period = 2 if (cfg.moe and cfg.moe.layer_pattern == "every_2") \
                else 1
        assert cfg.num_layers % period == 0, (cfg.num_layers, period)
        self.period = period
        self.n_super = cfg.num_layers // period
        self.kinds = []
        for i in range(period):
            mixer = "attn" if cfg._layer_is_attn(i) else "ssm"
            if cfg.moe is not None and cfg._layer_is_moe(i):
                ffn = "moe"
            elif cfg.d_ff > 0:
                ffn = "dense"
            else:
                ffn = None
            self.kinds.append((mixer, ffn))
        self.pdt = _dtype(cfg.param_dtype)
        self.cdt = _dtype(cfg.compute_dtype)

    # ------------------------------------------------------------------
    # parameter shapes / init / sharding
    # ------------------------------------------------------------------
    def _sublayer_shapes(self, mixer: str, ffn: Optional[str]) -> dict:
        cfg = self.cfg
        d, hd = cfg.d_model, cfg.resolved_head_dim
        out: Dict[str, Any] = {"norm1": (d,)}
        if mixer == "attn":
            out["attn"] = {
                "wq": (d, self.n_heads, hd),
                "wk": (d, self.n_kv, hd),
                "wv": (d, self.n_kv, hd),
                "wo": (self.n_heads, hd, d),
            }
        else:
            out["ssm"] = ssm_lib.ssm_param_shapes(d, cfg.ssm)
        if ffn == "dense":
            out["norm2"] = (d,)
            out["mlp"] = L.mlp_param_shapes(d, cfg.d_ff, cfg.mlp_type)
        elif ffn == "moe":
            out["norm2"] = (d,)
            out["moe"] = moe_lib.moe_param_shapes(d, cfg.moe, cfg.mlp_type)
        return out

    def param_shapes(self) -> Pytree:
        cfg = self.cfg
        shapes: Dict[str, Any] = {
            "embed": (cfg.vocab_size, cfg.d_model),
            "final_norm": (cfg.d_model,),
            "blocks": {},
        }
        if not cfg.tie_embeddings:
            shapes["head"] = (cfg.d_model, cfg.vocab_size)
        for i, (mixer, ffn) in enumerate(self.kinds):
            sub = self._sublayer_shapes(mixer, ffn)
            stacked = jax.tree.map(lambda s: (self.n_super, *s), sub,
                                   is_leaf=lambda s: isinstance(s, tuple))
            shapes["blocks"][f"pos{i}"] = stacked
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s, self.pdt), shapes,
            is_leaf=lambda s: isinstance(s, tuple))

    def param_pspecs(self) -> Pytree:
        assert self.ctx is not None
        return param_specs(self.cfg, self.param_shapes(), self.ctx)

    def init(self, rng: jax.Array) -> Pytree:
        shapes = self.param_shapes()
        leaves, treedef = jax.tree.flatten_with_path(shapes)
        keys = jax.random.split(rng, len(leaves))
        d = self.cfg.d_model

        def init_leaf(path, sds, key):
            name = path[-1].key
            shape, dtype = sds.shape, sds.dtype
            if name in ("norm1", "norm2", "final_norm", "gate_norm", "D"):
                return jnp.ones(shape, dtype)
            if name in ("conv_x_b", "conv_B_b", "conv_C_b"):
                return jnp.zeros(shape, dtype)
            if name == "A_log":
                u = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                return jnp.log(u).astype(dtype)
            if name == "dt_bias":
                u = jax.random.uniform(key, shape, jnp.float32,
                                       math.log(1e-3), math.log(1e-1))
                dt = jnp.exp(u)
                return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
            scale = 0.02 if name in ("embed", "head") else 1.0 / math.sqrt(d)
            return (jax.random.normal(key, shape, jnp.float32)
                    * scale).astype(dtype)

        out = [init_leaf(p, s, k) for (p, s), k in zip(leaves, keys)]
        return jax.tree.unflatten(treedef, out)

    # ------------------------------------------------------------------
    # forward components
    # ------------------------------------------------------------------
    def _attention_full(self, p: dict, x: jax.Array, positions: jax.Array,
                        want_cache: bool, capacity: int = 0):
        cfg = self.cfg
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(x.dtype))
        k = jnp.einsum("bsd,dke->bske", x, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dke->bske", x, p["wv"].astype(x.dtype))
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        o = L.gqa_attention(q, k, v, positions, positions,
                            swa_window=cfg.swa_window,
                            softcap=cfg.attn_logit_softcap,
                            score_dtype=self.score_dtype)
        out = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(x.dtype))
        if not want_cache:
            return out, None
        S = x.shape[1]
        C = capacity
        if C <= S:                       # ring (SWA) or exact-fit cache
            k_c = jnp.roll(k[:, S - C:], S % C, axis=1)
            v_c = jnp.roll(v[:, S - C:], S % C, axis=1)
        else:
            pad = [(0, 0), (0, C - S), (0, 0), (0, 0)]
            k_c, v_c = jnp.pad(k, pad), jnp.pad(v, pad)
        return out, {"k": k_c, "v": v_c}

    def _attention_decode(self, p: dict, x: jax.Array, cache: dict,
                          pos: jax.Array):
        """pos: scalar, or [B] vector for ragged continuous batching
        (per-slot positions; vector path uses one-hot masked writes)."""
        cfg = self.cfg
        ctx = self.ctx
        ragged = jnp.ndim(pos) == 1
        C = cache["k"].shape[1]
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(x.dtype))
        k = jnp.einsum("bsd,dke->bske", x, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dke->bske", x, p["wv"].astype(x.dtype))
        posv = pos[:, None] if ragged else jnp.full((1,), pos, jnp.int32)
        q = L.apply_rope(q, posv, cfg.rope_theta)
        k = L.apply_rope(k, posv, cfg.rope_theta)
        slots = jnp.arange(C, dtype=jnp.int32)
        if ragged:
            slot = (pos % C).astype(jnp.int32)               # [B]
            hit = slots[None, :] == slot[:, None]            # [B, C]
            k_c = jnp.where(hit[:, :, None, None],
                            k.astype(cache["k"].dtype), cache["k"])
            v_c = jnp.where(hit[:, :, None, None],
                            v.astype(cache["v"].dtype), cache["v"])
        else:
            slot = (pos % C).astype(jnp.int32)
            k_c = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
            v_c = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        if ctx is not None:
            B = k_c.shape[0]
            k_c = constrain(k_c, ctx, *cache_spec("kv", ctx, B))
            v_c = constrain(v_c, ctx, *cache_spec("kv", ctx, B))
        if cfg.swa_window and cfg.swa_window == C:
            p_ = pos[:, None] if ragged else pos
            slot_pos = p_ - ((p_ - slots) % C)
        else:
            slot_pos = slots
        o = L.decode_attention(q, k_c, v_c, slot_pos, pos,
                               softcap=cfg.attn_logit_softcap)
        out = jnp.einsum("bshe,hed->bsd", o, p["wo"].astype(x.dtype))
        return out, {"k": k_c, "v": v_c}

    def _sublayer(self, p: dict, x: jax.Array, kind, positions,
                  mode: str, cache=None, pos=None, capacity: int = 0):
        cfg = self.cfg
        mixer, ffn = kind
        h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
        new_cache = None
        if mixer == "attn":
            if mode == "decode":
                a, new_cache = self._attention_decode(p["attn"], h, cache,
                                                      pos)
            else:
                a, new_cache = self._attention_full(
                    p["attn"], h, positions, want_cache=(mode == "prefill"),
                    capacity=capacity)
        else:
            if mode == "decode":
                a, new_cache = ssm_lib.ssm_decode_step(h, cache, p["ssm"],
                                                       cfg.d_model, cfg.ssm)
            elif mode == "prefill":
                a, new_cache = ssm_lib.ssm_forward(h, p["ssm"], cfg.d_model,
                                                   cfg.ssm,
                                                   return_state=True)
            else:
                a = ssm_lib.ssm_forward(h, p["ssm"], cfg.d_model, cfg.ssm)
        x = x + cfg.residual_scale * a
        aux = jnp.zeros((), jnp.float32)
        if ffn is not None:
            h = L.rms_norm(x, p["norm2"], cfg.norm_eps)
            if ffn == "moe":
                y, aux = moe_lib.moe_mlp(h, p["moe"], cfg.moe, cfg.mlp_type)
            else:
                y = L.mlp(h, p["mlp"], cfg.mlp_type)
            x = x + cfg.residual_scale * y
        return x, aux, new_cache

    def _embed(self, params, tokens, embeds):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0).astype(self.cdt)
        x = x * cfg.embed_scale
        if embeds is not None:
            x = jnp.concatenate([embeds.astype(self.cdt), x], axis=1)
        return x

    def _unembed(self, params, x):
        cfg = self.cfg
        head = params.get("head")
        if head is None:
            head = params["embed"].T
        logits = (x @ head.astype(x.dtype)).astype(jnp.float32)
        return logits * cfg.logit_scale

    def _dp_spec(self):
        ctx = self.ctx
        dp = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
        return dp

    def _constrain_act(self, x):
        if self.ctx is None or x.shape[0] == 1:
            return x
        return constrain(x, self.ctx, self._dp_spec(), None, None)

    # ------------------------------------------------------------------
    # full-sequence forward (training)
    # ------------------------------------------------------------------
    def forward(self, params: Pytree, tokens: jax.Array,
                embeds: Optional[jax.Array] = None,
                return_hidden: bool = False):
        """tokens: [B, S_text]; embeds: [B, F, d] (VLM stub) or None.
        Returns (logits [B, S, V] fp32, aux_loss scalar); with
        ``return_hidden`` returns the final-normed hidden states instead of
        logits (the train step computes a blocked cross-entropy that never
        materialises the [B, S, V] fp32 logits)."""
        x = self._embed(params, tokens, embeds)
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)

        def superblock(carry, blk):
            x, aux = carry
            x = self._constrain_act(x)
            for i, kind in enumerate(self.kinds):
                x, a, _ = self._sublayer(blk[f"pos{i}"], x, kind, positions,
                                         mode="train")
                aux = aux + a
            return (x, aux), None

        policy = getattr(jax.checkpoint_policies, self.remat_policy)
        body = jax.checkpoint(superblock, policy=policy)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["blocks"])
        x = L.rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        if return_hidden:
            return x, aux
        return self._unembed(params, x), aux

    def unembed_matrix(self, params: Pytree) -> jax.Array:
        head = params.get("head")
        if head is None:
            head = params["embed"].T
        return head

    # ------------------------------------------------------------------
    # prefill / decode (serving)
    # ------------------------------------------------------------------
    def capacity_for(self, seq_len: int) -> int:
        cfg = self.cfg
        if cfg.swa_window:
            return min(cfg.swa_window, seq_len)
        return seq_len

    def prefill(self, params: Pytree, tokens: jax.Array,
                embeds: Optional[jax.Array] = None,
                capacity: Optional[int] = None):
        """Returns (cache pytree, last-position logits [B, V])."""
        x = self._embed(params, tokens, embeds)
        S = x.shape[1]
        capacity = capacity or self.capacity_for(S)
        positions = jnp.arange(S, dtype=jnp.int32)

        def superblock(carry, blk):
            x = carry
            x = self._constrain_act(x)
            caches = {}
            for i, kind in enumerate(self.kinds):
                x, _, c = self._sublayer(blk[f"pos{i}"], x, kind, positions,
                                         mode="prefill", capacity=capacity)
                caches[f"pos{i}"] = c
            return x, caches

        policy = getattr(jax.checkpoint_policies, self.remat_policy)
        body = jax.checkpoint(superblock, policy=policy)
        x, caches = jax.lax.scan(body, x, params["blocks"])
        x = L.rms_norm(x[:, -1:], params["final_norm"], self.cfg.norm_eps)
        logits = self._unembed(params, x)[:, 0]
        return caches, logits

    def decode_step(self, params: Pytree, cache: Pytree, tokens: jax.Array,
                    pos: jax.Array):
        """tokens: [B, 1]; pos: scalar int32 (absolute position of the new
        token).  Returns (new cache, logits [B, V])."""
        x = self._embed(params, tokens, None)

        def superblock(x, blk_and_cache):
            blk, cch = blk_and_cache
            new_caches = {}
            for i, kind in enumerate(self.kinds):
                x, _, c = self._sublayer(blk[f"pos{i}"], x, kind, None,
                                         mode="decode", cache=cch[f"pos{i}"],
                                         pos=pos)
                new_caches[f"pos{i}"] = c
            return x, new_caches

        x, new_cache = jax.lax.scan(superblock, x,
                                    (params["blocks"], cache))
        x = L.rms_norm(x, params["final_norm"], self.cfg.norm_eps)
        logits = self._unembed(params, x)[:, 0]
        return new_cache, logits

    # ------------------------------------------------------------------
    # cache specs (for dry-run input construction)
    # ------------------------------------------------------------------
    def cache_shapes(self, batch: int, seq_len: int) -> Pytree:
        cfg = self.cfg
        capacity = self.capacity_for(seq_len)
        hd = cfg.resolved_head_dim
        out = {}
        for i, (mixer, _) in enumerate(self.kinds):
            if mixer == "attn":
                kv = jax.ShapeDtypeStruct(
                    (self.n_super, batch, capacity, self.n_kv, hd),
                    jnp.bfloat16)
                out[f"pos{i}"] = {"k": kv, "v": kv}
            else:
                st = ssm_lib.ssm_state_shapes(batch, cfg.d_model, cfg.ssm)
                out[f"pos{i}"] = {
                    k: jax.ShapeDtypeStruct((self.n_super, *shape), dt)
                    for k, (shape, dt) in st.items()}
        return out

    def cache_pspecs(self, batch: int) -> Pytree:
        ctx = self.ctx
        assert ctx is not None

        def stack(spec: P) -> P:
            return P(None, *spec)

        out = {}
        for i, (mixer, _) in enumerate(self.kinds):
            if mixer == "attn":
                s = stack(cache_spec("kv", ctx, batch))
                out[f"pos{i}"] = {"k": s, "v": s}
            else:
                out[f"pos{i}"] = {
                    "ssm": stack(cache_spec("ssm", ctx, batch)),
                    "conv_x": stack(cache_spec("conv", ctx, batch)),
                    "conv_B": stack(cache_spec("conv", ctx, batch)),
                    "conv_C": stack(cache_spec("conv", ctx, batch)),
                }
        return out


def build_model(cfg: ArchConfig, ctx: Optional[ShardCtx] = None,
                remat_policy: str = "nothing_saveable",
                attn_score_dtype: str = "float32") -> LMModel:
    return LMModel(cfg, ctx, remat_policy, attn_score_dtype)
