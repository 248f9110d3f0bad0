"""Training step: blocked cross-entropy + grad + optimizer update.

Memory design notes:
  * Cross-entropy is computed *blocked over the sequence* with a
    rematerialised chunk body, so the fp32 [B, S, V] logits tensor is
    never resident (for llama3 train_4k that tensor would be ~33 GB per
    device).  Each chunk computes logits -> CE -> discards; backward
    recomputes the chunk logits.
  * Optional microbatching (gradient accumulation) splits the batch and
    accumulates grads in fp32 — the standard large-scale trick when the
    per-step activation footprint exceeds HBM.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax

import jax.numpy as jnp

CE_CHUNK = 512


def _ce_chunk(x, head, labels, mask, logit_scale):
    """x: [B, c, d]; head: [d, V]; labels/mask: [B, c] -> (sum_nll, count)."""
    logits = (x @ head.astype(x.dtype)).astype(jnp.float32) * logit_scale
    lse = jax.nn.logsumexp(logits, axis=-1)                   # [B, c]
    onehot = jax.nn.one_hot(labels, logits.shape[-1],
                            dtype=logits.dtype)               # fused by XLA
    picked = jnp.sum(logits * onehot, axis=-1)                # [B, c]
    nll = (lse - picked) * mask
    return jnp.sum(nll), jnp.sum(mask)


def blocked_cross_entropy(x, head, labels, mask, logit_scale=1.0,
                          chunk: int = CE_CHUNK):
    """Sequence-blocked CE.  x: [B, S, d]; labels/mask: [B, S]."""
    B, S, d = x.shape
    if S % chunk or S <= chunk:
        return _ce_chunk(x, head, labels, mask, logit_scale)
    n = S // chunk
    xs = x.reshape(B, n, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(B, n, chunk).swapaxes(0, 1)
    ms = mask.reshape(B, n, chunk).swapaxes(0, 1)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def body(carry, inp):
        tot, cnt = carry
        xc, lc, mc = inp
        s, c = _ce_chunk(xc, head, lc, mc, logit_scale)
        return (tot + s, cnt + c), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xs, ls, ms))
    return tot, cnt


def make_loss_fn(model, aux_weight: float = 0.01):
    cfg = model.cfg
    F = cfg.frontend_embeds

    def loss_fn(params, batch):
        tokens = batch["tokens"]
        embeds = batch.get("embeds")
        x, aux = model.forward(params, tokens, embeds, return_hidden=True)
        head = model.unembed_matrix(params)
        if F and embeds is not None:
            # frontend positions prepended: prediction for text token j
            # comes from hidden position F - 1 + j.
            x_pred = x[:, F - 1:-1]
            labels = tokens
            mask = jnp.ones(labels.shape, jnp.float32)
        else:
            x_pred = x[:, :-1]
            labels = tokens[:, 1:]
            mask = jnp.ones(labels.shape, jnp.float32)
        tot, cnt = blocked_cross_entropy(x_pred, head, labels, mask,
                                         cfg.logit_scale)
        ce = tot / jnp.maximum(cnt, 1.0)
        loss = ce + aux_weight * aux
        return loss, {"ce": ce, "aux": aux}

    return loss_fn


def make_train_step(model, optimizer, microbatches: int = 1,
                    aux_weight: float = 0.01):
    """Returns train_step(params, opt_state, batch, step) ->
    (params, opt_state, metrics)."""
    loss_fn = make_loss_fn(model, aux_weight)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch, step):
        if microbatches == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            def split(x):
                B = x.shape[0]
                return x.reshape(microbatches, B // microbatches,
                                 *x.shape[1:])
            mb = jax.tree.map(split, batch)

            def body(carry, mbatch):
                gsum, lsum = carry
                (loss, _), g = grad_fn(params, mbatch)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (gsum, lsum + loss), None

            gzero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, lsum), _ = jax.lax.scan(
                body, (gzero, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = lsum / microbatches
            metrics = {"ce": loss, "aux": jnp.zeros((), jnp.float32)}

        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads)))
        params, opt_state = optimizer.update(grads, opt_state, params, step)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm,
                       lr=optimizer.lr_fn(step))
        return params, opt_state, metrics

    return train_step
