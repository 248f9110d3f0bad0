"""Continuous-batching request scheduler (serving substrate).

Production serving at decode_32k scale interleaves requests: new prompts
prefill into free cache slots while resident requests decode every step.
This implements the slot-based variant matching the framework's
fixed-capacity decode caches:

  * a fixed pool of B cache slots (the decode batch — the jitted graphs
    stay fixed-shape, so continuous batching costs no recompiles);
  * arriving requests queue; a free slot triggers a single-sequence
    prefill whose cache rows are written into the slot;
  * every engine step decodes ALL active slots in one batched call with
    a per-slot position vector (the model's ragged decode path:
    one-hot masked cache writes + per-slot attention masks);
  * finished requests (max-tokens or EOS) free their slot.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # [S] int32
    max_new: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    submitted_s: float = dataclasses.field(default_factory=time.time)
    first_token_s: Optional[float] = None
    done_s: Optional[float] = None


class ContinuousBatcher:
    def __init__(self, model, params, batch_slots: int, capacity: int,
                 eos_token: int = -1):
        self.model = model
        self.params = params
        self.B = batch_slots
        self.capacity = capacity
        self.eos = eos_token
        self.queue: Deque[Request] = deque()
        self.active: Dict[int, Request] = {}        # slot -> request
        self.finished: List[Request] = []
        self.slot_pos = np.zeros((batch_slots,), np.int64)
        self.cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            model.cache_shapes(batch_slots, capacity))
        self._prefill_one = jax.jit(
            lambda p, t: model.prefill(p, t, capacity=capacity))
        self._decode = jax.jit(model.decode_step, donate_argnums=(1,))
        self.steps = 0
        self._next_rid = 0

    # ------------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int) -> Request:
        req = Request(rid=self._next_rid,
                      prompt=np.asarray(prompt, np.int32),
                      max_new=max_new)
        self._next_rid += 1
        self.queue.append(req)
        return req

    def _admit(self):
        for slot in range(self.B):
            if slot in self.active or not self.queue:
                continue
            req = self.queue.popleft()
            cache1, logits = self._prefill_one(self.params,
                                               req.prompt[None, :])

            def put(full, one):
                return full.at[:, slot:slot + 1].set(one.astype(full.dtype))

            self.cache = jax.tree.map(put, self.cache, cache1)
            req.out_tokens.append(int(jnp.argmax(logits, -1)[0]))
            req.first_token_s = time.time()
            self.slot_pos[slot] = len(req.prompt)
            self.active[slot] = req

    def _retire(self):
        for slot, req in list(self.active.items()):
            if len(req.out_tokens) >= req.max_new or \
                    req.out_tokens[-1] == self.eos:
                req.done_s = time.time()
                self.finished.append(req)
                del self.active[slot]

    def step(self):
        """One engine step: admit -> batched ragged decode -> retire."""
        self._admit()
        self._retire()
        if not self.active:
            return
        toks = np.zeros((self.B, 1), np.int32)
        for slot, req in self.active.items():
            toks[slot, 0] = req.out_tokens[-1]
        pos_vec = jnp.asarray(self.slot_pos, jnp.int32)      # [B]
        self.cache, logits = self._decode(self.params, self.cache,
                                          jnp.asarray(toks), pos_vec)
        nxt = np.asarray(jnp.argmax(logits, -1))
        for slot, req in self.active.items():
            req.out_tokens.append(int(nxt[slot]))
            self.slot_pos[slot] += 1
        self.steps += 1
        self._retire()

    def run_until_drained(self, max_steps: int = 10_000) -> List[Request]:
        for _ in range(max_steps):
            if not self.queue and not self.active:
                break
            self.step()
        return self.finished

    def stats(self) -> dict:
        done = [r for r in self.finished]
        return {
            "steps": self.steps,
            "finished": len(done),
            "queued": len(self.queue),
            "active": len(self.active),
            "mean_ttft_s": float(np.mean(
                [r.first_token_s - r.submitted_s for r in done]))
            if done else 0.0,
        }
