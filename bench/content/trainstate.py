"""A sharded train state under expert-specialized fine-tuning (ESFT),
made from a seed on the devices of a run.

The configuration gives a DeepSeek-V2 model's widths and how many ways
(``train_state.ways``) each layer is divided; a run holds ``ranks`` of
those ways, one per device, each with the share of every layer one
chip of the stated deployment holds.  Experts are divided by expert
parallelism (a rank holds ``n_routed_experts / ways`` of each layer,
stacked); every other tensor is cut on its first axis (FSDP-style); the
embedding and the head are cut into vocabulary slices.  Each MoE
layer trains one routed expert per rank (chosen from the seed), which
carries a bf16 weight in its stack and an fp32 master, Adam m and Adam
v; everything else is bf16 and frozen.

Every value comes from a counter-based integer hash of (seed, leaf,
rank, step, element index) and is a finite float whose bit pattern is
built from the hash, so the bytes are the same whether made here with
``jax.numpy`` on a device or with ``numpy`` by the plain reference
(``bench/reference/trainstate.py``), which imports only the layout and
keys of this module.  A frozen element always carries step 0; a trained
expert's elements carry the step of the last update.  JAX is imported
only by ``Source``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

M32 = 0xFFFFFFFF
GOLDEN = 0x9E3779B1


def fmix32(x: int) -> int:
    """murmur3's 32-bit finalizer on a Python int."""
    x &= M32
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & M32
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & M32
    return x ^ (x >> 16)


def key(seed: int, *parts: int) -> int:
    """The 32-bit key of (seed, parts...): seeds of any size, taken
    modulo 2**64."""
    seed %= 1 << 64
    h = 0x243F6A88
    for v in (seed & M32, seed >> 32, *parts):
        h = fmix32(h + (v & M32) * GOLDEN + 0x7F4A7C15)
    return h


@dataclass(frozen=True)
class Leaf:
    """One tensor of the state: ``shape`` is one rank's shard (ranks are
    stacked on axis 0), ``kind`` is ``frozen``, ``experts`` (a stack of
    routed experts, one of them trained) or ``opt`` (the trained
    expert's fp32 state, all rewritten each step)."""
    key: str
    uid: int
    shape: Tuple[int, ...]
    dtype: str
    kind: str = "frozen"
    layer: int = -1

    @property
    def itemsize(self) -> int:
        return 2 if self.dtype == "bfloat16" else 4

    @property
    def size(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n

    @property
    def nbytes(self) -> int:
        return self.size * self.itemsize

    @property
    def expert_size(self) -> int:
        """Elements of one expert's matrix in an ``experts`` stack."""
        return self.size // self.shape[0]


def layout(config: Dict) -> List[Leaf]:
    """The leaves of one rank's shard of the state, in save order."""
    c = config
    ways = int(c["train_state"]["ways"])
    h, heads = c["hidden_size"], c["num_attention_heads"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    kv, vocab = c["kv_lora_rank"], c["vocab_size"]
    inter, moe = c["intermediate_size"], c["moe_intermediate_size"]
    experts, shared = c["n_routed_experts"], c["n_shared_experts"]
    if c["q_lora_rank"] is not None:
        raise ValueError("layout has no q_lora path")
    out: List[Leaf] = []

    def add(k, shape, kind="frozen", layer=-1, dtype="bfloat16"):
        rows = shape[0]
        if kind == "frozen":
            if rows % ways:
                raise ValueError(f"{k}: {rows} rows in {ways} ways")
            rows //= ways
        out.append(Leaf(k, len(out), (rows,) + tuple(shape[1:]), dtype,
                        kind, layer))

    add("params/embed", (vocab, h))
    for i in range(c["num_hidden_layers"]):
        p = f"params/layers/{i}"
        add(f"{p}/input_norm", (h,))
        add(f"{p}/attn/q_proj", (heads * (nope + rope), h))
        add(f"{p}/attn/kv_a_proj", (kv + rope, h))
        add(f"{p}/attn/kv_a_norm", (kv,))
        add(f"{p}/attn/kv_b_proj", (heads * (nope + v), kv))
        add(f"{p}/attn/o_proj", (h, heads * v))
        add(f"{p}/post_attn_norm", (h,))
        if i < c["first_k_dense_replace"]:
            add(f"{p}/mlp/gate_proj", (inter, h))
            add(f"{p}/mlp/up_proj", (inter, h))
            add(f"{p}/mlp/down_proj", (h, inter))
            continue
        add(f"{p}/mlp/router", (experts, h))
        if experts % ways:
            raise ValueError(f"{experts} experts in {ways} ways")
        for m, shape in (("gate_proj", (moe, h)), ("up_proj", (moe, h)),
                         ("down_proj", (h, moe))):
            add(f"{p}/mlp/experts/{m}", (experts // ways,) + shape,
                "experts", i)
        add(f"{p}/mlp/shared/gate_proj", (shared * moe, h))
        add(f"{p}/mlp/shared/up_proj", (shared * moe, h))
        add(f"{p}/mlp/shared/down_proj", (h, shared * moe))
    add("params/final_norm", (h,))
    add("params/head", (vocab, h))
    for leaf in list(out):
        if leaf.kind != "experts":
            continue
        name = leaf.key.split("/")[-1]
        base = f"opt/layers/{leaf.layer}/experts/{name}"
        for part in ("master", "m", "v"):
            add(f"{base}/{part}", (1,) + leaf.shape[1:], "opt", leaf.layer,
                "float32")
    return out


def trained_expert(seed: int, layer: int, rank: int, n_local: int) -> int:
    """Which of a rank's ``n_local`` experts of ``layer`` ESFT trains."""
    return key(seed, 0xE5F7, layer, rank) % n_local


def element_steps(leaf: Leaf, seed: int, rank: int,
                  step: int) -> Tuple[int, int, int]:
    """(first, stop, step): the elements of the shard that carry
    ``step``; every other element carries step 0."""
    if leaf.kind == "opt":
        return 0, leaf.size, step
    if leaf.kind == "experts":
        e = trained_expert(seed, leaf.layer, rank, leaf.shape[0])
        return e * leaf.expert_size, (e + 1) * leaf.expert_size, step
    return 0, 0, step


class Source:
    """The state of ``ranks`` ranks, one per device of ``devices``, as
    ``jax.Array`` leaves sharded on axis 0 over a one-axis mesh; each
    shard is made on its own device.  ``tree()`` gives (params,
    opt_state) for a checkpointer; ``step()`` makes the next training
    step's state: each rank's trained expert rewritten, in its stack and
    in its optimizer state; every frozen leaf left as it is."""

    def __init__(self, config: Dict, seed: int, devices):
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        self.jax = jax
        self.np = np
        self._values, self._put_expert = _jnp_values()
        self.seed = int(seed)
        self.devices = list(devices)
        self.leaves = layout(config)
        self.step_no = 0
        mesh = Mesh(np.array(self.devices, dtype=object), ("rank",))
        self.sharding = NamedSharding(mesh, PartitionSpec("rank"))
        self.arrays = {leaf.key: self._global(
            leaf, [self._shard(leaf, r, 0) for r in range(len(devices))])
            for leaf in self.leaves}

    def _on(self, r: int, value: int):
        return self.jax.device_put(self.np.uint32(value), self.devices[r])

    def _shard(self, leaf: Leaf, r: int, step: int):
        first, stop, s = element_steps(leaf, self.seed, r, step)
        k0 = key(self.seed, leaf.uid, r, 0)
        if stop - first == leaf.size:
            k0 = key(self.seed, leaf.uid, r, s)
        out = self._values(self._on(r, k0), self._on(r, 0), n=leaf.size,
                           dtype=leaf.dtype).reshape(leaf.shape)
        if 0 < stop - first < leaf.size:
            out = self._expert(out, leaf, r, step)
        return out

    def _expert(self, stack, leaf: Leaf, r: int, step: int):
        """``stack`` with rank r's trained expert made at ``step``."""
        first, stop, s = element_steps(leaf, self.seed, r, step)
        part = self._values(self._on(r, key(self.seed, leaf.uid, r, s)),
                            self._on(r, first), n=stop - first,
                            dtype=leaf.dtype)
        return self._put_expert(stack, part, first // leaf.expert_size)

    def _global(self, leaf: Leaf, shards):
        shape = (leaf.shape[0] * len(shards),) + leaf.shape[1:]
        return self.jax.make_array_from_single_device_arrays(
            shape, self.sharding, shards)

    def step(self) -> None:
        self.step_no += 1
        for leaf in self.leaves:
            if leaf.kind == "frozen":
                continue
            shards = [self._shard(leaf, r, self.step_no)
                      if leaf.kind == "opt"
                      else self._expert(sh, leaf, r, self.step_no)
                      for r, sh in enumerate(by_rank(self.arrays[leaf.key]))]
            self.arrays[leaf.key] = self._global(leaf, shards)

    def tree(self):
        """(params, opt_state) as nested dicts of the leaves."""
        root: Dict = {}
        for leaf in self.leaves:
            cur = root
            parts = leaf.key.split("/")
            for p in parts[:-1]:
                cur = cur.setdefault(p, {})
            cur[parts[-1]] = self.arrays[leaf.key]
        return root["params"], root.get("opt")

    def free(self) -> None:
        for arr in self.arrays.values():
            arr.delete()
        self.arrays = {}


def by_rank(arr):
    """The single-device shards of a state leaf, in rank order."""
    return [sh.data for sh in sorted(arr.addressable_shards,
                                     key=lambda sh: sh.index[0].start or 0)]


def _jnp_values():
    """The jitted makers of values and of a stack with one expert
    replaced (JAX is imported here, not by the module)."""
    import functools
    import jax
    import jax.numpy as jnp

    def fmix(x):
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x85EBCA6B)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(0xC2B2AE35)
        return x ^ (x >> 16)

    @functools.partial(jax.jit, static_argnames=("n", "dtype"))
    def values(k, first, n: int, dtype: str):
        i = jax.lax.iota(jnp.uint32, n) + first
        h = fmix(i * jnp.uint32(GOLDEN) + k)
        if dtype == "bfloat16":
            bits = (((h >> 16) & 0x8000) | ((0x70 + ((h >> 7) & 0xF)) << 7)
                    | (h & 0x7F)).astype(jnp.uint16)
            return jax.lax.bitcast_convert_type(bits, jnp.bfloat16)
        bits = ((h & jnp.uint32(0x80000000))
                | ((0x70 + ((h >> 23) & 0xF)) << 23) | (h & 0x7FFFFF))
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    @jax.jit
    def put_expert(stack, part, e):
        return jax.lax.dynamic_update_index_in_dim(
            stack, part.reshape(stack.shape[1:]), e, 0)

    return values, put_expert

