"""End-to-end model compiler (EdgeLLM §IV) — the JAX restatement.

Two halves:

1. **quantize_model** — the offline half of the paper's compiler: walk the
   parameter pytree and replace every static weight matrix with its W4A16
   (``QuantizedTensor``) or log-scale-sparse (``SparseQuantizedTensor``)
   packed form, per a *sparse strategy* (the paper's Table II per-layer-kind
   density map).  Dynamically-generated operands (KV caches, activations,
   norms, router, conv, embeddings-as-lookup) stay 16-bit, exactly the
   paper's rule.

2. **CompileCache / buckets** — the online half: the paper compiles
   instruction streams per dynamic token length with a MAX-token static
   address space.  Under JAX, a compiled executable per (shape-bucket) is
   the same contract; ``TokenBuckets`` picks the bucket, and
   ``CompileCache`` memoizes jit executables per (fn, bucket) so serving
   never re-traces mid-flight.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import GROUP_SIZE, QuantizedTensor, quantize
from repro.core.sparsity import (
    BLOCKS_PER_GROUP,
    SparseQuantizedTensor,
    block_sparsify_quantize,
)

# ---------------------------------------------------------------------------
# sparse strategies (paper Table II)
# ---------------------------------------------------------------------------

# layer-kind -> density (1.0 = dense-quantized; None = keep 16-bit)
SPARSE_STRATEGIES: dict[str, dict[str, float]] = {
    # paper Table II, GLM-6B
    "dense": {"qkv": 1.0, "o": 1.0, "h_to_4h": 1.0, "4h_to_h": 1.0,
              "head": 1.0, "other": 1.0},
    "strategy1": {"qkv": 1.0, "o": 0.5, "h_to_4h": 0.5, "4h_to_h": 0.5,
                  "head": 1.0, "other": 1.0},
    "strategy2": {"qkv": 1.0, "o": 0.5, "h_to_4h": 0.25, "4h_to_h": 0.5,
                  "head": 1.0, "other": 1.0},
    "strategy3": {"qkv": 1.0, "o": 0.5, "h_to_4h": 0.25, "4h_to_h": 0.25,
                  "head": 1.0, "other": 1.0},
}

_KIND_BY_NAME = {
    "wq": "qkv", "wk": "qkv", "wv": "qkv", "wo": "o",
    "gate": "h_to_4h", "up": "h_to_4h", "down": "4h_to_h",
    "lm_head": "head",
    "in_proj": "other", "out_proj": "other",
    "up_x": "h_to_4h", "up_z": "h_to_4h",
    "w_gates": "other",
    # r_gates (sLSTM recurrent, block-diagonal, streamed per timestep) is
    # deliberately NOT quantized: it is tiny and sits inside the recurrence
}

_NEVER_QUANTIZE = {
    "embed", "router", "conv_w", "conv_b", "gamma", "beta", "norm",
    "out_norm", "A_log", "D", "dt_bias", "q_norm", "k_norm",
    "w_i", "w_f", "b_i", "b_f", "b_gates", "bq", "bk", "bv",
    "up_bias", "down_bias", "scale",
}


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
    return ""


def _quantize_2d(w, density: float, shard_groups: int | None = None,
                 tile_uniform: bool = False):
    """shard_groups: make (in_features // group_size) divisible by this —
    required when the contraction axis is TP-sharded at serve time (MoE
    experts under shard_map); smaller groups cost a few extra scale bits.

    tile_uniform: rank sparse kept-blocks across ALL output channels (one
    kept set per contraction block) — the layout the fused FFN kernel's
    down-projection gather consumes (see ``kernels/ffn_fused.py``)."""
    in_f, out_f = w.shape
    group = GROUP_SIZE
    if shard_groups:
        for g in (128, 64, 32):
            if in_f % g == 0 and (in_f // g) % shard_groups == 0:
                group = g
                break
    if in_f % group or (density < 1.0 and out_f % 128):
        return w  # not tileable; keep 16-bit
    if density >= 1.0:
        return quantize(w, group_size=group)
    n_blocks = in_f // 128
    if in_f % 128 == 0:
        for m in (BLOCKS_PER_GROUP, 4, 2):
            if n_blocks % m == 0 and round(density * m) >= 1:
                return block_sparsify_quantize(w, density, blocks_per_group=m,
                                               tile_uniform=tile_uniform)
    return quantize(w, group_size=group)


def _leaf_quantizer(path, leaf, dmap: dict) -> Callable | None:
    """The packing of one (in, out) matrix of this leaf, or None where the
    leaf stays 16-bit."""
    names = [str(e.key) for e in path
             if isinstance(e, jax.tree_util.DictKey)]
    name = _leaf_name(path)
    if name in _NEVER_QUANTIZE or not hasattr(leaf, "dtype"):
        return None
    if not jnp.issubdtype(leaf.dtype, jnp.floating) or leaf.ndim < 2:
        return None
    kind = _KIND_BY_NAME.get(name)
    if kind is None:
        return None
    density = dmap.get(kind, 1.0)
    if density is None:
        return None
    # MoE expert contractions are TP-sharded at serve time: keep their
    # quant-group count divisible by the model-axis size (16)
    shard_groups = 16 if "moe" in names else None
    # the FFN down projection contracts over d_ff — the axis the fused
    # FFN kernel walks; a tile-uniform kept set lets it skip dropped
    # hidden tiles (and their gate/up weight streams) outright
    return functools.partial(_quantize_2d, density=density,
                             shard_groups=shard_groups,
                             tile_uniform=(kind == "4h_to_h"))


def _density_map(strategy: str | dict) -> dict:
    return SPARSE_STRATEGIES[strategy] if isinstance(strategy, str) else strategy


def quantize_model(params: Any, strategy: str | dict = "dense") -> Any:
    """Pytree transform: static weight matrices -> packed INT4 (+sparse).

    Stacked leading dims (layer scan, experts, segments) are vmapped over,
    so a (L, E, d, f) MoE weight becomes a QuantizedTensor whose arrays
    carry (L, E, ...) leading axes — scan/slice compatible.
    """
    dmap = _density_map(strategy)

    def f(path, leaf):
        fn = _leaf_quantizer(path, leaf, dmap)
        if fn is None:
            return leaf
        for _ in range(leaf.ndim - 2):
            fn = jax.vmap(fn)
        return fn(leaf)

    return jax.tree_util.tree_map_with_path(f, params)


def init_quantized(init_fn: Callable[[jax.Array], Any], key: jax.Array,
                   strategy: str | dict = "dense") -> Any:
    """``quantize_model(init_fn(key), strategy)`` without the 16-bit tree.

    At full width the 16-bit weights do not fit on one chip beside their
    packed form (qwen-7b: 13.6 GB in bf16 against 2.8 GB packed, on a
    16 GB v5e).  So each leaf is made on the host CPU by a program of its
    own, which XLA prunes to that leaf's initialiser, then moved to the
    default device and packed there, a stacked leaf one layer at a time
    (``lax.map``).  Host and device each hold one 16-bit leaf at a time.
    (Made on the chip, the random initialisers of a full-width model take
    minutes to compile; on the host, seconds.)
    """
    dmap = _density_map(strategy)
    key = jax.device_put(key, jax.devices("cpu")[0])
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(init_fn, key))

    def build(i, path):
        leaf = jax.jit(lambda k: jax.tree.leaves(init_fn(k))[i])(key)
        fn = _leaf_quantizer(path, leaf, dmap)
        # through numpy: device_put leaves a jax.Array where it is unless
        # told a device, and a told device would commit the leaf to it
        leaf = jax.device_put(np.asarray(leaf))
        if fn is None:
            return leaf
        for _ in range(leaf.ndim - 3):
            fn = jax.vmap(fn)
        return jax.jit(functools.partial(jax.lax.map, fn) if leaf.ndim > 2
                       else fn)(leaf)

    return treedef.unflatten([build(i, path)
                              for i, (path, _) in enumerate(flat)])


def quantized_bytes(params: Any) -> int:
    """Total HBM bytes of the packed model (the paper's Table II wt. sums)."""
    total = 0

    def visit(leaf):
        nonlocal total
        if isinstance(leaf, (QuantizedTensor, SparseQuantizedTensor)):
            total += leaf.nbytes_model
        elif hasattr(leaf, "dtype"):
            total += leaf.size * leaf.dtype.itemsize

    jax.tree.map(visit, params,
                 is_leaf=lambda x: isinstance(
                     x, (QuantizedTensor, SparseQuantizedTensor)))
    return total


# ---------------------------------------------------------------------------
# dynamic-token compile cache (paper §IV-B)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TokenBuckets:
    """Power-of-two token-length buckets with a MAX token bound.

    The paper's compiler embeds the token count as a DAG variable evaluated
    at runtime; XLA needs static shapes, so the equivalent contract is
    bucketed padding: 17 operators × B buckets executables instead of 17 × T.
    """

    max_tokens: int
    min_bucket: int = 16

    def bucket(self, n: int) -> int:
        if n > self.max_tokens:
            raise ValueError(f"{n} tokens exceeds MAX {self.max_tokens}")
        b = self.min_bucket
        while b < n:
            b *= 2
        return min(b, self.max_tokens)

    def all_buckets(self) -> list[int]:
        out, b = [], self.min_bucket
        while b < self.max_tokens:
            out.append(b)
            b *= 2
        out.append(self.max_tokens)
        return out


class CompileCache:
    """Memoized jit executables per (name, key) — dynamic compilation.

    Serving uses three key families (the paper's pre-compiled executable
    set from Fig. 9, restated for XLA's static shapes):

    * ``("mixed", W)`` — the mixed prefill/decode tick at chunk-width
      bucket W (``TokenBuckets`` over the engine's chunk size): prompts
      admit through the SAME dispatch that advances decode rows, so there
      is no per-prompt-length prefill family at all;
    * ``("decode", B)`` — the pure-decode tick: one executable per resident
      slot-batch size, shared by every request at every step;
    * ``("insert", B)`` — the slot scatter behind ``insert_request`` /
      ``evict_slot`` (the slot index is a traced operand, so one executable
      covers all B slots); audio engines add one ``("admit", F)`` encoder
      executable per frame count.

    Total serving executables are therefore bounded by
    ``n_chunk_buckets + 2`` per engine regardless of traffic — the JAX
    restatement of the paper's "17 operators x B buckets"
    instruction-stream budget.
    """

    def __init__(self):
        self._cache: dict[tuple, Any] = {}
        self.hits = 0
        self.misses = 0
        self.misses_by_name: dict[str, int] = {}

    def get(self, name: str, bucket: int, build: Callable[[], Any]):
        key = (name, bucket)
        if key not in self._cache:
            self._cache[key] = build()
            self.misses += 1
            self.misses_by_name[name] = self.misses_by_name.get(name, 0) + 1
        else:
            self.hits += 1
        return self._cache[key]

    def keys(self) -> list[tuple]:
        return list(self._cache)

    def __len__(self):
        return len(self._cache)
