"""Public jit'd entry points for the kernel package.

Each op dispatches between:

* ``impl="pallas"``   — the Pallas TPU kernel (``interpret=True`` on CPU, a
  real Mosaic lowering on TPU).  This is the performance path.
* ``impl="xla"``      — a pure-XLA implementation with the *same numerics
  contract* (group-exact scale-after-dot).  This is what the 512-device
  dry-run lowers (Pallas cannot target the CPU dry-run backend), and the
  fallback for shapes the kernels don't tile.  A fallback never happens in
  silence: it raises a :class:`KernelFallbackWarning`, which a chip run can
  turn into an error.

The op-graph compiler (``core/compiler.py``) selects the impl per operator;
models only ever call these wrappers.
"""

from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp

from repro.core.quant import QuantizedTensor
from repro.core.sparsity import SparseQuantizedTensor
from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.pallas_compat import default_interpret
from repro.kernels.sparse_w4a16 import sparse_w4a16_matmul_pallas
from repro.kernels.w4a16_matmul import w4a16_matmul_pallas

__all__ = ["w4a16_matmul", "sparse_w4a16_matmul", "ffn_w4a16", "attention",
           "decode_attention", "mixed_attention", "gather_paged_cache",
           "KernelFallbackWarning"]


class KernelFallbackWarning(RuntimeWarning):
    """A selected Pallas kernel could not take its operands, and the XLA
    twin ran in its place."""


def _fallback(op: str, why: str) -> None:
    warnings.warn(f"{op}: the Pallas kernel gave way to the XLA twin ({why})",
                  KernelFallbackWarning, stacklevel=3)


def _auto() -> str:
    # probed at the first call, never at import (the probe takes the
    # device); the kernels resolve interpret=None through the same cached
    # function
    return "xla" if default_interpret() else "pallas"


def w4a16_matmul(x: jax.Array, qt: QuantizedTensor, *, impl: str = "auto") -> jax.Array:
    """x @ dequant(qt); group-exact W4A16 numerics on every path."""
    if impl == "auto":
        impl = _auto()
    if impl == "pallas":
        return w4a16_matmul_pallas(x, qt)
    if impl == "xla":
        return _ref.w4a16_matmul_ref(x, qt)
    raise ValueError(f"unknown impl {impl!r}")


def sparse_w4a16_matmul(
    x: jax.Array, st: SparseQuantizedTensor, *, impl: str = "auto"
) -> jax.Array:
    if impl == "auto":
        impl = _auto()
    if impl == "pallas":
        return sparse_w4a16_matmul_pallas(x, st)
    if impl == "xla":
        # gather-then-dense-dot: same block gather the kernel does, expressed
        # as XLA take + einsum (keeps the sparse byte/FLOP savings visible to
        # cost_analysis); shared with the fused-FFN twin
        from repro.kernels.ffn_fused import sparse_matmul_f32
        return sparse_matmul_f32(x, st).astype(x.dtype)
    raise ValueError(f"unknown impl {impl!r}")


def ffn_w4a16(
    x: jax.Array,
    gate,
    up,
    down,
    *,
    activation: str = "swiglu",
    up_bias: jax.Array | None = None,
    down_bias: jax.Array | None = None,
    impl: str = "auto",
) -> jax.Array:
    """Whole FFN — ``down( act(x@gate) * (x@up) )`` — as ONE operator.

    Weights may be dense arrays, ``QuantizedTensor``s (W4A16) or
    ``SparseQuantizedTensor``s (log-scale sparse); ``activation`` is
    swiglu/geglu (gated) or gelu (ungated, optional biases).

    * ``impl="pallas"`` — the fused kernel (``kernels/ffn_fused.py``): one
      dispatch per MLP, the ``(tokens, d_ff)`` hidden state never leaves
      VMEM.  Falls back to the twin for operand mixes the kernel doesn't
      tile (non-128 quant groups, non-tile-uniform sparse down, ...).
    * ``impl="xla"``    — the blocked twin: same numerics contract
      (f32 scale-after-dot per quant group, f32 activation), no 16-bit
      weight materialization.  The hot path on CPU and in the dry run.
      Plain 16-bit weights keep the seed's exact unfused composition.
    * ``impl="ref"``    — the unfused 3-matmul oracle.
    """
    if impl == "auto":
        impl = _auto()
    gated = activation in ("swiglu", "geglu")
    if gated and (up_bias is not None or down_bias is not None):
        raise ValueError("gated activations take no FFN biases")
    ws = (gate, up, down) if gated else (up, down)
    quantized = any(
        isinstance(w, (QuantizedTensor, SparseQuantizedTensor)) for w in ws)
    if impl == "ref" or (impl == "xla" and not quantized):
        return _ref.ffn_ref(x, gate, up, down, activation=activation,
                            up_bias=up_bias, down_bias=down_bias)
    from repro.kernels import ffn_fused
    if impl == "pallas":
        variant = ffn_fused.fused_variant(
            x, gate, up, down, activation, up_bias, down_bias)
        if variant == "quant":
            return ffn_fused.ffn_fused_w4a16_pallas(
                x, gate if gated else None, up, down, activation=activation,
                up_bias=up_bias, down_bias=down_bias)
        if variant == "sparse":
            return ffn_fused.ffn_fused_sparse_pallas(
                x, gate if gated else None, up, down, activation=activation,
                up_bias=up_bias, down_bias=down_bias)
        if variant == "fp":
            return ffn_fused.ffn_fused_dense_pallas(
                x, gate if gated else None, up, down, activation=activation,
                up_bias=up_bias, down_bias=down_bias)
        _fallback("ffn_w4a16", "no fused kernel tiles these operands")
        impl = "xla"
    if impl == "xla":
        if not quantized:
            return _ref.ffn_ref(x, gate, up, down, activation=activation,
                                up_bias=up_bias, down_bias=down_bias)
        return ffn_fused.ffn_w4a16_xla(
            x, gate, up, down, activation=activation,
            up_bias=up_bias, down_bias=down_bias)
    raise ValueError(f"unknown impl {impl!r}")


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    impl: str = "auto",
) -> jax.Array:
    """Fused attention (MODE-0). q (b,hq,sq,d), k/v (b,hkv,skv,d)."""
    if impl == "auto":
        impl = _auto()
    if impl == "pallas":
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, scale=scale)
    if impl == "xla":
        if k.shape[2] >= 2048:
            # chunked flash recurrence: O(chunk^2) temporaries instead of
            # O(s^2) — the dense oracle at 32k context costs ~TB/device
            from repro.kernels.xla_attention import attention_chunked
            return attention_chunked(q, k, v, causal=causal, window=window,
                                     scale=scale)
        return _ref.attention_ref(q, k, v, causal=causal, window=window, scale=scale)
    raise ValueError(f"unknown impl {impl!r}")


def gather_paged_cache(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """Materialize a paged pool ``(P, g, bs, ...)`` as the contiguous
    per-slot cache ``(b, g, n_pages*bs, ...)`` a dense oracle expects —
    the layout inverse of the engine's block leasing (null-block pages
    gather finite garbage that true-length masking hides, exactly like
    stale rows in the slot layout)."""
    g = jnp.take(pool, page_table, axis=0)        # (b, n_pages, g, bs, ...)
    b, npg, heads, bs = g.shape[:4]
    g = jnp.moveaxis(g, 2, 1)                     # (b, g, n_pages, bs, ...)
    return g.reshape(b, heads, npg * bs, *g.shape[4:])


def _paged_kernel_ok(pool: jax.Array) -> bool:
    return pool.shape[2] >= 8    # page size tiles the kernel's KV block


def _materialize_ref_cache(q, k_cache, v_cache, k_scale, v_scale, page_table):
    """The ref oracle's operand prep: gather a paged pool contiguous, then
    drop any int8 quantization via a full-precision copy (the seed's path)."""
    k_full, v_full = k_cache, v_cache
    ks_full, vs_full = k_scale, v_scale
    if page_table is not None:
        k_full = gather_paged_cache(k_full, page_table)
        v_full = gather_paged_cache(v_full, page_table)
        if k_scale is not None:
            ks_full = gather_paged_cache(ks_full, page_table)
            vs_full = gather_paged_cache(vs_full, page_table)
    if k_scale is not None:
        from repro.models.attention import dequantize_kv
        k_full = dequantize_kv(k_full, ks_full, q.dtype)
        v_full = dequantize_kv(v_full, vs_full, q.dtype)
    return k_full, v_full


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    length: jax.Array | int,
    *,
    window: int | None = None,
    scale: float | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    impl: str = "auto",
    page_table: jax.Array | None = None,
) -> jax.Array:
    """One-token decode attention against a preallocated KV cache.

    q (b, hq, 1, d); caches (b, hkv, MAX, d) — fp, or int8 with
    ``k_scale``/``v_scale`` (b, hkv, MAX, 1), in which case dequant is fused
    into the attention (scale-after-dot; the cache is read at 1 byte/value).
    With ``page_table`` (b, n_pages) the caches are shared paged pools
    ``(P, hkv, bs, d)`` (scales ``(P, hkv, bs, 1)``) and every impl
    translates logical blocks through the table.

    * ``impl="pallas"`` — the flash-decoding kernel: per-row KV-block
      skipping, bytes and FLOPs scale with each row's actual context.
    * ``impl="xla"``    — the length-blocked twin: a while_loop over KV
      blocks bounded by max(lengths), per-row masking.  The hot path on CPU
      and in the distributed serve_step (length masks keep addresses static
      under jit — the paper's MAX-token trick).
    * ``impl="ref"``    — the dense full-cache oracle (dequantizes the whole
      cache first when quantized; gathers a paged pool contiguous first):
      the numerics ground truth and the bandwidth baseline
      ``benchmarks/decode_bench.py`` measures against.
    """
    if impl == "auto":
        impl = _auto()
    if impl == "pallas":
        from repro.kernels.decode_flash import (
            DEFAULT_BLOCK_KV, decode_flash_attention_pallas, kv_block_size)
        ok = (_paged_kernel_ok(k_cache) if page_table is not None
              else kv_block_size(k_cache.shape[2], DEFAULT_BLOCK_KV) >= 8)
        if ok:
            return decode_flash_attention_pallas(
                q, k_cache, v_cache, length, window=window, scale=scale,
                k_scale=k_scale, v_scale=v_scale, page_table=page_table)
        _fallback("decode_attention", "cache length tiles too poorly")
        impl = "xla"
    if impl == "xla":
        from repro.kernels.xla_attention import decode_attention_blocked
        return decode_attention_blocked(
            q, k_cache, v_cache, length, window=window, scale=scale,
            k_scale=k_scale, v_scale=v_scale, page_table=page_table)
    if impl == "ref":
        k_full, v_full = _materialize_ref_cache(
            q, k_cache, v_cache, k_scale, v_scale, page_table)
        return _ref.decode_attention_ref(
            q, k_full, v_full, length, window=window, scale=scale)
    raise ValueError(f"unknown impl {impl!r}")


def mixed_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    q_lens: jax.Array,
    *,
    window: int | None = None,
    scale: float | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    impl: str = "auto",
    page_table: jax.Array | None = None,
) -> jax.Array:
    """Mixed prefill/decode attention against a preallocated KV cache.

    The chunked generalization of ``decode_attention``: q (b, hq, C, d)
    carries ``q_lens[b]`` live queries per row (1 = a decoding row, up to C
    = a row mid-prefill), ``lengths`` (b,) is the valid context *including*
    this step's chunk, and intra-chunk causality is masked per query — one
    dispatch advances a mixed batch (the serving tick's shape contract).
    ``page_table`` switches all three impls to the paged pool layout.

    * ``impl="pallas"`` — the flash-decoding kernel with a chunk q-block:
      per-row KV-block skipping, the chunk rides the same DMA pipeline.
    * ``impl="xla"``    — the length-blocked twin (``mixed_attention_blocked``),
      sharing its block walker with the decode path.
    * ``impl="ref"``    — the dense full-cache oracle.
    """
    if impl == "auto":
        impl = _auto()
    if impl == "pallas":
        from repro.kernels.decode_flash import (
            DEFAULT_BLOCK_KV, kv_block_size, mixed_flash_attention_pallas)
        ok = (_paged_kernel_ok(k_cache) if page_table is not None
              else kv_block_size(k_cache.shape[2], DEFAULT_BLOCK_KV) >= 8)
        if ok:
            return mixed_flash_attention_pallas(
                q, k_cache, v_cache, lengths, q_lens, window=window,
                scale=scale, k_scale=k_scale, v_scale=v_scale,
                page_table=page_table)
        _fallback("mixed_attention", "cache length tiles too poorly")
        impl = "xla"
    if impl == "xla":
        from repro.kernels.xla_attention import mixed_attention_blocked
        return mixed_attention_blocked(
            q, k_cache, v_cache, lengths, q_lens, window=window, scale=scale,
            k_scale=k_scale, v_scale=v_scale, page_table=page_table)
    if impl == "ref":
        k_full, v_full = _materialize_ref_cache(
            q, k_cache, v_cache, k_scale, v_scale, page_table)
        return _ref.mixed_attention_ref(
            q, k_full, v_full, lengths, q_lens, window=window, scale=scale)
    raise ValueError(f"unknown impl {impl!r}")
