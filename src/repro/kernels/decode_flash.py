"""Pallas TPU kernel: length-aware batched flash-decoding with fused int8-KV
dequant (EdgeLLM §IV-B static MAX-token addressing + Fig. 4 mixed-precision
datapath, applied to the decode hot path).

One-token decode against a preallocated ``(B, hkv, MAX, d)`` cache is the
memory-bound half of serving: every step streams the KV cache once and does
O(1) FLOPs per byte.  The paper wins its HBM-bandwidth-utilization metric by
(a) never touching addresses past the valid context and (b) keeping the
quantized operand packed all the way into the PE array, rescaling partial
sums afterwards.  This kernel is the TPU restatement of both:

* **Grid** ``(B, hkv, MAX/bk)`` with the KV-block axis innermost
  ("arbitrary").  ``lengths: (B,)`` and ``q_lens: (B,)`` ride in as
  scalar-prefetch operands (SMEM), so both the kernel body and the BlockSpec
  index maps can read them.

* **Mixed q-block.**  The query block packs ``q_lens[b]`` live queries per
  row (1 for a decoding row, C for a row mid-prefill), so one fixed
  executable advances a mixed prefill/decode batch — the paper's "one data
  shape for every operator" contract (§IV universal data parallelism)
  applied to the serving tick.  Query j of row b sits at absolute position
  ``lengths[b] - q_lens[b] + j``; intra-chunk causality is a per-position
  mask, and dead queries (j >= q_lens[b]) end with ``l == 0`` -> zeros.

* **Per-row block skipping.**  Blocks at or past row ``b``'s valid context
  are (1) skipped in compute via ``pl.when`` and (2) *elided in the DMA*:
  the K/V index maps clamp the block index into the row's live range, and
  Mosaic's pipeline skips the copy when consecutive grid steps map the same
  block.  Compute AND bytes scale with ``ceil(length_b / bk)`` instead of
  ``MAX/bk`` — the paper's "only the valid tokens travel" contract.

* **GQA via query-group packing.**  The ``rep = hq/hkv`` query heads that
  share one KV head are packed (together with the chunk axis) into a single
  ``(rep*C, d)`` q block, so each KV byte is read once per *group*, never
  ``jnp.repeat``-ed into an ``hq``-sized cache copy.

* **Fused int8→fp dequant.**  With an int8 cache the kernel reads 1
  byte/value from HBM, does the integer-exact dot in bf16 (int8 values are
  exactly representable), and multiplies the per-token scale into the
  **partial sum** — the paper's Fig. 4 Stage-3 scale-after-accumulate, same
  contract as ``w4a16_matmul_pallas``.  The full-precision cache copy the
  old path materialized every step never exists.

* **Rolling-SWA addressing.**  A rolling buffer (``cache_len <= window``)
  stores the last ``cache_len`` tokens at slot ``pos mod cache_len``; RoPE
  is applied before caching and softmax is permutation-invariant, so the
  kernel just treats every slot below ``min(length, MAX)`` as valid (the
  caller clamps ``lengths``).  A non-rolling window additionally raises the
  *first* live block to the first block the earliest query's window reaches.

* **(m, l, acc) in VMEM scratch.**  Softmax running stats and the output
  accumulator stay resident across the KV-block axis — the G-VSA
  "partial sums never leave the array" discipline.

Roofline (per decode step, per layer): bytes ≈
``sum_b ceil(len_b/bk) * bk * d * hkv * kv_bytes * 2`` (+ ``4`` scale
bytes/token for int8) vs the dense ref's ``B * MAX * d * hkv * elt * 2`` —
at length 128 in a 2048-slot fp16 cache that is 16× fewer bytes, and int8
halves the per-byte cost again while the seed's dequantize-everything path
*tripled* it (int8 read + fp write + fp read).  A C-token chunk amortizes
the same KV stream over C queries — chunked prefill is the compute-bound
counterpart riding the identical pipeline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_compat import default_interpret

__all__ = [
    "decode_flash_attention_pallas",
    "mixed_flash_attention_pallas",
    "kv_block_size",
    "DEFAULT_BLOCK_KV",
]

_NEG_INF = -1e30
_STATS = 128  # lane-replicated softmax statistics width
DEFAULT_BLOCK_KV = 128  # KV tile; ops.decode_attention gates tileability on it


def kv_block_size(max_len: int, block_kv: int) -> int:
    """Largest divisor of ``max_len`` that is <= ``block_kv``."""
    bk = min(block_kv, max_len)
    while max_len % bk:
        bk -= 1
    return bk


def _kernel(len_ref, qlen_ref, *refs, scale, window, bk, max_len, rep, chunk,
            quant, paged=False):
    if paged:
        # the page table is consumed by the BlockSpec index maps only — the
        # body sees logical positions; physical placement is pure DMA routing
        _pt_ref, *refs = refs
    q_ref, k_ref, v_ref, *rest = refs
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]           # total valid context incl. this step's chunk
    qlen = qlen_ref[b]            # live queries this step (1 = plain decode)
    valid_len = jnp.clip(length, 1, max_len)
    k_start = ik * bk
    live = k_start < valid_len
    if window is not None:
        # earliest query position is length - qlen; its window floor is
        # (length - qlen) - window + 1
        live = jnp.logical_and(
            live, k_start + bk > length - qlen - window + 1)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0]                                    # (rep*chunk, d)
        k = k_ref[0, 0]                                    # (bk, d)
        s = jax.lax.dot_general(
            q, k.astype(q.dtype),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # (rep*chunk, bk)
        if quant:
            # scale-after-dot: the int8 dot is integer-exact in bf16; the
            # per-token fp scale multiplies the finished partial sum
            s = s * ks_ref[0, 0]                           # (1, bk)
        s = s * scale

        rows = rep * chunk
        pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
        j = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0) % chunk
        q_pos = length - qlen + j                           # per-query position
        valid = jnp.logical_and(pos < jnp.minimum(length, max_len),
                                pos <= q_pos)               # intra-chunk causal
        valid = jnp.logical_and(valid, j < qlen)            # dead query rows
        if window is not None:
            valid = jnp.logical_and(valid, pos > q_pos - window)
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_ref[:, :1]                              # (rows, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)                       # dead rows: l == 0
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)

        if quant:
            # fold the per-token v scale into the probabilities (linear in v)
            p = p * vs_ref[0, 0]
        pv = jax.lax.dot_general(
            p.astype(q.dtype), v_ref[0, 0].astype(q.dtype),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (rows, d)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _done():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "scale", "block_kv", "interpret"))
def mixed_flash_attention_pallas(
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
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool | None = None,
    page_table: jax.Array | None = None,
) -> jax.Array:
    """Mixed prefill/decode batched attention (chunk q-block).

    ``q`` (B, hq, C, d); caches (B, hkv, MAX, d) in fp or int8 (with
    ``k_scale``/``v_scale`` (B, hkv, MAX, 1) f32); ``lengths`` (B,) =
    per-row valid context *including* this step's chunk; ``q_lens`` (B,) =
    live queries per row (1 = decoding row, up to C = mid-prefill row; the
    padding queries return zeros).  Rolling-SWA callers pass ``lengths``
    pre-clamped to the buffer size and ``window=None``.  Returns
    (B, hq, C, d) in q.dtype.

    Paged layout: ``page_table`` (B, n_pages) int32 rides in as a THIRD
    scalar-prefetch operand; the caches are shared pools
    ``(P, hkv, bs, d)`` (scales ``(P, hkv, bs)``-shaped), the KV tile is
    the page size, and the K/V BlockSpec index maps translate the logical
    block id to ``page_table[b, ik]`` — the length-clamp DMA elision
    composes unchanged (clamped steps revisit the last live page's physical
    block, so Mosaic skips the copy).
    """
    if interpret is None:
        interpret = default_interpret()
    b, hq, chunk, d = q.shape
    hkv = k_cache.shape[1]
    paged = page_table is not None
    if paged:
        bk = k_cache.shape[2]                 # the page size IS the KV tile
        n_blocks = page_table.shape[1]
        max_len = n_blocks * bk
    else:
        max_len = k_cache.shape[2]
        bk = kv_block_size(max_len, block_kv)
        n_blocks = max_len // bk
    if hq % hkv:
        raise ValueError(f"hq={hq} not a multiple of hkv={hkv}")
    rep = hq // hkv
    rows = rep * chunk
    quant = k_scale is not None
    scale_v = scale if scale is not None else float(1.0 / (d ** 0.5))

    lengths = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32).reshape(-1), (b,))
    q_lens = jnp.broadcast_to(
        jnp.asarray(q_lens, jnp.int32).reshape(-1), (b,))
    # (B, hq, C, d) -> (B, hkv, rep*C, d): row r*C + j is (group head r, query j)
    q4 = q.reshape(b, hkv, rep, chunk, d).reshape(b, hkv, rows, d)

    def _live_block(ib, ik, len_ref, qlen_ref):
        # clamp into the row's live block range: steps outside it revisit an
        # already-resident block, so Mosaic issues no DMA for them
        vl = jnp.clip(len_ref[ib], 1, max_len)
        last = (vl - 1) // bk
        if window is None:
            first = 0
        else:
            first = jnp.minimum(jnp.maximum(
                (len_ref[ib] - qlen_ref[ib] - window + 1) // bk, 0), last)
        return jnp.clip(ik, first, last)

    def kv_map(ib, h, ik, len_ref, qlen_ref, *pt_ref):
        lg = _live_block(ib, ik, len_ref, qlen_ref)
        if paged:     # logical -> physical page translation
            return (pt_ref[0][ib, lg], h, 0, 0)
        return (ib, h, lg, 0)

    def kv_scale_map(ib, h, ik, len_ref, qlen_ref, *pt_ref):
        blk, h, lg, _ = kv_map(ib, h, ik, len_ref, qlen_ref, *pt_ref)
        return (blk, h, 0, lg)

    def q_map(ib, h, ik, len_ref, qlen_ref, *pt_ref):
        return (ib, h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, rows, d), q_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
        pl.BlockSpec((1, 1, bk, d), kv_map),
    ]
    operands = [q4, k_cache, v_cache]
    if quant:
        # scales ride as (.., 1, tokens): a block's last two dims must tile
        # (8k, 128m) or span the array, and a lone token row does neither
        in_specs += [
            pl.BlockSpec((1, 1, 1, bk), kv_scale_map),
            pl.BlockSpec((1, 1, 1, bk), kv_scale_map),
        ]
        scale_shape = ((k_cache.shape[0], hkv, 1, bk) if paged
                       else (b, hkv, 1, max_len))
        operands += [
            k_scale.astype(jnp.float32).reshape(scale_shape),
            v_scale.astype(jnp.float32).reshape(scale_shape),
        ]

    kernel = functools.partial(
        _kernel, scale=scale_v, window=window, bk=bk, max_len=max_len,
        rep=rep, chunk=chunk, quant=quant, paged=paged)

    prefetch = [lengths, q_lens]
    if paged:
        prefetch.append(jnp.asarray(page_table, jnp.int32))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, hkv, n_blocks),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, rows, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((rows, _STATS), jnp.float32),
                pltpu.VMEM((rows, _STATS), jnp.float32),
                pltpu.VMEM((rows, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="mixed_flash_attention",
        interpret=interpret,
    )(*prefetch, *operands)
    return out.reshape(b, hkv, rep, chunk, d).reshape(b, hq, chunk, d)


def decode_flash_attention_pallas(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    window: int | None = None,
    scale: float | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool | None = None,
    page_table: jax.Array | None = None,
) -> jax.Array:
    """One-token batched decode attention: the chunk=1 specialization.

    ``q`` (B, hq, 1, d); caches (B, hkv, MAX, d) in fp or int8 (with
    ``k_scale``/``v_scale`` (B, hkv, MAX, 1) f32) — or shared pools with a
    ``page_table``; ``lengths`` scalar or (B,) = per-row valid context
    *including* the new token.  Rolling-SWA callers pass ``lengths``
    pre-clamped to the buffer size and ``window=None``.  Returns
    (B, hq, 1, d) in q.dtype.
    """
    b, hq, sq, d = q.shape
    if sq != 1:
        raise ValueError(f"decode kernel is single-token (sq={sq}); use "
                         "mixed_flash_attention_pallas for chunked queries")
    return mixed_flash_attention_pallas(
        q, k_cache, v_cache, lengths, jnp.ones((b,), jnp.int32),
        window=window, scale=scale, k_scale=k_scale, v_scale=v_scale,
        block_kv=block_kv, interpret=interpret, page_table=page_table)
