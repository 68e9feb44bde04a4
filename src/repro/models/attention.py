"""Attention module: GQA/MQA, RoPE/M-RoPE, qk-norm, SWA, KV cache.

Train/prefill path goes through ``ops.attention`` (Pallas flash kernel on
TPU, dense oracle on CPU); decode path uses ``ops.decode_attention`` against
a preallocated MAX-token cache (the paper's static-address trick, §IV-B).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import layers
from repro.models.layers import Params, dense_init, linear


def _impl(cfg) -> str:
    """The ``ops`` implementation every attention call of this model uses:
    the Pallas kernels under ``use_kernels``, the XLA twins otherwise."""
    return "pallas" if cfg.use_kernels else "xla"


def attn_init(key, cfg, d_model: int | None = None) -> Params:
    d = d_model or cfg.d_model
    hd, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], d, hq * hd, cfg.dtype),
        "wk": dense_init(ks[1], d, hkv * hd, cfg.dtype),
        "wv": dense_init(ks[2], d, hkv * hd, cfg.dtype),
        "wo": dense_init(ks[3], hq * hd, d, cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), cfg.dtype)
        p["bk"] = jnp.zeros((hkv * hd,), cfg.dtype)
        p["bv"] = jnp.zeros((hkv * hd,), cfg.dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), cfg.dtype)
        p["k_norm"] = jnp.ones((hd,), cfg.dtype)
    return p


def _project_qkv(cfg, p: Params, x: jax.Array, positions):
    b, s, _ = x.shape
    hd, hq, hkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    uk = cfg.use_kernels
    q = linear(x, p["wq"], p.get("bq"), use_kernels=uk).reshape(b, s, hq, hd)
    k = linear(x, p["wk"], p.get("bk"), use_kernels=uk).reshape(b, s, hkv, hd)
    v = linear(x, p["wv"], p.get("bv"), use_kernels=uk).reshape(b, s, hkv, hd)
    q = q.transpose(0, 2, 1, 3)   # (b, h, s, d)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = layers.rmsnorm(q, p["q_norm"])
        k = layers.rmsnorm(k, p["k_norm"])
    if cfg.rope_type == "standard":
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope_type == "mrope":
        q = layers.apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = layers.apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    return q, k, v


def attn_apply(cfg, p: Params, x: jax.Array, positions, *,
               causal: bool = True) -> jax.Array:
    """Full-sequence attention (training / prefill)."""
    b, s, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, positions)
    o = ops.attention(q, k, v, causal=causal, window=cfg.window,
                      impl=_impl(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    return linear(o, p["wo"], use_kernels=cfg.use_kernels)


# -- paged KV layout ---------------------------------------------------------
#
# The slot cache reserves ``max_len`` rows per slot; the paged layout leases
# fixed-size blocks from ONE shared pool instead.  Per layer the pool leaf is
# ``(n_blocks + 1, hkv, block_size, hd)`` — the LAST block is the null block:
# writes that must not land (dead chunk queries, masked decode rows) are
# routed there, and page-table entries of pages a slot has not leased point
# there too, so a stale table can never alias a live block.  The page table
# ``(B, pages_per_slot)`` of physical block ids is HOST-managed (the engine
# allocates/frees blocks) and rides into each dispatch as a plain operand —
# logical position ``p`` of slot ``b`` lives at
# ``pool[page_table[b, p // bs], :, p % bs]``.
#
# Rewind contract (speculative rollback): shrinking a row's ``lengths[b]``
# is ALWAYS safe — every read masks by length, so stale K/V past the new
# length (rejected draft tokens) is invisible and later writes overwrite it
# in place.  Blocks wholly past ``ceil(new_len / bs)`` may be returned to
# the pool, provided their page-table entries are re-pointed at the null
# block FIRST (a freed block must never stay reachable through a stale
# table row); the partially-used tail block must stay leased.

def paged_blocks_for(length: int, block_size: int) -> int:
    """Blocks needed to cover ``length`` logical tokens (ceil division) —
    the one formula the engine's lease/reserve/rewind accounting shares."""
    return -(-length // block_size)


def paged_geometry(cfg, max_len: int) -> tuple[int, int]:
    """(block_size, pages_per_slot) for a paged cache addressing ``max_len``
    logical positions per slot (the last page may be partially addressable)."""
    bs = cfg.kv_block_size
    return bs, -(-max_len // bs)


def paged_pool_blocks(cfg, batch: int, max_len: int) -> int:
    """Usable (non-null) pool blocks: ``cfg.kv_pool_blocks`` or the slot
    layout's exact capacity ``batch * pages_per_slot``."""
    _, n_pages = paged_geometry(cfg, max_len)
    return cfg.kv_pool_blocks or batch * n_pages


def default_page_table(batch: int, pool_blocks: int) -> jax.Array:
    """Linear identity table for a default-sized pool (blocks 0..B*pages-1,
    slot ``b`` owning the contiguous run ``b*pages .. (b+1)*pages-1``) — the
    layout bit-equivalent to the slot cache.  ``pool_blocks`` is the pool
    leaf's leading dim INCLUDING the null block."""
    n_pages = (pool_blocks - 1) // batch
    return jnp.arange(batch * n_pages, dtype=jnp.int32).reshape(batch, n_pages)


def init_kv_cache_paged(cfg, batch: int, max_len: int) -> Params:
    """Shared-pool paged KV leaves (one layer): ``(P+1, hkv, bs, hd)``."""
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    bs, _ = paged_geometry(cfg, max_len)
    p = paged_pool_blocks(cfg, batch, max_len) + 1   # + null block (last)
    if cfg.kv_quant == "int8":
        return {
            "k": jnp.zeros((p, hkv, bs, hd), jnp.int8),
            "v": jnp.zeros((p, hkv, bs, hd), jnp.int8),
            "k_scale": jnp.zeros((p, hkv, bs, 1), jnp.float32),
            "v_scale": jnp.zeros((p, hkv, bs, 1), jnp.float32),
        }
    return {
        "k": jnp.zeros((p, hkv, bs, hd), cfg.dtype),
        "v": jnp.zeros((p, hkv, bs, hd), cfg.dtype),
    }


def _paged_token_write(pool: jax.Array, new: jax.Array, page_table: jax.Array,
                       pos: jax.Array, mask: jax.Array | None) -> jax.Array:
    """Scatter one token per row into the pool.  ``new`` (b, hkv, w);
    ``pos`` (b,) logical positions; rows with ``mask == False`` are routed to
    the null block (last pool row) — their write never lands."""
    b = new.shape[0]
    bs = pool.shape[2]
    null = pool.shape[0] - 1
    blk = jnp.take_along_axis(page_table, (pos // bs)[:, None], axis=1)[:, 0]
    if mask is not None:
        blk = jnp.where(mask, blk, null)
    return pool.at[blk, :, pos % bs].set(new.astype(pool.dtype))


def _paged_chunk_write(pool: jax.Array, new: jax.Array, page_table: jax.Array,
                       starts: jax.Array, q_lens: jax.Array) -> jax.Array:
    """Per-row variable-length chunk scatter through the page table.

    ``new`` (b, hkv, C, w); row ``b`` writes its first ``q_lens[b]`` chunk
    tokens at logical positions ``starts[b] ..``; dead chunk positions are
    routed to the null block, so a ``q_lens == 0`` row is exactly a no-op —
    the paged counterpart of ``_chunk_write``'s read-modify-write masking.
    """
    b, _, c, _ = new.shape
    bs = pool.shape[2]
    null = pool.shape[0] - 1
    n_pos = page_table.shape[1] * bs
    j = jnp.arange(c, dtype=jnp.int32)
    pos = jnp.clip(starts[:, None] + j[None, :], 0, n_pos - 1)   # (b, C)
    live = j[None, :] < q_lens[:, None]
    blk = jnp.take_along_axis(page_table, pos // bs, axis=1)     # (b, C)
    blk = jnp.where(live, blk, null)
    vals = new.transpose(0, 2, 1, 3)                             # (b, C, hkv, w)
    return pool.at[blk, :, pos % bs].set(vals.astype(pool.dtype))


def init_kv_cache(cfg, batch: int, max_len: int, d_model=None) -> Params:
    if cfg.kv_layout == "paged":
        return init_kv_cache_paged(cfg, batch, max_len)
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    if cfg.kv_quant == "int8":
        # per-(token, head) absmax scale over head_dim — the paper's
        # block-scale packing applied to the dynamic operand (beyond-paper)
        return {
            "k": jnp.zeros((batch, hkv, max_len, hd), jnp.int8),
            "v": jnp.zeros((batch, hkv, max_len, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, hkv, max_len, 1), jnp.float32),
            "v_scale": jnp.zeros((batch, hkv, max_len, 1), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, hkv, max_len, hd), cfg.dtype),
        "v": jnp.zeros((batch, hkv, max_len, hd), cfg.dtype),
    }


def kv_cache_slot_axes(cfg, axis: int = 1) -> Params:
    """Pytree (matching ``init_kv_cache`` structure) of batch/slot axes.

    Callers stack per-layer caches along leading axes, so the request-slot
    axis of each leaf is ``axis`` (1 for a single (layers, B, ...) stack).
    Consumed by ``models.api.insert_request`` / ``evict_slot``.

    Paged leaves are SHARED pools — no slot axis exists, marked with the
    ``-1`` sentinel: insert/evict/per-row selects skip them (stale pool data
    hides behind true-length masking at block granularity, and writes by
    masked rows are routed to the null block instead of being reverted).
    """
    if cfg.kv_layout == "paged":
        axis = -1
    axes: Params = {"k": axis, "v": axis}
    if cfg.kv_quant == "int8":
        axes["k_scale"] = axis
        axes["v_scale"] = axis
    return axes


def quantize_kv(t: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(…, hd) -> int8 values + per-vector absmax scale."""
    a = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(a / 127.0, 1e-10)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def attn_prefill(cfg, p: Params, x: jax.Array, positions, cache: Params):
    """Prefill: run full attention AND populate the cache.

    With a sliding-window (rolling) cache smaller than the prompt, only the
    last ``cache_len`` tokens' K/V are retained — exactly the set SWA decode
    will ever attend to."""
    if cfg.kv_layout == "paged":
        raise ValueError(
            "paged KV caches have no full-sequence prefill path — serve "
            "through mixed_step/decode_step (chunked admission); the "
            "standalone api.prefill is a slot-layout/training surface")
    b, s, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, positions)
    o = ops.attention(q, k, v, causal=True, window=cfg.window,
                      impl=_impl(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    out = linear(o, p["wo"], use_kernels=cfg.use_kernels)
    cache_len = cache["k"].shape[2]
    if cache_len < s:
        k = k[:, :, -cache_len:]
        v = v[:, :, -cache_len:]
    if cfg.kv_quant == "int8":
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        cache = {
            "k": jax.lax.dynamic_update_slice(cache["k"], kq, (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], vq, (0, 0, 0, 0)),
            "k_scale": jax.lax.dynamic_update_slice(cache["k_scale"], ks,
                                                    (0, 0, 0, 0)),
            "v_scale": jax.lax.dynamic_update_slice(cache["v_scale"], vs,
                                                    (0, 0, 0, 0)),
        }
        return out, cache
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, 0, 0, 0)),
    }
    return out, cache


def _chunk_write(cache_leaf: jax.Array, new: jax.Array, starts: jax.Array,
                 q_lens: jax.Array) -> jax.Array:
    """Scatter per-row variable-length chunks into a (B, hkv, L, w) cache.

    Row ``b`` writes ``new[b, :, :q_lens[b]]`` at positions
    ``starts[b] .. starts[b] + q_lens[b] - 1`` — a read-modify-write of one
    C-wide block per row, so positions outside the live span keep their
    current cache values exactly (a q_lens == 0 row is a no-op, and a row
    near the MAX boundary never clobbers valid neighbors the way a clamped
    ``dynamic_update_slice`` of the raw chunk would).  Callers guarantee
    ``starts + q_lens <= L``.
    """
    c = new.shape[2]
    cache_len = cache_leaf.shape[2]
    idx = jnp.arange(c)

    def one(dst, blk, start, ql):
        off = jnp.clip(start, 0, cache_len - c)
        delta = start - off            # 0 unless the block straddles the end
        cur = jax.lax.dynamic_slice_in_dim(dst, off, c, axis=1)
        shifted = jnp.roll(blk, delta, axis=1)
        mask = (idx >= delta) & (idx < delta + ql)
        merged = jnp.where(mask[None, :, None], shifted.astype(dst.dtype), cur)
        return jax.lax.dynamic_update_slice_in_dim(dst, merged, off, axis=1)

    return jax.vmap(one)(cache_leaf, new, jnp.asarray(starts, jnp.int32),
                         jnp.asarray(q_lens, jnp.int32))


def attn_mixed(cfg, p: Params, x: jax.Array, positions, cache: Params,
               lengths: jax.Array, q_lens: jax.Array, *,
               page_table: jax.Array | None = None):
    """Mixed prefill/decode attention step.  x (b, C, d); ``lengths`` (b,) =
    valid cache tokens BEFORE this step; ``q_lens`` (b,) = live new tokens
    per row (1 = decoding row, up to C = mid-prefill row; the rest of the
    chunk is padding).  Writes each row's live K/V at its true positions —
    no left-pad bucket writes — then attends over the cache with intra-chunk
    causal masking.  Requires ``lengths + q_lens <= cache_len`` (the serving
    scheduler's cache-room invariant), which also means a rolling-SWA buffer
    never wraps here — so the rolling case degenerates to the non-rolling
    one and ``cfg.window`` masking applies directly.

    Paged layout: cache leaves are shared pools, ``page_table`` (b, pages)
    routes both the chunk K/V scatter (dead positions to the null block) and
    the kernels' logical→physical block translation.
    """
    b, c, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, positions)
    lengths = jnp.asarray(lengths, jnp.int32)
    q_lens = jnp.asarray(q_lens, jnp.int32)
    total = lengths + q_lens

    if cfg.kv_layout == "paged":
        if page_table is None:
            page_table = default_page_table(b, cache["k"].shape[0])
        if cfg.kv_quant == "int8":
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            new_cache = {
                "k": _paged_chunk_write(cache["k"], kq, page_table,
                                        lengths, q_lens),
                "v": _paged_chunk_write(cache["v"], vq, page_table,
                                        lengths, q_lens),
                "k_scale": _paged_chunk_write(cache["k_scale"], ks,
                                              page_table, lengths, q_lens),
                "v_scale": _paged_chunk_write(cache["v_scale"], vs,
                                              page_table, lengths, q_lens),
            }
            o = ops.mixed_attention(q, new_cache["k"], new_cache["v"], total,
                                    q_lens, window=cfg.window,
                                    k_scale=new_cache["k_scale"],
                                    v_scale=new_cache["v_scale"],
                                    page_table=page_table,
                                    impl=_impl(cfg))
        else:
            new_cache = {
                "k": _paged_chunk_write(cache["k"], k, page_table,
                                        lengths, q_lens),
                "v": _paged_chunk_write(cache["v"], v, page_table,
                                        lengths, q_lens),
            }
            o = ops.mixed_attention(q, new_cache["k"], new_cache["v"], total,
                                    q_lens, window=cfg.window,
                                    page_table=page_table,
                                    impl=_impl(cfg))
        o = o.transpose(0, 2, 1, 3).reshape(b, c, cfg.n_heads * cfg.head_dim)
        return linear(o, p["wo"], use_kernels=cfg.use_kernels), new_cache

    if cfg.kv_quant == "int8":
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        new_cache = {
            "k": _chunk_write(cache["k"], kq, lengths, q_lens),
            "v": _chunk_write(cache["v"], vq, lengths, q_lens),
            "k_scale": _chunk_write(cache["k_scale"], ks, lengths, q_lens),
            "v_scale": _chunk_write(cache["v_scale"], vs, lengths, q_lens),
        }
        o = ops.mixed_attention(q, new_cache["k"], new_cache["v"], total,
                                q_lens, window=cfg.window,
                                k_scale=new_cache["k_scale"],
                                v_scale=new_cache["v_scale"],
                                impl=_impl(cfg))
    else:
        new_cache = {
            "k": _chunk_write(cache["k"], k, lengths, q_lens),
            "v": _chunk_write(cache["v"], v, lengths, q_lens),
        }
        o = ops.mixed_attention(q, new_cache["k"], new_cache["v"], total,
                                q_lens, window=cfg.window,
                                impl=_impl(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(b, c, cfg.n_heads * cfg.head_dim)
    out = linear(o, p["wo"], use_kernels=cfg.use_kernels)
    return out, new_cache


def attn_decode(cfg, p: Params, x: jax.Array, positions, cache: Params,
                lengths: jax.Array, *, page_table: jax.Array | None = None,
                write_mask: jax.Array | None = None):
    """One-token decode.  x (b, 1, d); lengths (b,) = context length
    *including* the new token.

    Paged layout: the new K/V scatters through ``page_table`` into the
    shared pool; ``write_mask`` (b,) bool routes masked rows' writes to the
    null block — the paged replacement for the slot layout's per-row
    select-revert (a pool has no slot axis to select over).
    """
    b = x.shape[0]
    q, k, v = _project_qkv(cfg, p, x, positions)
    # write the new K/V at position lengths-1 (static max-token addressing).
    lengths = jnp.asarray(lengths)
    # shard_map flash-decoding: cache stays sequence-sharded (slot layout)
    # or block-home-sharded (paged pool), LSE merge across shards
    # (EXPERIMENTS.md §Perf qwen3-decode)
    from repro.parallel import decode_attn
    from repro.parallel.hints import active_mesh
    mesh = active_mesh()
    if cfg.kv_layout == "paged":
        # the sharded gate consults the POOL extent (rows incl. null) and
        # must fire before the single-program paged path; no rolling-SWA
        # variant exists, so windowed configs stay single-program
        if cfg.window is None and decode_attn.usable(
                mesh, b, cfg.n_heads, cfg.n_kv_heads, cache["k"].shape[0],
                lengths, paged=True):
            return _attn_decode_paged_sharded(cfg, p, q, k, v, cache,
                                              lengths, page_table,
                                              write_mask, mesh)
        return _attn_decode_paged(cfg, p, q, k, v, cache, lengths,
                                  page_table, write_mask)
    cache_len = cache["k"].shape[2]
    rolling = cfg.window is not None and cache_len <= cfg.window

    if decode_attn.usable(mesh, b, cfg.n_heads, cfg.n_kv_heads,
                          cache_len, lengths, paged=False):
        scales = ((cache["k_scale"], cache["v_scale"])
                  if cfg.kv_quant == "int8" else None)
        o, new_cache = decode_attn.decode_attention_sharded(
            q, k, v, cache["k"], cache["v"], lengths, mesh, rolling=rolling,
            scales=scales)
        o = o.transpose(0, 2, 1, 3).reshape(b, 1, cfg.n_heads * cfg.head_dim)
        out = linear(o, p["wo"], use_kernels=cfg.use_kernels)
        return out, new_cache
    if rolling:
        # SWA rolling buffer: slot = (pos mod window).  RoPE is applied
        # before caching, and softmax is permutation-invariant, so slot
        # order inside the buffer is irrelevant.
        write_idx = (lengths - 1) % cache_len
        attn_len = jnp.minimum(lengths, cache_len)
        attn_window = None          # every valid slot participates
    else:
        write_idx = lengths - 1
        attn_len = lengths
        attn_window = cfg.window
    if cfg.kv_quant == "int8":
        # unsharded path: quantized write + FUSED dequant attention — the
        # int8 cache and its scales go straight into ops.decode_attention,
        # which rescales partial sums in-kernel (no full-precision copy)
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        if lengths.ndim == 0:
            new_cache = {
                "k": jax.lax.dynamic_update_slice(
                    cache["k"], kq, (0, 0, write_idx, 0)),
                "v": jax.lax.dynamic_update_slice(
                    cache["v"], vq, (0, 0, write_idx, 0)),
                "k_scale": jax.lax.dynamic_update_slice(
                    cache["k_scale"], ks, (0, 0, write_idx, 0)),
                "v_scale": jax.lax.dynamic_update_slice(
                    cache["v_scale"], vs, (0, 0, write_idx, 0)),
            }
        else:
            # ragged batch (slot-based serving): per-row scatter
            def upd(c, new, l):
                return jax.lax.dynamic_update_slice(c, new, (0, l, 0))
            new_cache = {
                "k": jax.vmap(upd)(cache["k"], kq, write_idx),
                "v": jax.vmap(upd)(cache["v"], vq, write_idx),
                "k_scale": jax.vmap(upd)(cache["k_scale"], ks, write_idx),
                "v_scale": jax.vmap(upd)(cache["v_scale"], vs, write_idx),
            }
        o = ops.decode_attention(q, new_cache["k"], new_cache["v"], attn_len,
                                 window=attn_window,
                                 k_scale=new_cache["k_scale"],
                                 v_scale=new_cache["v_scale"],
                                 impl=_impl(cfg))
        o = o.transpose(0, 2, 1, 3).reshape(b, 1, cfg.n_heads * cfg.head_dim)
        out = linear(o, p["wo"], use_kernels=cfg.use_kernels)
        return out, new_cache
    if lengths.ndim == 0:
        # common serving case (uniform batch): O(1) in-place slice update
        k_new = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, 0, write_idx, 0))
        v_new = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, 0, write_idx, 0))
    else:
        # ragged batch: per-row scatter via vmap'd slice update
        def upd(c, new, l):
            return jax.lax.dynamic_update_slice(c, new, (0, l, 0))
        k_new = jax.vmap(upd)(cache["k"], k.astype(cache["k"].dtype), write_idx)
        v_new = jax.vmap(upd)(cache["v"], v.astype(cache["v"].dtype), write_idx)
    o = ops.decode_attention(q, k_new, v_new, attn_len, window=attn_window,
                             impl=_impl(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, cfg.n_heads * cfg.head_dim)
    out = linear(o, p["wo"], use_kernels=cfg.use_kernels)
    return out, {"k": k_new, "v": v_new}


def _attn_decode_paged_sharded(cfg, p: Params, q, k, v, cache: Params,
                               lengths, page_table, write_mask, mesh):
    """Paged one-token decode across a device mesh: the pool is partitioned
    into block homes (``parallel/decode_attn.paged_homes``), each shard
    writes/attends only blocks it is home to, and the flash-decoding LSE
    merge combines the partials.  The host allocator guarantees page-table
    entries resolve to (shard, local block) consistently with this split."""
    from repro.parallel import decode_attn
    b = q.shape[0]
    if page_table is None:
        page_table = default_page_table(b, cache["k"].shape[0])
    scales = ((cache["k_scale"], cache["v_scale"])
              if cfg.kv_quant == "int8" else None)
    o, new_cache = decode_attn.decode_attention_sharded_paged(
        q, k, v, cache["k"], cache["v"], lengths, page_table, write_mask,
        mesh, scales=scales)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, cfg.n_heads * cfg.head_dim)
    return linear(o, p["wo"], use_kernels=cfg.use_kernels), new_cache


def _attn_decode_paged(cfg, p: Params, q, k, v, cache: Params, lengths,
                       page_table, write_mask):
    """Paged one-token decode: scatter the new K/V through the page table,
    then attend via the paged kernels.  Rolling SWA works transparently —
    the modular slot index is just another logical position the table maps."""
    b = q.shape[0]
    bs = cache["k"].shape[2]
    if page_table is None:
        page_table = default_page_table(b, cache["k"].shape[0])
    n_pos = page_table.shape[1] * bs     # addressable logical positions
    lengths = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32).reshape(-1), (b,))
    rolling = cfg.window is not None and n_pos <= cfg.window
    if rolling:
        write_idx = (lengths - 1) % n_pos
        attn_len = jnp.minimum(lengths, n_pos)
        attn_window = None
    else:
        write_idx = jnp.clip(lengths - 1, 0, n_pos - 1)
        attn_len = lengths
        attn_window = cfg.window
    if cfg.kv_quant == "int8":
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        new_cache = {
            "k": _paged_token_write(cache["k"], kq[:, :, 0], page_table,
                                    write_idx, write_mask),
            "v": _paged_token_write(cache["v"], vq[:, :, 0], page_table,
                                    write_idx, write_mask),
            "k_scale": _paged_token_write(cache["k_scale"], ks[:, :, 0],
                                          page_table, write_idx, write_mask),
            "v_scale": _paged_token_write(cache["v_scale"], vs[:, :, 0],
                                          page_table, write_idx, write_mask),
        }
        o = ops.decode_attention(q, new_cache["k"], new_cache["v"], attn_len,
                                 window=attn_window,
                                 k_scale=new_cache["k_scale"],
                                 v_scale=new_cache["v_scale"],
                                 page_table=page_table,
                                 impl=_impl(cfg))
    else:
        new_cache = {
            "k": _paged_token_write(cache["k"], k[:, :, 0], page_table,
                                    write_idx, write_mask),
            "v": _paged_token_write(cache["v"], v[:, :, 0], page_table,
                                    write_idx, write_mask),
        }
        o = ops.decode_attention(q, new_cache["k"], new_cache["v"], attn_len,
                                 window=attn_window, page_table=page_table,
                                 impl=_impl(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, cfg.n_heads * cfg.head_dim)
    return linear(o, p["wo"], use_kernels=cfg.use_kernels), new_cache


# -- cross attention (Whisper decoder) --------------------------------------

def cross_attn_init(key, cfg) -> Params:
    return attn_init(key, cfg)


def cross_attn_apply(cfg, p: Params, x: jax.Array, enc_kv: tuple) -> jax.Array:
    """x (b, s, d) attends to precomputed encoder K/V (b, hkv, s_enc, hd)."""
    b, s, _ = x.shape
    hd, hq = cfg.head_dim, cfg.n_heads
    q = linear(x, p["wq"], p.get("bq"), use_kernels=cfg.use_kernels)
    q = q.reshape(b, s, hq, hd).transpose(0, 2, 1, 3)
    k, v = enc_kv
    o = ops.attention(q, k, v, causal=False,
                      impl=_impl(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
    return linear(o, p["wo"], use_kernels=cfg.use_kernels)


def cross_kv(cfg, p: Params, enc_out: jax.Array) -> tuple:
    """Precompute cross-attention K/V from encoder output (done once)."""
    b, s, _ = enc_out.shape
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    k = linear(enc_out, p["wk"], p.get("bk"), use_kernels=cfg.use_kernels)
    v = linear(enc_out, p["wv"], p.get("bv"), use_kernels=cfg.use_kernels)
    return (k.reshape(b, s, hkv, hd).transpose(0, 2, 1, 3),
            v.reshape(b, s, hkv, hd).transpose(0, 2, 1, 3))
