"""Decoder-only transformer assembly (dense / MoE / VLM families).

Layers are stacked along a leading axis and executed with ``lax.scan``
(compact HLO — essential for the 512-device dry-run of 56-layer models) with
optional per-block remat.  The same block parameters serve three entry
points: ``forward`` (training), ``prefill`` (populate KV cache) and
``decode_step`` (one token against the cache).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention, layers, moe
from repro.models.config import ModelConfig
from repro.models.layers import Params


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig) -> Params:
    ka, kf = jax.random.split(key)
    p: Params = {
        "ln_attn": layers.norm_init(cfg),
        "attn": attention.attn_init(ka, cfg),
        "ln_mlp": layers.norm_init(cfg),
    }
    if cfg.is_moe:
        p["moe"] = moe.moe_init(kf, cfg)
    else:
        p["mlp"] = layers.mlp_init(kf, cfg)
    return p


def stack_blocks(key, cfg: ModelConfig, n: int, init_fn) -> Params:
    keys = jax.random.split(key, n)
    blocks = [init_fn(k, cfg) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def init_params(cfg: ModelConfig, key) -> Params:
    ke, kb, kh = jax.random.split(key, 3)
    p: Params = {
        "embed": layers.embed_init(ke, cfg.vocab_size, cfg.d_model, cfg.dtype),
        "blocks": stack_blocks(kb, cfg, cfg.n_layers, block_init),
        "ln_f": layers.norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = layers.dense_init(kh, cfg.d_model, cfg.vocab_size, cfg.dtype)
    return p


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------

def block_apply(cfg: ModelConfig, p: Params, x: jax.Array, positions) -> tuple:
    h = attention.attn_apply(cfg, p["attn"],
                             layers.apply_norm(cfg, p["ln_attn"], x), positions)
    x = x + h
    inner = layers.apply_norm(cfg, p["ln_mlp"], x)
    if cfg.is_moe:
        f, aux = moe.moe_apply(cfg, p["moe"], inner)
    else:
        f, aux = layers.mlp_apply(cfg, p["mlp"], inner), jnp.float32(0)
    return x + f, aux


def _scan_blocks(cfg: ModelConfig, blocks: Params, x: jax.Array, positions):
    def body(carry, bp):
        y, aux = block_apply(cfg, bp, carry, positions)
        return y, aux

    if cfg.remat == "block":
        body = jax.checkpoint(body, prevent_cse=False)
    if cfg.scan_layers:
        x, auxs = jax.lax.scan(body, x, blocks)
        return x, auxs.sum()
    aux_total = jnp.float32(0)
    for i in range(cfg.n_layers):
        bp = jax.tree.map(lambda a: a[i], blocks)
        x, aux = body(x, bp)
        aux_total += aux
    return x, aux_total


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def unembed(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        w = params["embed"].T
        logits = layers.linear(x, w)
    else:
        logits = layers.linear(x, params["lm_head"], use_kernels=cfg.use_kernels)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array,
            positions=None, vision_embeds: jax.Array | None = None):
    """tokens (B, S) -> logits (B, S, V); returns (logits, aux_loss)."""
    x = embed_tokens(cfg, params, tokens)
    if vision_embeds is not None:
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    if positions is None:
        positions = layers.positions_for(cfg, b, s)
    x, aux = _scan_blocks(cfg, params["blocks"], x, positions)
    x = layers.apply_norm(cfg, params["ln_f"], x)
    return unembed(cfg, params, x), aux


# -- serving -----------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    one = attention.init_kv_cache(cfg, batch, max_len)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_layers,) + a.shape),
        one)


def cache_slot_axes(cfg: ModelConfig) -> Params:
    """Request-slot axis per cache leaf: (n_layers, B, hkv, L, hd) -> axis 1
    (paged layout: shared-pool leaves, marked -1 — no slot axis)."""
    return attention.kv_cache_slot_axes(cfg, axis=1)


PREFILL_CHUNK = 4096


def prefill(cfg: ModelConfig, params: Params, tokens: jax.Array, max_len: int):
    """Returns (last-token logits (B, V), cache).

    Long prompts run CHUNKED (Sarathi-style): the prompt is processed in
    PREFILL_CHUNK slices, each attending to the KV cache written so far —
    activation peak becomes O(chunk) instead of O(prompt) (32k prompts cost
    20-600 GB/device otherwise; EXPERIMENTS.md §Perf it.9).  Chunk offsets
    are static (python loop), so the chunked-attention causal pruning still
    skips future KV blocks."""
    b, s = tokens.shape
    if s > PREFILL_CHUNK:
        return _prefill_chunked(cfg, params, tokens, max_len)
    x = embed_tokens(cfg, params, tokens)
    positions = layers.positions_for(cfg, b, s)
    cache = init_cache(cfg, b, max_len)

    def body(carry, inp):
        bp, layer_cache = inp
        h, new_cache = attention.attn_prefill(
            cfg, bp["attn"], layers.apply_norm(cfg, bp["ln_attn"], carry),
            positions, layer_cache)
        x2 = carry + h
        inner = layers.apply_norm(cfg, bp["ln_mlp"], x2)
        if cfg.is_moe:
            f, _ = moe.moe_apply(cfg, bp["moe"], inner)
        else:
            f = layers.mlp_apply(cfg, bp["mlp"], inner)
        return x2 + f, new_cache

    if cfg.remat == "block":
        body = jax.checkpoint(body, prevent_cse=False)
    x, cache = jax.lax.scan(body, x, (params["blocks"], cache))
    x = layers.apply_norm(cfg, params["ln_f"], x[:, -1:])
    return unembed(cfg, params, x)[:, 0], cache


def _prefill_chunked(cfg: ModelConfig, params: Params, tokens: jax.Array,
                     max_len: int):
    from repro.kernels import ops

    b, s = tokens.shape
    cq = PREFILL_CHUNK
    assert s % cq == 0, (s, cq)
    swa = cfg.window is not None and cfg.window <= cq
    cache = init_cache(cfg, b, max_len)
    cache_len = cache["k"].shape[3]
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    # SWA: carry the previous chunk's K/V per layer (covers the window)
    prev_kv = None
    if swa:
        prev_kv = {
            "k": jnp.zeros((cfg.n_layers, b, hkv, cq, hd), cfg.dtype),
            "v": jnp.zeros((cfg.n_layers, b, hkv, cq, hd), cfg.dtype),
        }
    logits = None
    for o in range(0, s, cq):
        x = embed_tokens(cfg, params, tokens[:, o:o + cq])
        positions = layers.positions_for(cfg, b, cq, offset=o)

        def body(carry, inp, o=o):
            if swa:
                bp, layer_cache, pkv = inp
            else:
                bp, layer_cache = inp
                pkv = None
            xin = layers.apply_norm(cfg, bp["ln_attn"], carry)
            q, k, v = attention._project_qkv(cfg, bp["attn"], xin, positions)
            w_off = o % cache_len
            new_cache = {
                "k": jax.lax.dynamic_update_slice(
                    layer_cache["k"], k.astype(layer_cache["k"].dtype),
                    (0, 0, w_off, 0)),
                "v": jax.lax.dynamic_update_slice(
                    layer_cache["v"], v.astype(layer_cache["v"].dtype),
                    (0, 0, w_off, 0)),
            }
            impl = "pallas" if cfg.use_kernels else "xla"
            if swa:
                # context = previous chunk ++ current chunk, window-masked;
                # chunk 0 has no valid previous chunk (zeros buffer) — skip it
                if o == 0:
                    h = ops.attention(q, k, v, causal=True,
                                      window=cfg.window, impl=impl)
                else:
                    k_ctx = jnp.concatenate(
                        [pkv["k"], k.astype(pkv["k"].dtype)], axis=2)
                    v_ctx = jnp.concatenate(
                        [pkv["v"], v.astype(pkv["v"].dtype)], axis=2)
                    h = ops.attention(q, k_ctx, v_ctx, causal=True,
                                      window=cfg.window, impl=impl)
                new_pkv = {"k": k.astype(pkv["k"].dtype),
                           "v": v.astype(pkv["v"].dtype)}
            else:
                # static slice of everything written so far; q sits at the
                # end of it, so causal pruning applies by construction
                hi = min(o + cq, cache_len)
                k_ctx = jax.lax.slice_in_dim(new_cache["k"], 0, hi, axis=2)
                v_ctx = jax.lax.slice_in_dim(new_cache["v"], 0, hi, axis=2)
                h = ops.attention(q, k_ctx, v_ctx, causal=True,
                                  window=cfg.window, impl=impl)
                new_pkv = None
            h = h.transpose(0, 2, 1, 3).reshape(b, cq, -1)
            h = layers.linear(h, bp["attn"]["wo"], use_kernels=cfg.use_kernels)
            x2 = carry + h
            inner = layers.apply_norm(cfg, bp["ln_mlp"], x2)
            if cfg.is_moe:
                f, _ = moe.moe_apply(cfg, bp["moe"], inner)
            else:
                f = layers.mlp_apply(cfg, bp["mlp"], inner)
            out_cache = (new_cache, new_pkv) if swa else new_cache
            return x2 + f, out_cache

        if cfg.remat == "block":
            body = jax.checkpoint(body, prevent_cse=False)
        if swa:
            x, (cache, prev_kv) = jax.lax.scan(
                body, x, (params["blocks"], cache, prev_kv))
        else:
            x, cache = jax.lax.scan(body, x, (params["blocks"], cache))
        if o + cq >= s:
            x = layers.apply_norm(cfg, params["ln_f"], x[:, -1:])
            logits = unembed(cfg, params, x)[:, 0]
    return logits, cache


# The serving steps name their parts with ``jax.named_scope`` (embed,
# attention with its KV write, ffn, lm_head): metadata only, so a profiler
# trace can put each device op down to its part of the step.

def _ffn(cfg: ModelConfig, bp: Params, x: jax.Array) -> jax.Array:
    """A block's FFN on the residual stream ``x``, its norm included."""
    with jax.named_scope("ffn"):
        inner = layers.apply_norm(cfg, bp["ln_mlp"], x)
        if cfg.is_moe:
            return moe.moe_apply(cfg, bp["moe"], inner)[0]
        return layers.mlp_apply(cfg, bp["mlp"], inner)


def mixed_step(cfg: ModelConfig, params: Params, cache: Params,
               tokens: jax.Array, lengths, q_lens, *, page_table=None,
               all_logits: bool = False):
    """Mixed prefill/decode step (one dispatch for the whole tick).

    tokens (B, C); ``lengths`` (B,) = valid cache tokens BEFORE this step;
    ``q_lens`` (B,) = live new tokens per row — 1 for a decoding row, up to
    C for a row mid-prefill (its chunk is ``tokens[b, :q_lens[b]]``, the
    rest padding).  Token j of row b sits at true position ``lengths[b]+j``
    (no left-pad bucket positions).  Returns (logits (B, V) of each row's
    LAST live token, new cache).  ``page_table`` (B, pages) routes paged
    K/V placement (None = the linear default table).

    ``all_logits=True`` unembeds EVERY chunk position instead of just the
    last live one, returning (B, C, V) — the draft-verify surface: a
    speculating row's K+1 positions are scored in this one dispatch, so
    acceptance needs zero extra device round-trips.  Position j's row is
    the model's next-token distribution AFTER consuming ``tokens[b, j]``
    (positions past ``q_lens[b]-1`` are padding garbage; callers mask).
    """
    b, c = tokens.shape
    with jax.named_scope("embed"):
        x = embed_tokens(cfg, params, tokens)
    lengths = jnp.asarray(lengths, jnp.int32)
    q_lens = jnp.asarray(q_lens, jnp.int32)
    pos = lengths[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    if cfg.rope_type == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, b, c))

    def body(carry, inp):
        bp, layer_cache = inp
        with jax.named_scope("attention"):
            h, new_cache = attention.attn_mixed(
                cfg, bp["attn"], layers.apply_norm(cfg, bp["ln_attn"], carry),
                pos, layer_cache, lengths, q_lens, page_table=page_table)
        x2 = carry + h
        return x2 + _ffn(cfg, bp, x2), new_cache

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    with jax.named_scope("lm_head"):
        if all_logits:
            # verify surface: every chunk position reaches the LM head
            x = layers.apply_norm(cfg, params["ln_f"], x)
            return unembed(cfg, params, x), new_cache
        # only each row's last live position reaches the LM head (C-fold
        # cheaper than unembedding the full chunk; mid-prefill rows need
        # just this one)
        idx = jnp.clip(q_lens - 1, 0, c - 1)
        x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        x_last = layers.apply_norm(cfg, params["ln_f"], x_last)
        return unembed(cfg, params, x_last)[:, 0], new_cache


def decode_step(cfg: ModelConfig, params: Params, cache: Params,
                tokens: jax.Array, lengths, *, page_table=None,
                write_mask=None):
    """One decode step.  tokens (B, 1); lengths scalar or (B,) — context
    length including this token.  Returns (logits (B, V), new cache).
    Paged layout: ``page_table`` routes the K/V scatter; ``write_mask``
    (B,) bool sends masked rows' writes to the null block."""
    b = tokens.shape[0]
    with jax.named_scope("embed"):
        x = embed_tokens(cfg, params, tokens)
    lengths = jnp.asarray(lengths)
    pos = (lengths - 1).reshape(-1, 1) * jnp.ones((b, 1), jnp.int32)
    if cfg.rope_type == "mrope":
        pos = jnp.broadcast_to(pos[None], (3, b, 1))

    def body(carry, inp):
        bp, layer_cache = inp
        with jax.named_scope("attention"):
            h, new_cache = attention.attn_decode(
                cfg, bp["attn"], layers.apply_norm(cfg, bp["ln_attn"], carry),
                pos, layer_cache, lengths, page_table=page_table,
                write_mask=write_mask)
        x2 = carry + h
        return x2 + _ffn(cfg, bp, x2), new_cache

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    with jax.named_scope("lm_head"):
        x = layers.apply_norm(cfg, params["ln_f"], x)
        return unembed(cfg, params, x)[:, 0], new_cache
