"""shard_map flash-decoding: one-token attention over a sequence-sharded KV
cache (EXPERIMENTS.md §Perf qwen3-decode iterations).

Under plain pjit, the decode step's cache update + attention trigger
"involuntary full rematerialization" resharding copies between the
seq-sharded cache and the head-sharded attention compute — measured ~200×
the int4-floor memory traffic on qwen3-8b decode_32k.  This module makes
the intended dataflow explicit:

* the cache NEVER moves: each model shard holds a contiguous sequence slice
  (slot layout) or a contiguous run of pool rows — its block HOMES (paged
  layout);
* the new token's K/V is written by whichever shard owns its slot / home
  block (a masked local scatter — rows homed elsewhere keep their values);
* each shard computes partial attention over its slice with a local max /
  sum, then the shards merge with the flash-decoding log-sum-exp rule
  (one pmax + two psums of (b, h, d)-sized partials — KBs, not GBs);
* q is replicated across the sequence axes (it is one token).

``lengths`` may be a scalar or per-row ``(B,)`` — the serving engine always
passes the vector, so both the write scatter and the live-length clamp are
per-row.  Numerically identical to ``ref.decode_attention_ref`` (tested);
batched token streams match the single-device walk bitwise at the argmax.

Paged layout (``decode_attention_sharded_paged``): the shared pool's rows
are partitioned into ``n_shards`` contiguous "block homes"; the engine's
allocator leases each row's blocks round-robin across homes, page-table
entries stay GLOBAL block ids, and each shard's walker translates them to
home-local rows (non-home blocks masked to exact zeros — see
``decode_blocked_partials``).  Every logical block is counted by exactly
one shard, so the same pmax/psum merge combines the partials.  Resident
batch then scales with total mesh memory instead of one device's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def seq_axes_for(mesh: Mesh, batch: int) -> tuple[str, ...]:
    """Mirror kv_cache_specs: seq shards over 'model', plus the data axes
    when the batch can't occupy them (batch == 1 / indivisible)."""
    da = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dsize = int(np.prod([mesh.shape[a] for a in da])) if da else 1
    if batch > 1 and batch % dsize == 0:
        return ("model",)
    return da + ("model",)


def _shard_index(mesh: Mesh, sa: tuple[str, ...]) -> jax.Array:
    """Linear index of this program among the ``sa`` shards (row-major)."""
    return sum(jax.lax.axis_index(a) * int(np.prod(
        [mesh.shape[x] for x in sa[i + 1:]]))
        for i, a in enumerate(sa))


def decode_attention_sharded(
    q: jax.Array,            # (b, hq, 1, hd)
    k_new: jax.Array,        # (b, hkv, 1, hd)
    v_new: jax.Array,
    k_cache: jax.Array,      # (b, hkv, S, hd) — seq sharded
    v_cache: jax.Array,
    lengths: jax.Array,      # scalar or (b,): context length incl. new token
    mesh: Mesh,
    *,
    rolling: bool,
    scale: float | None = None,
    scales: tuple | None = None,   # (k_scale, v_scale) for int8-quantized KV
):
    """Returns (out (b, hq, 1, hd), new_cache dict)."""
    b, hq, _, hd = q.shape
    hkv, S = k_cache.shape[1], k_cache.shape[2]
    rep = hq // hkv
    scale_v = scale if scale is not None else float(1.0 / (hd ** 0.5))
    sa = seq_axes_for(mesh, b)
    da = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    batch_ax = da if (b > 1 and sa == ("model",)) else None
    quant = scales is not None
    lengths = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32).reshape(-1), (b,))

    def local(q_l, kn, vn, ck, cv, ksc, vsc, length):
        s_loc = ck.shape[2]
        bl = q_l.shape[0]                                    # local batch
        off = _shard_index(mesh, sa) * s_loc
        write_idx = ((length - 1) % S) if rolling else (length - 1)  # (bl,)
        local_idx = write_idx - off
        in_range = (local_idx >= 0) & (local_idx < s_loc)
        rows = jnp.arange(bl)
        safe = jnp.clip(local_idx, 0, s_loc - 1)

        def upd(c, new):
            # per-row scatter: a row whose write slot lives on another
            # shard keeps its current value (each slot written exactly once
            # across the mesh)
            cur = c[rows, :, safe]
            vals = jnp.where(in_range[:, None, None],
                             new[:, :, 0].astype(c.dtype), cur)
            return c.at[rows, :, safe].set(vals)

        if quant:
            from repro.models.attention import quantize_kv
            knq, kns = quantize_kv(kn)
            vnq, vns = quantize_kv(vn)
            ck2, cv2 = upd(ck, knq), upd(cv, vnq)
            ksc2, vsc2 = upd(ksc, kns), upd(vsc, vns)
        else:
            ck2, cv2 = upd(ck, kn), upd(cv, vn)
            ksc2 = vsc2 = None

        # partial attention over the local slice, via the SAME blocked
        # walker as the single-host path (kernels/xla_attention).  Its
        # traffic rules (measured on qwen3 decode, §Perf): (1) the cache
        # stays in its storage dtype — an explicit .astype(f32) materializes
        # a full f32 cache copy per layer; (2) GQA via grouped einsum, NOT
        # jnp.repeat — repeating K/V to 32 heads materializes rep x the
        # cache bytes; (3) per-shard block skipping — each shard clamps the
        # walk to ITS live positions (`length - off`), so a shard whose
        # slice sits past the valid context streams zero KV blocks instead
        # of its whole slice (the length-clamp trick from decode_flash.py,
        # restated for shard_map).  int8 KV: scale-after-dot (the paper's
        # Stage-3 trick applied to the dynamic operand):
        # logits_s = (q·k_q_s)·kscale_s.
        from repro.kernels.xla_attention import decode_blocked_partials
        q5 = q_l.reshape(bl, hkv, rep, 1, hd)
        valid_len = jnp.minimum(length, S) if rolling else length
        local_live = jnp.clip(valid_len - off, 0, s_loc)     # (bl,)
        m_loc, l_loc, acc = decode_blocked_partials(
            q5, ck2, cv2, local_live,
            scale=scale_v,
            k_scale=ksc2[..., 0] if quant else None,
            v_scale=vsc2[..., 0] if quant else None)

        # flash-decoding merge across sequence shards
        m_g = jax.lax.pmax(m_loc, sa)
        corr = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * corr, sa)
        acc_g = jax.lax.psum(acc * corr[..., None], sa)
        out = (acc_g / jnp.maximum(l_g, 1e-30)[..., None])
        out = out.reshape(bl, hq, 1, hd).astype(q_l.dtype)
        if quant:
            return out, ck2, cv2, ksc2, vsc2
        return out, ck2, cv2

    cache_spec = P(batch_ax, None, sa if len(sa) > 1 else sa[0], None)
    rep_spec = P(batch_ax, None, None, None)
    len_spec = P(batch_ax)          # per-row lengths ride with the batch
    # check_vma=False: the blocked partials walk is a lax.while_loop (trip
    # count = this shard's live blocks), which shard_map's replication
    # checker cannot type yet; the explicit pmax/psum merge below is what
    # establishes replication of the output
    if quant:
        ksc, vsc = scales
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(rep_spec, rep_spec, rep_spec, cache_spec, cache_spec,
                      cache_spec, cache_spec, len_spec),
            out_specs=(rep_spec, cache_spec, cache_spec, cache_spec,
                       cache_spec),
            check_vma=False,
        )
        out, k2, v2, ks2, vs2 = fn(q, k_new, v_new, k_cache, v_cache,
                                   ksc, vsc, lengths)
        return out, {"k": k2, "v": v2, "k_scale": ks2, "v_scale": vs2}

    def local_noq(q_l, kn, vn, ck, cv, length):
        return local(q_l, kn, vn, ck, cv, None, None, length)

    fn = jax.shard_map(
        local_noq, mesh=mesh,
        in_specs=(rep_spec, rep_spec, rep_spec, cache_spec, cache_spec,
                  len_spec),
        out_specs=(rep_spec, cache_spec, cache_spec),
        check_vma=False,
    )
    out, k2, v2 = fn(q, k_new, v_new, k_cache, v_cache, lengths)
    return out, {"k": k2, "v": v2}


def decode_attention_sharded_paged(
    q: jax.Array,            # (b, hq, 1, hd)
    k_new: jax.Array,        # (b, hkv, 1, hd)
    v_new: jax.Array,
    k_pool: jax.Array,       # (N, hkv, bs, hd) — pool rows home-sharded
    v_pool: jax.Array,
    lengths: jax.Array,      # (b,) context length incl. new token
    page_table: jax.Array,   # (b, n_pages) GLOBAL physical block ids
    write_mask: jax.Array | None,   # (b,) bool; False rows never land
    mesh: Mesh,
    *,
    scale: float | None = None,
    scales: tuple | None = None,
):
    """Sequence-sharded PAGED decode: one engine across a device mesh.

    The pool's ``N`` rows (null block included, last) are partitioned into
    ``n_shards`` contiguous block homes of ``N // n_shards`` rows; shard
    ``s`` holds rows ``[s*R, (s+1)*R)``.  The engine's allocator leases a
    row's blocks round-robin across homes, so each shard's walker — the
    shared ``decode_blocked_partials`` with ``block_home`` — visits only
    the blocks it is home to (non-home blocks mask to exact zeros) and the
    flash-decoding pmax/psum merge combines the partials.  The new token's
    K/V is written by the shard homing its block (masked rows and
    other-home rows drop).  No rolling-SWA variant: the dispatch gates this
    path on ``cfg.window is None``.

    Returns (out (b, hq, 1, hd), new_cache dict).
    """
    b, hq, _, hd = q.shape
    hkv, bs = k_pool.shape[1], k_pool.shape[2]
    rep = hq // hkv
    scale_v = scale if scale is not None else float(1.0 / (hd ** 0.5))
    sa = seq_axes_for(mesh, b)
    n_shards = 1
    for a in sa:
        n_shards *= mesh.shape[a]
    quant = scales is not None
    n_pos = page_table.shape[1] * bs
    lengths = jnp.broadcast_to(
        jnp.asarray(lengths, jnp.int32).reshape(-1), (b,))
    mask = (jnp.ones((b,), bool) if write_mask is None
            else jnp.asarray(write_mask, bool))

    def local(q_l, kn, vn, kp, vp, ksp, vsp, length, table, wmask):
        r_loc = kp.shape[0]                   # home rows on this shard
        base = _shard_index(mesh, sa) * r_loc

        # -- write the new token: the row's physical block translates to a
        # home-local row; rows homed on other shards drop, masked rows route
        # to the GLOBAL null row (last pool row) so the null-homing shard
        # absorbs them exactly like the single-device write path — pools
        # stay bitwise identical across the two dispatches
        pos = jnp.clip(length - 1, 0, n_pos - 1)
        blk = jnp.take_along_axis(table, (pos // bs)[:, None], axis=1)[:, 0]
        blk = jnp.where(wmask, blk, r_loc * n_shards - 1)
        loc = blk - base
        ok = (loc >= 0) & (loc < r_loc)
        blk_eff = jnp.where(ok, loc, r_loc)   # r_loc is out of bounds

        def upd(pool_l, new):
            return pool_l.at[blk_eff, :, pos % bs].set(
                new.astype(pool_l.dtype), mode="drop")

        if quant:
            from repro.models.attention import quantize_kv
            knq, kns = quantize_kv(kn)
            vnq, vns = quantize_kv(vn)
            kp2, vp2 = upd(kp, knq[:, :, 0]), upd(vp, vnq[:, :, 0])
            ksp2, vsp2 = upd(ksp, kns[:, :, 0]), upd(vsp, vns[:, :, 0])
        else:
            kp2, vp2 = upd(kp, kn[:, :, 0]), upd(vp, vn[:, :, 0])
            ksp2 = vsp2 = None

        # -- partial attention over home blocks only, then the LSE merge
        from repro.kernels.xla_attention import decode_blocked_partials
        q5 = q_l.reshape(b, hkv, rep, 1, hd)
        m_loc, l_loc, acc = decode_blocked_partials(
            q5, kp2, vp2, jnp.clip(length, 0, n_pos),
            scale=scale_v,
            k_scale=ksp2[..., 0] if quant else None,
            v_scale=vsp2[..., 0] if quant else None,
            page_table=table, block_home=base)
        m_g = jax.lax.pmax(m_loc, sa)
        corr = jnp.exp(m_loc - m_g)
        l_g = jax.lax.psum(l_loc * corr, sa)
        acc_g = jax.lax.psum(acc * corr[..., None], sa)
        out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
        out = out.reshape(b, hq, 1, hd).astype(q_l.dtype)
        if quant:
            return out, kp2, vp2, ksp2, vsp2
        return out, kp2, vp2

    pool_spec = P(sa if len(sa) > 1 else sa[0], None, None, None)
    rep4 = P(None, None, None, None)
    # batch stays replicated: the pool has no batch axis, and replicated
    # writes by the full batch keep every data-replica identical
    if quant:
        ksc, vsc = scales
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(rep4, rep4, rep4, pool_spec, pool_spec, pool_spec,
                      pool_spec, P(None), P(None, None), P(None)),
            out_specs=(rep4, pool_spec, pool_spec, pool_spec, pool_spec),
            check_vma=False,
        )
        out, k2, v2, ks2, vs2 = fn(q, k_new, v_new, k_pool, v_pool,
                                   ksc, vsc, lengths, page_table, mask)
        return out, {"k": k2, "v": v2, "k_scale": ks2, "v_scale": vs2}

    def local_noq(q_l, kn, vn, kp, vp, length, table, wmask):
        return local(q_l, kn, vn, kp, vp, None, None, length, table, wmask)

    fn = jax.shard_map(
        local_noq, mesh=mesh,
        in_specs=(rep4, rep4, rep4, pool_spec, pool_spec, P(None),
                  P(None, None), P(None)),
        out_specs=(rep4, pool_spec, pool_spec),
        check_vma=False,
    )
    out, k2, v2 = fn(q, k_new, v_new, k_pool, v_pool, lengths,
                     page_table, mask)
    return out, {"k": k2, "v": v2}


def paged_homes(mesh: Mesh | None, batch: int, pool_rows: int, *,
                window: int | None = None) -> int:
    """Number of block homes the sharded paged path partitions the pool
    into (1 = unsharded).  The engine's allocator MUST agree with the
    dispatch gate, so both derive from this one function: homes > 1 exactly
    when ``usable(..., paged=True)`` will route decode through
    ``decode_attention_sharded_paged``.  ``pool_rows`` counts the null row.
    """
    if window is not None or mesh is None or "model" not in mesh.axis_names:
        return 1
    sa = seq_axes_for(mesh, batch)
    n = int(np.prod([mesh.shape[a] for a in sa]))
    if pool_rows % n == 0 and pool_rows >= n:
        return n
    return 1


def usable(mesh: Mesh | None, batch: int, hq: int, hkv: int, S: int,
           lengths, *, paged: bool = False) -> bool:
    """Whether the sequence-sharded decode path applies.

    ``S`` is the cache's sharded extent: sequence slots for the slot
    layout, pool ROWS (null block included) for ``paged=True``.  Either
    way the requirement is the same — the extent divides evenly across the
    sequence shards (contiguous slice per shard for slots, equal block
    homes for pages).  ``lengths`` may be a scalar or a per-row ``(B,)``
    vector — the serving engine always passes the vector.
    """
    if mesh is None or "model" not in mesh.axis_names:
        return False
    sa = seq_axes_for(mesh, batch)
    n = int(np.prod([mesh.shape[a] for a in sa]))
    return S % n == 0 and S >= n
