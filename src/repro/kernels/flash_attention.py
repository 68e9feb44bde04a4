"""Pallas TPU kernel: fused attention, bf16 operands (EdgeLLM MODE-0).

The paper's FP16*FP16 unit handles every matmul whose second operand is
*dynamically generated* (Q·Kᵀ and P·V against the KV cache) — those can never
be pre-quantized.  On TPU that is the flash-attention kernel: K/V stream
through VMEM block by block while the softmax statistics (m, l) and the
output accumulator stay resident, the same stationary-accumulator discipline
as the G-VSA array.

Supports causal masking, sliding windows (Mixtral SWA), GQA/MQA head
grouping, decode alignment (q block occupies the last ``sq`` positions of the
``skv`` context), and non-causal cross-attention (Whisper).

Grid: ``(batch*q_heads, sq/bq, skv/bk)`` with the KV axis innermost
("arbitrary"); fully-masked KV blocks are skipped with ``pl.when`` — the
TPU version of the paper's "MHA latency grows quadratically" mitigation,
halving work under causal masks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_compat import default_interpret

__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30
_STATS = 128  # lane-replicated softmax statistics width


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale, causal, window, q_offset, bq, bk):
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = q_offset + iq * bq
    k_start = ik * bk
    # block-level skip: under a causal mask, blocks strictly above the
    # diagonal contribute nothing; under a window, blocks too far in the
    # past contribute nothing either.
    live = True
    if causal:
        live = k_start <= q_start + bq - 1
    if window is not None:
        live = jnp.logical_and(live, k_start + bk - 1 >= q_start - window + 1)

    @pl.when(live)
    def _step():
        q = q_ref[0]                                       # (bq, d)
        k = k_ref[0]                                       # (bk, d)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (bq, bk)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]                              # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)          # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
        p = jnp.exp(s - m_new)                             # (bq, bk)
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)

        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (bq, d)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _done():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_kv", "interpret"))
def flash_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    block_q: int = 256,
    block_kv: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused attention.  q (b, hq, sq, d); k/v (b, hkv, skv, d); GQA via
    hq % hkv == 0.  Causal alignment: q block sits at the end of the context.
    ``interpret=None`` derives from the backend (Mosaic on TPU).
    """
    if interpret is None:
        interpret = default_interpret()
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"hq={hq} not a multiple of hkv={hkv}")
    rep = hq // hkv
    scale_v = scale if scale is not None else float(1.0 / (d ** 0.5))

    bq = min(block_q, sq)
    bk = min(block_kv, skv)
    if sq % bq or skv % bk:
        raise ValueError(f"sq={sq} % bq={bq} or skv={skv} % bk={bk} != 0")
    q_offset = skv - sq

    q3 = q.reshape(b * hq, sq, d)
    k3 = k.reshape(b * hkv, skv, d)
    v3 = v.reshape(b * hkv, skv, d)

    def kv_index(bh, iq, ik):
        return (bh // hq) * hkv + (bh % hq) // rep

    kernel = functools.partial(
        _kernel, scale=scale_v, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, sq // bq, skv // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (kv_index(bh, iq, ik), ik, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, iq, ik: (kv_index(bh, iq, ik), ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, _STATS), jnp.float32),
            pltpu.VMEM((bq, _STATS), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="flash_attention",
        interpret=interpret,
    )(q3, k3, v3)
    return out.reshape(b, hq, sq, d)
