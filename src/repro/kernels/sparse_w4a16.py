"""Pallas TPU kernel: log-scale block-sparse W4A16 matmul (EdgeLLM §III-C).

The paper's sparse path: masks select which activation data enters the PE
array; power-of-two densities keep the PEs 100 % busy; HBM traffic shrinks
with density (Fig. 5).  TPU restatement:

* sparsity granularity = one 128-channel weight block shared across a
  128-wide output tile (DESIGN.md §2 — DBB "larger blocks" taken to MXU
  scale);
* the kept-block indices (the paper's address-in-block encoding) are scalars
  prefetched into SMEM via ``PrefetchScalarGridSpec``; the **activation
  BlockSpec's index_map reads them**, so the sparse gather happens in the
  DMA engine while the MXU runs the previous block — this is precisely the
  paper's "sparse DMA picks out the necessary activation data" mechanism;
* every surviving grid step is a dense (bt×128)·(128×128) MXU matmul →
  100 % utilization at any sparsity, the paper's core hardware claim;
* the grid simply has ``density × 8`` fewer contraction steps per group, so
  compute *and* weight traffic shrink together — on the FPGA this was the
  time-unrolled schedule, on TPU it is a shorter grid.

Numerics identical to the dense kernel: integer-exact bf16 MXU dot, f32
accumulation, per-block scale applied to the partial sum.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_compat import (
    default_interpret, pick_row, token_block, unpack_int4_rows)

from repro.core.quant import GROUP_SIZE
from repro.core.sparsity import SparseQuantizedTensor

__all__ = ["sparse_w4a16_matmul_pallas"]

_HALF = GROUP_SIZE // 2


def _kernel(idx_ref, x_ref, packed_ref, scale_ref, o_ref, acc_ref):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = unpack_int4_rows(packed_ref[0, 0])               # (128, 128) bf16
    part = jax.lax.dot_general(
        x_ref[...], w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # the scale block holds all S kept blocks of this out tile (Mosaic tiles
    # a block's last two dims); this step's block is one row of it
    acc_ref[...] += part * pick_row(scale_ref[0], s)

    @pl.when(s == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_tokens", "interpret"))
def sparse_w4a16_matmul_pallas(
    x: jax.Array,
    st: SparseQuantizedTensor,
    *,
    block_tokens: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """``x @ sparse_dequant(st)`` via the scalar-prefetch block-gather kernel.

    ``x``: (..., tokens, in_features).  Out tile fixed at 128 (= sparsity
    granularity); contraction grid has S = density * n_blocks steps.
    ``interpret=None`` derives from the backend (Mosaic on TPU).
    """
    if interpret is None:
        interpret = default_interpret()
    in_f, out_f = st.shape
    *lead, tokens, xin = x.shape
    if xin != in_f:
        raise ValueError(f"contraction mismatch {xin} vs {in_f}")
    x2 = x.reshape(-1, in_f)
    n_tok = x2.shape[0]
    bt = token_block(n_tok, block_tokens)  # exact fit at decode, no 8-row pad
    pad = (-n_tok) % bt
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))

    out_tiles, S = st.block_idx.shape
    grid = (x2.shape[0] // bt, out_tiles, S)

    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # activation block chosen by the prefetched kept-block index
                pl.BlockSpec(
                    (bt, GROUP_SIZE),
                    lambda t, o, s, idx_ref: (t, idx_ref[o, s])),
                pl.BlockSpec(
                    (1, 1, _HALF, GROUP_SIZE),
                    lambda t, o, s, idx_ref: (o, s, 0, 0)),
                pl.BlockSpec(
                    (1, S, GROUP_SIZE),
                    lambda t, o, s, idx_ref: (o, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (bt, GROUP_SIZE), lambda t, o, s, idx_ref: (t, o)),
            scratch_shapes=[pltpu.VMEM((bt, GROUP_SIZE), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((x2.shape[0], out_f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="sparse_w4a16_matmul",
        interpret=interpret,
    )(st.block_idx, x2, st.packed, st.scales)
    if pad:
        out = out[:n_tok]
    return out.reshape(*lead, tokens, out_f)
