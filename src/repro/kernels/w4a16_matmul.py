"""Pallas TPU kernel: dense W4A16 block-quant matmul (EdgeLLM MODE-1).

The paper's FP16*INT4 PE array, restated for the MXU:

* weights live in HBM as packed int4 nibbles (2/byte) + one 16-bit scale per
  128-channel group — the paper's scale/wt package;
* each grid step streams one 128-deep weight block into VMEM, unpacks it with
  one mask + one shift + one sublane concat (the sublane-pair packing from
  ``core.quant``), and runs a fully dense (bt×128)·(128×bo) MXU matmul;
* int4 values are *exactly* representable in bf16, so the matmul is
  integer-exact; the per-group FP16 scale multiplies the **partial sum**
  (paper Fig. 4 Stage-3 "Scale value" multiply) — numerically identical to
  the FPGA's keep-full-mantissa-then-rescale datapath, and strictly more
  accurate than dequantize-to-bf16-then-dot;
* the accumulator stays resident in a VMEM scratch across the contraction
  grid axis — the G-VSA "partial sums never leave the array" property.

Roofline intent (paper Fig. 3): at decode (bt small) the kernel moves
``in·out/2`` weight bytes + ``in·out/64`` scale bytes per call and does
``2·bt·in·out`` FLOPs — arithmetic intensity ≈ bt·4 FLOP/byte, memory-bound
until bt ≈ 100, exactly the regime the paper sizes its PE bandwidth for.

VMEM budget per step: x (bt·128·2) + packed (64·bo) + scales
(2·bo·in/128) + acc (bt·bo·4) bytes; defaults (bt=256, bo=512, in=4096)
≈ 1.1 MB « 16 MB v5e VMEM,
leaving room for Mosaic's double buffering of the streamed weight blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pallas_compat import (
    default_interpret, pick_row, token_block, unpack_int4_rows)

from repro.core.quant import GROUP_SIZE, QuantizedTensor

__all__ = ["w4a16_matmul_pallas"]

_HALF = GROUP_SIZE // 2  # 64 packed rows per 128-row group


def _kernel(x_ref, packed_ref, scale_ref, o_ref, acc_ref):
    g = pl.program_id(2)

    @pl.when(g == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = unpack_int4_rows(packed_ref[...])                  # (128, bo) bf16, integer-exact
    part = jax.lax.dot_general(
        x_ref[...], w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                      # (bt, bo) f32
    # the scale block holds every group of this out tile (Mosaic tiles a
    # block's last two dims); this step's group is one row of it
    acc_ref[...] += part * pick_row(scale_ref[...], g)     # (1, bo)

    @pl.when(g == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_tokens", "block_out", "interpret"))
def w4a16_matmul_pallas(
    x: jax.Array,
    qt: QuantizedTensor,
    *,
    block_tokens: int = 256,
    block_out: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """``x @ dequant(qt)`` via the Pallas MODE-1 kernel.

    ``x``: (..., tokens, in_features) bf16/f16/f32.  Returns x.dtype.
    ``interpret=None`` derives from the backend (Mosaic on TPU, interpreter
    elsewhere), so direct callers never run the interpreter on TPU.
    """
    if interpret is None:
        interpret = default_interpret()
    in_f, out_f = qt.shape
    if qt.group_size != GROUP_SIZE:
        raise ValueError("kernel assumes 128-channel groups")
    *lead, tokens, xin = x.shape
    if xin != in_f:
        raise ValueError(f"contraction mismatch {xin} vs {in_f}")
    x2 = x.reshape(-1, in_f)
    n_tok = x2.shape[0]

    bt = token_block(n_tok, block_tokens)  # exact fit at decode, no 8-row pad
    # pad tokens to a multiple of bt
    pad = (-n_tok) % bt
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    # a ragged last out tile (e.g. a 151936-wide lm_head) is a partial
    # block: its out-of-range columns are computed on padding and dropped
    bo = min(block_out, out_f)
    n_groups = in_f // GROUP_SIZE

    grid = (x2.shape[0] // bt, pl.cdiv(out_f, bo), n_groups)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, GROUP_SIZE), lambda t, o, g: (t, g)),
            pl.BlockSpec((_HALF, bo), lambda t, o, g: (g, o)),
            pl.BlockSpec((n_groups, bo), lambda t, o, g: (0, o)),
        ],
        out_specs=pl.BlockSpec((bt, bo), lambda t, o, g: (t, o)),
        out_shape=jax.ShapeDtypeStruct((x2.shape[0], out_f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, bo), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        name="w4a16_matmul",
        interpret=interpret,
    )(x2, qt.packed, qt.scales)
    if pad:
        out = out[:n_tok]
    return out.reshape(*lead, tokens, out_f)
