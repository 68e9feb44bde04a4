"""Pallas TPU kernel: fused W4A16 FFN — ONE dispatch per MLP (EdgeLLM §III-B/C).

The paper's headline datapath is the FP16×INT4 FFN: the mixed-precision PE
array (Fig. 4) multiplies FP16 activations against streamed INT4 weights,
keeps full-mantissa partial sums in the array, and applies the per-group
"Scale value" multiply AFTER accumulation (Stage-3); log-scale structured
sparsity (§III-C) then shrinks the weight stream itself.  Our serving FFN
used to run as three independent ``pallas_call``s per MLP (gate, up, down)
that each re-streamed the activations and bounced two full ``(tokens, d_ff)``
intermediates plus the silu-multiply through HBM.  This kernel is the fusion:

* the ``(bt, d)`` activation block is **resident in VMEM** for the whole
  MLP — streamed from HBM once per token block, not once per projection;
* per f-tile (128 hidden channels — the MXU width AND the down projection's
  quant-group length), gate and up partial sums accumulate in two VMEM
  scratch accumulators across the contraction grid, with each 128-group's
  scale applied to its partial sum (Fig. 4 scale-after-accumulate);
* at the last group step the activation (silu/gelu) and elementwise product
  run **in-kernel** on the f32 accumulators, and the resulting ``(bt, 128)``
  hidden tile is immediately contracted against the down projection's
  matching 128-wide weight group — whose quant group axis IS this f-tile, so
  one scale covers the whole contraction — into a resident ``(bt, d)``
  output accumulator;
* the ``(tokens, d_ff)`` hidden state therefore **never touches HBM**: a
  whole MLP is one dispatch moving ``W + x + out`` bytes instead of
  ``W + 2x + 6·tokens·d_ff·2 + out`` (3 kernels + 2 XLA elementwise ops).

The sparse twin composes ``sparse_w4a16.py``'s kept-block gather with the
fusion: gate/up kept-block indices are scalar-prefetched into SMEM and drive
the activation gather (a VMEM slice of the resident block — the DMA-side
gather of the standalone kernel, moved on-chip by the fusion), and the down
projection's kept f-blocks (``tile_uniform`` sparsity, one kept set for all
output channels) drive the OUTER grid axis — hidden tiles the down
projection dropped are never computed and their gate/up weight blocks are
never streamed, so compute and weight bytes shrink together exactly like the
paper's time-unrolled sparse schedule.

Usage: call :func:`repro.kernels.ops.ffn_w4a16` (``impl="pallas"`` → these
kernels, ``impl="xla"`` → the blocked twin with the same numerics contract,
``impl="ref"`` → the unfused oracle).  ``models/layers.mlp_apply`` and the
MoE expert loops dispatch through it; direct callers exist only in tests and
benchmarks.

VMEM budget per step (dense-quant, defaults bt=128, d=4096): x block
``bt·d·2`` = 1 MB + out accumulator ``bt·d·4`` = 2 MB + out block 1 MB +
gate/up accumulators ``2·bt·128·4`` = 128 KB + weight blocks (gate/up
``64·128`` packed + down ``64·d``) ≈ 0.3 MB — ≈ 4.5 MB, well under 16 MB
v5e VMEM with room for Mosaic's double buffering.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import GROUP_SIZE, QuantizedTensor
from repro.core.sparsity import SparseQuantizedTensor
from repro.kernels.pallas_compat import (
    default_interpret, pick_row, token_block, unpack_int4_rows)

__all__ = [
    "DEFAULT_BLOCK_TOKENS",
    "ffn_fused_w4a16_pallas",
    "ffn_fused_dense_pallas",
    "ffn_fused_sparse_pallas",
    "ffn_w4a16_xla",
    "fused_variant",
]

_HALF = GROUP_SIZE // 2
DEFAULT_BLOCK_TOKENS = 128

GATED_ACTIVATIONS = ("swiglu", "geglu")
ACTIVATIONS = GATED_ACTIVATIONS + ("gelu",)


def _apply_act(name: str, gate_f32, u_f32):
    """Activation + gating on the f32 accumulators (in-kernel, VPU)."""
    if name == "swiglu":
        return jax.nn.silu(gate_f32) * u_f32
    if name == "geglu":
        return jax.nn.gelu(gate_f32, approximate=True) * u_f32
    if name == "gelu":
        return jax.nn.gelu(u_f32, approximate=True)
    raise ValueError(f"unknown activation {name!r}")


def _dot_f32(a: jax.Array, b: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# dense-layout kernels (fp16 weights / dense-quantized W4A16)
# ---------------------------------------------------------------------------

def _scale_rows(n_groups: int) -> int:
    """Rows of a down-projection scale block: one 8-row sublane tile of
    groups, or the whole group axis when it is shorter."""
    return min(8, n_groups)


def _make_kernel(activation: str, gated: bool, bias: bool, quant: bool,
                 rows: int = 1):
    """Kernel body for the dense-layout fused FFN.

    Grid (token_blocks, f_tiles, d_groups); operand order (quant):
      x, [gate packed+scales], up packed+scales, down packed+scales,
      [up_bias, down_bias], out, scratch: [gate_acc], up_acc, out_acc.
    fp variant drops the packed/scales pairs for plain (128, ·) blocks.
    Gate/up scale blocks hold all d-groups of an f-tile; down scale blocks
    hold ``rows`` f-tiles' groups.
    """

    def kernel(*refs):
        it = iter(refs)
        x_ref = next(it)
        if gated:
            g_refs = (next(it), next(it)) if quant else (next(it),)
        u_refs = (next(it), next(it)) if quant else (next(it),)
        d_refs = (next(it), next(it)) if quant else (next(it),)
        if bias:
            ub_ref, db_ref = next(it), next(it)
        o_ref = next(it)
        gacc = next(it) if gated else None
        uacc = next(it)
        oacc = next(it)

        j, g = pl.program_id(1), pl.program_id(2)
        nj, ng = pl.num_programs(1), pl.num_programs(2)

        @pl.when(g == 0)
        def _reset_tile():
            uacc[...] = jnp.zeros_like(uacc)
            if gated:
                gacc[...] = jnp.zeros_like(gacc)

        @pl.when((g == 0) & (j == 0))
        def _reset_out():
            oacc[...] = jnp.zeros_like(oacc)

        xg = x_ref[:, pl.ds(pl.multiple_of(g * GROUP_SIZE, GROUP_SIZE),
                            GROUP_SIZE)]

        def proj(refs_):
            if quant:
                # scale blocks hold every group (Mosaic tiles a block's last
                # two dims); the contraction group g is one row of it
                pk, sc = refs_
                w = unpack_int4_rows(pk[...])                  # (128, 128)
                return _dot_f32(xg, w) * pick_row(sc[...], g)
            (w_ref,) = refs_
            return _dot_f32(xg, w_ref[...].astype(x_ref.dtype))

        uacc[...] += proj(u_refs)
        if gated:
            gacc[...] += proj(g_refs)

        @pl.when(g == ng - 1)
        def _tile_done():
            u = uacc[...]
            if bias:
                u = u + ub_ref[...].astype(jnp.float32)
            h = _apply_act(activation, gacc[...] if gated else None, u)
            h16 = h.astype(x_ref.dtype)
            if quant:
                pk, sc = d_refs
                wd = unpack_int4_rows(pk[...])                 # (128, out_f)
                part = _dot_f32(h16, wd) * pick_row(sc[...], j % rows)
            else:
                (wd_ref,) = d_refs
                part = _dot_f32(h16, wd_ref[...].astype(x_ref.dtype))
            oacc[...] += part

        @pl.when((g == ng - 1) & (j == nj - 1))
        def _write():
            out = oacc[...]
            if bias:
                out = out + db_ref[...].astype(jnp.float32)
            o_ref[...] = out.astype(o_ref.dtype)

    return kernel


def _flatten_pad(x: jax.Array, in_f: int, block_tokens: int | None):
    x2 = x.reshape(-1, in_f)
    n_tok = x2.shape[0]
    bt = token_block(n_tok, block_tokens or DEFAULT_BLOCK_TOKENS)
    pad = (-n_tok) % bt
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, n_tok, bt


def _bias_rows(up_bias, down_bias, f: int, out_f: int, dtype):
    ub = jnp.zeros((f,), dtype) if up_bias is None else up_bias
    db = jnp.zeros((out_f,), dtype) if down_bias is None else down_bias
    return ub.reshape(1, f), db.reshape(1, out_f)


def _check_gated_bias(gated: bool, up_bias, down_bias):
    if gated and (up_bias is not None or down_bias is not None):
        raise ValueError("gated activations take no FFN biases")


@functools.partial(
    jax.jit, static_argnames=("activation", "block_tokens", "interpret"))
def ffn_fused_w4a16_pallas(
    x: jax.Array,
    gate: QuantizedTensor | None,
    up: QuantizedTensor,
    down: QuantizedTensor,
    *,
    activation: str = "swiglu",
    up_bias: jax.Array | None = None,
    down_bias: jax.Array | None = None,
    block_tokens: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused quantized FFN: ``down( act(x@gate) * (x@up) )`` in one dispatch.

    All three weights are dense W4A16 ``QuantizedTensor``s with 128-channel
    groups; ``activation`` picks swiglu/geglu (gated, ``gate`` required) or
    gelu (ungated, ``gate`` ignored, optional biases)."""
    if interpret is None:
        interpret = default_interpret()
    gated = activation in GATED_ACTIVATIONS
    _check_gated_bias(gated, up_bias, down_bias)
    in_f, f = up.shape
    out_f = down.shape[1]
    for name, qt in (("up", up), ("down", down)) + (
            (("gate", gate),) if gated else ()):
        if qt.group_size != GROUP_SIZE:
            raise ValueError(f"{name}: fused kernel needs 128-channel groups")
    if down.shape[0] != f:
        raise ValueError(f"down in_features {down.shape[0]} != d_ff {f}")
    if x.shape[-1] != in_f:
        raise ValueError(f"contraction mismatch {x.shape[-1]} vs {in_f}")
    if in_f % GROUP_SIZE or f % GROUP_SIZE or out_f % GROUP_SIZE:
        raise ValueError("d_model/d_ff/out must be multiples of 128")

    *lead, tokens, _ = x.shape
    x2, n_tok, bt = _flatten_pad(x, in_f, block_tokens)
    nj, ng = f // GROUP_SIZE, in_f // GROUP_SIZE
    grid = (x2.shape[0] // bt, nj, ng)
    bias = not gated
    rows = _scale_rows(nj)

    in_specs = [pl.BlockSpec((bt, in_f), lambda t, j, g: (t, 0))]
    args = [x2]
    if gated:
        in_specs += [
            pl.BlockSpec((_HALF, GROUP_SIZE), lambda t, j, g: (g, j)),
            pl.BlockSpec((ng, GROUP_SIZE), lambda t, j, g: (0, j)),
        ]
        args += [gate.packed, gate.scales]
    in_specs += [
        pl.BlockSpec((_HALF, GROUP_SIZE), lambda t, j, g: (g, j)),
        pl.BlockSpec((ng, GROUP_SIZE), lambda t, j, g: (0, j)),
        pl.BlockSpec((_HALF, out_f), lambda t, j, g: (j, 0)),
        pl.BlockSpec((rows, out_f), lambda t, j, g: (j // rows, 0)),
    ]
    args += [up.packed, up.scales, down.packed, down.scales]
    if bias:
        ub, db = _bias_rows(up_bias, down_bias, f, out_f, x.dtype)
        in_specs += [
            pl.BlockSpec((1, GROUP_SIZE), lambda t, j, g: (0, j)),
            pl.BlockSpec((1, out_f), lambda t, j, g: (0, 0)),
        ]
        args += [ub, db]

    scratch = ([pltpu.VMEM((bt, GROUP_SIZE), jnp.float32)] if gated else []) + [
        pltpu.VMEM((bt, GROUP_SIZE), jnp.float32),
        pltpu.VMEM((bt, out_f), jnp.float32),
    ]
    out = pl.pallas_call(
        _make_kernel(activation, gated, bias, quant=True, rows=rows),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, out_f), lambda t, j, g: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((x2.shape[0], out_f), x.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="ffn_fused_w4a16",
        interpret=interpret,
    )(*args)
    if n_tok != x2.shape[0]:
        out = out[:n_tok]
    return out.reshape(*lead, tokens, out_f)


@functools.partial(
    jax.jit, static_argnames=("activation", "block_tokens", "interpret"))
def ffn_fused_dense_pallas(
    x: jax.Array,
    gate: jax.Array | None,
    up: jax.Array,
    down: jax.Array,
    *,
    activation: str = "swiglu",
    up_bias: jax.Array | None = None,
    down_bias: jax.Array | None = None,
    block_tokens: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused 16-bit-weight FFN (same fusion, no dequant stage)."""
    if interpret is None:
        interpret = default_interpret()
    gated = activation in GATED_ACTIVATIONS
    _check_gated_bias(gated, up_bias, down_bias)
    in_f, f = up.shape
    out_f = down.shape[1]
    if x.shape[-1] != in_f or down.shape[0] != f:
        raise ValueError("FFN weight shape mismatch")
    if in_f % GROUP_SIZE or f % GROUP_SIZE or out_f % GROUP_SIZE:
        raise ValueError("d_model/d_ff/out must be multiples of 128")

    *lead, tokens, _ = x.shape
    x2, n_tok, bt = _flatten_pad(x, in_f, block_tokens)
    nj, ng = f // GROUP_SIZE, in_f // GROUP_SIZE
    grid = (x2.shape[0] // bt, nj, ng)
    bias = not gated

    in_specs = [pl.BlockSpec((bt, in_f), lambda t, j, g: (t, 0))]
    args = [x2]
    if gated:
        in_specs += [pl.BlockSpec((GROUP_SIZE, GROUP_SIZE),
                                  lambda t, j, g: (g, j))]
        args += [gate]
    in_specs += [
        pl.BlockSpec((GROUP_SIZE, GROUP_SIZE), lambda t, j, g: (g, j)),
        pl.BlockSpec((GROUP_SIZE, out_f), lambda t, j, g: (j, 0)),
    ]
    args += [up, down]
    if bias:
        ub, db = _bias_rows(up_bias, down_bias, f, out_f, x.dtype)
        in_specs += [
            pl.BlockSpec((1, GROUP_SIZE), lambda t, j, g: (0, j)),
            pl.BlockSpec((1, out_f), lambda t, j, g: (0, 0)),
        ]
        args += [ub, db]

    scratch = ([pltpu.VMEM((bt, GROUP_SIZE), jnp.float32)] if gated else []) + [
        pltpu.VMEM((bt, GROUP_SIZE), jnp.float32),
        pltpu.VMEM((bt, out_f), jnp.float32),
    ]
    out = pl.pallas_call(
        _make_kernel(activation, gated, bias, quant=False),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, out_f), lambda t, j, g: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((x2.shape[0], out_f), x.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="ffn_fused_dense",
        interpret=interpret,
    )(*args)
    if n_tok != x2.shape[0]:
        out = out[:n_tok]
    return out.reshape(*lead, tokens, out_f)


# ---------------------------------------------------------------------------
# sparse twin (scalar-prefetched kept-block indices)
# ---------------------------------------------------------------------------

def _make_sparse_kernel(activation: str, gated: bool, bias: bool,
                        down_sparse: bool, rows: int = 1):
    """Kernel body for the sparse fused FFN.

    Grid (token_blocks, down_f_steps, kept_contraction_blocks).  Prefetch
    refs: ftile (f-tile per outer step — down's kept blocks, or arange when
    down is dense-quantized), then gate/up kept-block index tables whose
    rows are f-tiles; they drive both the activation slice of the resident
    x block and the weight BlockSpec index maps (DMA-side weight gather)."""

    def kernel(*refs):
        it = iter(refs)
        ft_ref = next(it)
        gi_ref = next(it) if gated else None
        ui_ref = next(it)
        x_ref = next(it)
        if gated:
            gpk_ref, gsc_ref = next(it), next(it)
        upk_ref, usc_ref = next(it), next(it)
        dpk_ref, dsc_ref = next(it), next(it)
        if bias:
            ub_ref, db_ref = next(it), next(it)
        o_ref = next(it)
        gacc = next(it) if gated else None
        uacc = next(it)
        oacc = next(it)

        s, sg = pl.program_id(1), pl.program_id(2)
        ns, nsg = pl.num_programs(1), pl.num_programs(2)
        jf = ft_ref[s]

        @pl.when(sg == 0)
        def _reset_tile():
            uacc[...] = jnp.zeros_like(uacc)
            if gated:
                gacc[...] = jnp.zeros_like(gacc)

        @pl.when((sg == 0) & (s == 0))
        def _reset_out():
            oacc[...] = jnp.zeros_like(oacc)

        # activation gather: the kept d-block index picks the slice of the
        # RESIDENT x block (sparse_w4a16's DMA-side gather, moved on-chip)
        # scale blocks hold all kept blocks of the tile (Mosaic tiles a
        # block's last two dims); kept block sg is one row of it
        xu = x_ref[:, pl.ds(ui_ref[jf, sg] * GROUP_SIZE, GROUP_SIZE)]
        wu = unpack_int4_rows(upk_ref[0, 0])                  # (128, 128)
        uacc[...] += _dot_f32(xu, wu) * pick_row(usc_ref[0], sg)
        if gated:
            xg = x_ref[:, pl.ds(gi_ref[jf, sg] * GROUP_SIZE, GROUP_SIZE)]
            wg = unpack_int4_rows(gpk_ref[0, 0])
            gacc[...] += _dot_f32(xg, wg) * pick_row(gsc_ref[0], sg)

        @pl.when(sg == nsg - 1)
        def _tile_done():
            u = uacc[...]
            if bias:
                u = u + ub_ref[...].astype(jnp.float32)
            h = _apply_act(activation, gacc[...] if gated else None, u)
            h16 = h.astype(x_ref.dtype)
            if down_sparse:
                wd = unpack_int4_rows(dpk_ref[:, 0])          # (Td, 128, 128)
                part = jax.lax.dot_general(
                    h16, wd, dimension_numbers=(((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)        # (bt, Td, 128)
                part = part * pick_row(dsc_ref[...], s, axis=1)[:, 0][None]
                oacc[...] += part.reshape(part.shape[0], -1)
            else:
                wd = unpack_int4_rows(dpk_ref[...])           # (128, out_f)
                part = _dot_f32(h16, wd) * pick_row(dsc_ref[...], jf % rows)
                oacc[...] += part

        @pl.when((sg == nsg - 1) & (s == ns - 1))
        def _write():
            out = oacc[...]
            if bias:
                out = out + db_ref[...].astype(jnp.float32)
            o_ref[...] = out.astype(o_ref.dtype)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("activation", "block_tokens", "interpret"))
def ffn_fused_sparse_pallas(
    x: jax.Array,
    gate: SparseQuantizedTensor | None,
    up: SparseQuantizedTensor,
    down: QuantizedTensor | SparseQuantizedTensor,
    *,
    activation: str = "swiglu",
    up_bias: jax.Array | None = None,
    down_bias: jax.Array | None = None,
    block_tokens: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused log-scale-sparse FFN.

    ``gate``/``up`` are block-sparse (per-f-tile kept d-blocks, scalar
    prefetched); ``down`` is either dense-quantized (all f-tiles visited) or
    ``tile_uniform`` block-sparse, in which case the outer grid walks ONLY
    its kept f-blocks — dropped hidden tiles are never computed and their
    gate/up weight blocks never leave HBM."""
    if interpret is None:
        interpret = default_interpret()
    gated = activation in GATED_ACTIVATIONS
    _check_gated_bias(gated, up_bias, down_bias)
    in_f, f = up.shape
    down_sparse = isinstance(down, SparseQuantizedTensor)
    out_f = down.shape[1]
    if down.shape[0] != f or x.shape[-1] != in_f:
        raise ValueError("FFN weight shape mismatch")
    if up.group_size != GROUP_SIZE or down.group_size != GROUP_SIZE:
        raise ValueError("fused kernel needs 128-channel groups")
    if gated and (gate.shape != up.shape
                  or gate.kept_blocks != up.kept_blocks):
        raise ValueError("gate/up must share shape and kept-block count")
    if down_sparse and not down.tile_uniform:
        raise ValueError("sparse down must be tile_uniform for the fused "
                         "kernel (one kept set for all output channels)")

    *lead, tokens, _ = x.shape
    x2, n_tok, bt = _flatten_pad(x, in_f, block_tokens)
    nt = x2.shape[0] // bt
    n_ftiles = f // GROUP_SIZE
    sc = up.kept_blocks
    if down_sparse:
        ftile = down.block_idx[0]                              # (S_dn,)
        n_fsteps = down.kept_blocks
    else:
        ftile = jnp.arange(n_ftiles, dtype=jnp.int32)
        n_fsteps = n_ftiles
    grid = (nt, n_fsteps, sc)
    bias = not gated
    rows = _scale_rows(n_ftiles)

    # prefetch + tensor operands; index maps receive the prefetch refs last
    prefetch = [ftile]
    if gated:
        prefetch.append(gate.block_idx)
    prefetch.append(up.block_idx)
    n_pre = len(prefetch)

    def _ft(s, refs):
        return refs[0][s]

    in_specs = [pl.BlockSpec((bt, in_f), lambda t, s, sg, *r: (t, 0))]
    args = [x2]
    if gated:
        in_specs += [
            pl.BlockSpec((1, 1, _HALF, GROUP_SIZE),
                         lambda t, s, sg, *r: (_ft(s, r), sg, 0, 0)),
            pl.BlockSpec((1, sc, GROUP_SIZE),
                         lambda t, s, sg, *r: (_ft(s, r), 0, 0)),
        ]
        args += [gate.packed, gate.scales]
    in_specs += [
        pl.BlockSpec((1, 1, _HALF, GROUP_SIZE),
                     lambda t, s, sg, *r: (_ft(s, r), sg, 0, 0)),
        pl.BlockSpec((1, sc, GROUP_SIZE),
                     lambda t, s, sg, *r: (_ft(s, r), 0, 0)),
    ]
    args += [up.packed, up.scales]
    if down_sparse:
        td = out_f // GROUP_SIZE
        in_specs += [
            pl.BlockSpec((td, 1, _HALF, GROUP_SIZE),
                         lambda t, s, sg, *r: (0, s, 0, 0)),
            pl.BlockSpec((td, n_fsteps, GROUP_SIZE),
                         lambda t, s, sg, *r: (0, 0, 0)),
        ]
    else:
        in_specs += [
            pl.BlockSpec((_HALF, out_f),
                         lambda t, s, sg, *r: (_ft(s, r), 0)),
            pl.BlockSpec((rows, out_f),
                         lambda t, s, sg, *r: (_ft(s, r) // rows, 0)),
        ]
    args += [down.packed, down.scales]
    if bias:
        ub, db = _bias_rows(up_bias, down_bias, f, out_f, x.dtype)
        in_specs += [
            pl.BlockSpec((1, GROUP_SIZE),
                         lambda t, s, sg, *r: (0, _ft(s, r))),
            pl.BlockSpec((1, out_f), lambda t, s, sg, *r: (0, 0)),
        ]
        args += [ub, db]

    scratch = ([pltpu.VMEM((bt, GROUP_SIZE), jnp.float32)] if gated else []) + [
        pltpu.VMEM((bt, GROUP_SIZE), jnp.float32),
        pltpu.VMEM((bt, out_f), jnp.float32),
    ]
    out = pl.pallas_call(
        _make_sparse_kernel(activation, gated, bias, down_sparse, rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_pre,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bt, out_f), lambda t, s, sg, *r: (t, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((x2.shape[0], out_f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="ffn_fused_sparse",
        interpret=interpret,
    )(*(prefetch + args))
    if n_tok != x2.shape[0]:
        out = out[:n_tok]
    return out.reshape(*lead, tokens, out_f)


# ---------------------------------------------------------------------------
# blocked-XLA twin (CPU CI parity / dry-run path)
# ---------------------------------------------------------------------------

def _unpack_f32(packed: jax.Array, group_size: int) -> jax.Array:
    """(in/2, out) packed nibbles -> (groups, gs, out) f32 integer values.

    Unlike the ref oracle, no intermediate bf16 weight matrix is
    materialized — the nibbles go straight to the f32 einsum operand (int4
    is exact in both, so numerics are identical; one fewer full-matrix
    round trip through memory, the twin's decode-shape win)."""
    half = group_size // 2
    out_f = packed.shape[-1]
    return unpack_int4_rows(packed.reshape(-1, half, out_f), jnp.float32)


def w4a16_matmul_f32(x: jax.Array, qt: QuantizedTensor) -> jax.Array:
    """Group-exact ``x @ dequant(qt)`` returning f32 (scale-after-dot)."""
    in_f = qt.shape[0]
    gs = qt.group_size
    xg = x.reshape(*x.shape[:-1], in_f // gs, gs).astype(jnp.float32)
    qg = _unpack_f32(qt.packed, gs)
    partial = jnp.einsum("...kg,kgo->...ko", xg, qg,
                         preferred_element_type=jnp.float32)
    return (partial * qt.scales.astype(jnp.float32)).sum(axis=-2)


def sparse_matmul_f32(x: jax.Array, st: SparseQuantizedTensor) -> jax.Array:
    """Block-gathered sparse W4A16 matmul returning f32 (per-block scale)."""
    in_f, out_f = st.shape
    g = st.group_size
    *lead, tokens, _ = x.shape
    xb = x.reshape(-1, in_f // g, g).astype(jnp.float32)
    w = unpack_int4_rows(st.packed, jnp.float32)              # (T,S,128,128)
    xg = jnp.take(xb, st.block_idx, axis=1)                    # (N,T,S,128)
    part = jnp.einsum("ntsg,tsgo->ntso", xg, w,
                      preferred_element_type=jnp.float32)
    out = (part * st.scales.astype(jnp.float32)[None]).sum(axis=2)
    return out.reshape(*lead, tokens, out_f)


def ffn_w4a16_xla(
    x: jax.Array,
    gate,
    up,
    down,
    *,
    activation: str = "swiglu",
    up_bias: jax.Array | None = None,
    down_bias: jax.Array | None = None,
) -> jax.Array:
    """Blocked-XLA twin of the fused kernel (any weight mix).

    Same numerics contract as the Pallas kernels: per-quant-group (the block
    axis) scale-after-dot in f32, activation and gating on the f32
    accumulators, hidden state cast to the compute dtype only for the down
    contraction.  Unpacks int4 straight to the f32 dot operand — no
    intermediate 16-bit weight matrix — which is what makes it faster than
    the unfused 3-matmul path at decode shapes on CPU."""
    _check_gated_bias(activation in GATED_ACTIVATIONS, up_bias, down_bias)

    def mm(x_, w):
        if isinstance(w, QuantizedTensor):
            return w4a16_matmul_f32(x_, w)
        if isinstance(w, SparseQuantizedTensor):
            return sparse_matmul_f32(x_, w)
        return jax.lax.dot_general(
            x_.astype(jnp.float32), w.astype(jnp.float32),
            (((x_.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if activation == "swiglu":
        h = jax.nn.silu(mm(x, gate)) * mm(x, up)
    elif activation == "geglu":
        h = jax.nn.gelu(mm(x, gate), approximate=True) * mm(x, up)
    elif activation == "gelu":
        u = mm(x, up)
        if up_bias is not None:
            u = u + up_bias.astype(jnp.float32)
        h = jax.nn.gelu(u, approximate=True)
    else:
        raise ValueError(f"unknown activation {activation!r}")
    out = mm(h.astype(x.dtype), down)
    if down_bias is not None:
        out = out + down_bias.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dispatch predicate
# ---------------------------------------------------------------------------

def fused_variant(x, gate, up, down, activation, up_bias, down_bias):
    """Which fused Pallas kernel fits these operands, if any.

    Returns ``"quant"`` / ``"sparse"`` / ``"fp"`` / ``None`` — a STATIC
    decision (types, shapes, group sizes, the tile_uniform flag), so the
    choice is stable under jit and never adds executables."""
    gated = activation in GATED_ACTIVATIONS
    if activation not in ACTIVATIONS:
        return None
    if gated and (up_bias is not None or down_bias is not None):
        return None
    if len(up.shape) != 2 or len(down.shape) != 2:
        return None
    if gated and (gate is None or len(gate.shape) != 2):
        return None
    in_f, f = up.shape
    out_f = down.shape[1]
    if x.shape[-1] != in_f or down.shape[0] != f:
        return None
    if in_f % GROUP_SIZE or f % GROUP_SIZE or out_f % GROUP_SIZE:
        return None
    ws = ((gate, up, down) if gated else (up, down))

    if all(isinstance(w, QuantizedTensor) for w in ws):
        if all(w.group_size == GROUP_SIZE for w in ws):
            return "quant"
        return None
    if (isinstance(up, SparseQuantizedTensor)
            and (not gated or isinstance(gate, SparseQuantizedTensor))):
        if not isinstance(down, (QuantizedTensor, SparseQuantizedTensor)):
            return None
        if up.group_size != GROUP_SIZE or down.group_size != GROUP_SIZE:
            return None
        if gated and (gate.shape != up.shape
                      or gate.kept_blocks != up.kept_blocks
                      or gate.group_size != GROUP_SIZE):
            return None
        if isinstance(down, QuantizedTensor):
            return "sparse"
        if down.tile_uniform:
            return "sparse"
        return None
    if all(isinstance(w, jax.Array) and jnp.issubdtype(w.dtype, jnp.floating)
           for w in ws):
        return "fp"
    return None
