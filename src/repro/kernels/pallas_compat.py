"""Helpers shared by the Pallas kernels of this package.

:func:`default_interpret` — every kernel resolves ``interpret=None`` through
it, so direct callers get the Mosaic lowering on TPU and the interpreter
elsewhere without passing a flag.  The others keep each kernel body inside
what Mosaic lowers: the int4 unpack, the in-block row pick of quant scales,
and the decode-shaped token block.
"""

import functools

import jax
import jax.numpy as jnp


@functools.cache
def default_interpret() -> bool:
    """True (interpret mode) unless a TPU backend is attached."""
    return not any(d.platform == "tpu" for d in jax.devices())


def unpack_int4_rows(packed_u8: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    """(..., 64, n) packed nibbles -> (..., 128, n) int4 values as ``dtype``.

    Sublane-pair packing (``core.quant``): the low nibble is row r, the high
    nibble row r + 64, so unpacking is two shifts per nibble and one sublane
    concat — integer-exact in bf16 and f32 alike.  The shifts run on int32:
    Mosaic has no 8-bit shifts, and the arithmetic right shift sign-extends
    each nibble.  The one unpack every kernel and the XLA twin share."""
    p = packed_u8.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(p, 28), 28)
    hi = jnp.right_shift(jnp.left_shift(p, 24), 28)
    return jnp.concatenate([lo, hi], axis=-2).astype(dtype)


def pick_row(block: jax.Array, i, axis: int = 0) -> jax.Array:
    """Entry ``i`` (traced) of ``block`` along ``axis``, kept as a size-1
    axis, in f32.

    The kernels' scale blocks hold every quant group of a tile, because
    Mosaic tiles a block's last two dims and a one-group block fits no
    tile; the group a grid step needs is then one row of the block.
    Mosaic loads sublane slices only at offsets it can prove are multiples
    of 8, so the row is taken as a one-hot masked sum — exact, since every
    other term is zero."""
    b = block.astype(jnp.float32)
    hit = jax.lax.broadcasted_iota(jnp.int32, b.shape, axis) == i
    return jnp.sum(jnp.where(hit, b, 0.0), axis=axis, keepdims=True)


def token_block(n_tok: int, block_tokens: int) -> int:
    """Decode-shaped token-block size for the matmul-family kernels.

    A batch-1 decode step carries ONE live token row; the old
    ``min(block_tokens, max(8, n_tok))`` rule padded it to an 8-row block —
    8x wasted activation DMA and MXU issue on the serving hot path.  Small
    token counts now get an exact-fit block (no padding at all up to
    ``block_tokens``); only prefill-sized calls tile at ``block_tokens`` and
    pad the remainder."""
    return n_tok if n_tok <= block_tokens else block_tokens
