"""Ahead-of-time compiles of the main-path kernels for a described v5e chip.

Each test lowers one Pallas kernel at Qwen-7B widths (d 4096, d_ff 11008,
32 query heads, 4 KV heads, head_dim 128, bf16) for one chip of a described
``v5e:2x2`` topology and asserts that the compiled program contains the
Mosaic custom call.  Nothing runs: these compiles catch what interpret mode
cannot — block shapes Mosaic refuses, VMEM overruns — before any chip time
is spent.  The topology is described inside a fixture (never at import), so
every pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU compiler.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.compiler import quantize_model
from repro.kernels.decode_flash import (
    decode_flash_attention_pallas, mixed_flash_attention_pallas)
from repro.kernels.ffn_fused import (
    ffn_fused_sparse_pallas, ffn_fused_w4a16_pallas)
from repro.kernels.sparse_w4a16 import sparse_w4a16_matmul_pallas
from repro.kernels.w4a16_matmul import w4a16_matmul_pallas

D, F, HQ, HKV, HD, VOCAB = 4096, 11008, 32, 4, 128, 151936
BATCH, CHUNK, MAX_LEN, PAGE = 4, 16, 2048, 16
BF16 = jnp.bfloat16
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """Shape factory on one described chip, with the persistent compile
    cache off: a compile for a chip that is not attached is written to the
    cache but cannot be read back, and the next compile would warn."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)
    yield sds
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_mosaic(text, kernel):
    """The named Mosaic kernel is in the compiled program, read the way
    chip_smoke.py reads the served step."""
    sys.path.insert(0, ROOT)
    from chip_smoke import mosaic_kernels
    assert "tpu_custom_call" in text
    assert kernel in mosaic_kernels(text), mosaic_kernels(text)


def _strategy2(**shapes):
    """Shapes (no values) of weights packed as the serving path packs them:
    ``quantize_model`` under strategy2, keyed by the model's leaf names."""
    tree = {name: jax.ShapeDtypeStruct(s, BF16) for name, s in shapes.items()}
    return jax.eval_shape(lambda p: quantize_model(p, "strategy2"), tree)


# token counts of the engine's two tick shapes: a decode tick (one token per
# row) and a mixed tick at the smallest chunk bucket
TOKENS = [BATCH, BATCH * CHUNK]


@pytest.mark.parametrize("layout", ["slot", "paged", "int8", "paged-int8"])
def test_decode_flash(chip, layout):
    paged, int8 = layout.startswith("paged"), layout.endswith("int8")
    q = jax.ShapeDtypeStruct((BATCH, HQ, 1, HD), BF16)
    n_pages = MAX_LEN // PAGE
    rows = BATCH * n_pages + 1 if paged else BATCH
    kv_shape = ((rows, HKV, PAGE, HD) if paged
                else (BATCH, HKV, MAX_LEN, HD))
    kv = jax.ShapeDtypeStruct(kv_shape, jnp.int8 if int8 else BF16)
    sc = jax.ShapeDtypeStruct(kv_shape[:3] + (1,), jnp.float32)
    lengths = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    pt = jax.ShapeDtypeStruct((BATCH, n_pages), jnp.int32)
    args = [q, kv, kv, lengths] + ([sc, sc] if int8 else []) + (
        [pt] if paged else [])

    def fn(q, k, v, lengths, *rest):
        rest = list(rest)
        ks, vs = (rest.pop(0), rest.pop(0)) if int8 else (None, None)
        return decode_flash_attention_pallas(
            q, k, v, lengths, k_scale=ks, v_scale=vs,
            page_table=rest.pop(0) if paged else None, interpret=False)
    _assert_mosaic(_compiled_text(fn, *chip(args)), "mixed_flash_attention")


@pytest.mark.parametrize("layout", ["slot", "paged", "paged-int8"])
def test_mixed_chunk(chip, layout):
    paged, int8 = layout.startswith("paged"), layout.endswith("int8")
    q = jax.ShapeDtypeStruct((BATCH, HQ, CHUNK, HD), BF16)
    n_pages = MAX_LEN // PAGE
    kv_shape = ((BATCH * n_pages + 1, HKV, PAGE, HD) if paged
                else (BATCH, HKV, MAX_LEN, HD))
    kv = jax.ShapeDtypeStruct(kv_shape, jnp.int8 if int8 else BF16)
    sc = jax.ShapeDtypeStruct(kv_shape[:3] + (1,), jnp.float32)
    vec = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    pt = jax.ShapeDtypeStruct((BATCH, n_pages), jnp.int32)
    args = [q, kv, kv, vec, vec] + ([sc, sc] if int8 else []) + (
        [pt] if paged else [])

    def fn(q, k, v, lengths, q_lens, *rest):
        rest = list(rest)
        ks, vs = (rest.pop(0), rest.pop(0)) if int8 else (None, None)
        return mixed_flash_attention_pallas(
            q, k, v, lengths, q_lens, k_scale=ks, v_scale=vs,
            page_table=rest.pop(0) if paged else None, interpret=False)
    _assert_mosaic(_compiled_text(fn, *chip(args)), "mixed_flash_attention")


@pytest.mark.parametrize("tokens", TOKENS)
@pytest.mark.parametrize("kind", ["w4", "sparse"])
def test_ffn_fused(chip, kind, tokens):
    """Dense W4 weights, and strategy2's (gate/up 0.25, down 0.5)."""
    x = jax.ShapeDtypeStruct((tokens, D), BF16)
    shapes = dict(gate=(D, F), up=(D, F), down=(F, D))
    if kind == "w4":
        w = jax.eval_shape(lambda p: quantize_model(p, "dense"), {
            n: jax.ShapeDtypeStruct(s, BF16) for n, s in shapes.items()})
        kernel = ffn_fused_w4a16_pallas
    else:
        w = _strategy2(**shapes)
        kernel = ffn_fused_sparse_pallas
    gate, up, down = w["gate"], w["up"], w["down"]

    def fn(x, gate, up, down):
        return kernel(x, gate, up, down, interpret=False)
    _assert_mosaic(_compiled_text(fn, *chip((x, gate, up, down))),
                   "ffn_fused_w4a16" if kind == "w4" else "ffn_fused_sparse")


@pytest.mark.parametrize("tokens", TOKENS)
@pytest.mark.parametrize("out_f", [D, HKV * HD, VOCAB])
def test_w4a16_matmul(chip, out_f, tokens):
    """The q/k/v projections and the lm_head (151936 is 128 x a prime)."""
    x = jax.ShapeDtypeStruct((tokens, D), BF16)
    w = _strategy2(lm_head=(D, out_f))["lm_head"]

    def fn(x, w):
        return w4a16_matmul_pallas(x, w, interpret=False)
    _assert_mosaic(_compiled_text(fn, *chip((x, w))), "w4a16_matmul")


@pytest.mark.parametrize("tokens", TOKENS)
def test_sparse_w4a16(chip, tokens):
    """The attention output projection at strategy2 density 0.5."""
    x = jax.ShapeDtypeStruct((tokens, D), BF16)
    w = _strategy2(wo=(D, D))["wo"]

    def fn(x, w):
        return sparse_w4a16_matmul_pallas(x, w, interpret=False)
    _assert_mosaic(_compiled_text(fn, *chip((x, w))), "sparse_w4a16_matmul")
