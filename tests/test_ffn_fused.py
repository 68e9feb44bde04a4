"""Fused FFN datapath tests (kernels/ffn_fused.py + ops.ffn_w4a16).

Coverage per the PR-4 checklist:
* fused (Pallas, interpret) ≡ blocked-XLA twin ≡ unfused ref across
  {swiglu, geglu, gelu+bias} × {dense, W4A16, sparse} × token counts
  including non-multiples of the block;
* ops dispatch: static variant selection, graceful fallback (non-128
  groups, non-tile-uniform sparse down);
* mlp_apply wiring: plain 16-bit weights stay bit-identical to the seed
  composition; quantized weights route through the twin;
* MoE: quantized experts dispatch through ops (no dense dequantize-
  everything oracle in the hot loop);
* engine-vs-oracle token parity for a SPARSE-strategy quantized model and
  the compile-cache bound (the fused FFN adds no executables);
* decode-shaped token blocking (no 8-row pad at batch 1).
"""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import quantize
from repro.core.sparsity import block_sparsify_quantize
from repro.kernels import ffn_fused, ops, ref
from repro.kernels.pallas_compat import token_block


def _rand(shape, seed=0, dtype=jnp.bfloat16, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(0, scale, shape).astype(np.float32)).astype(dtype)


def _weights(kind: str, d: int, f: int, seed=0):
    """(gate, up, down) for a weight kind: dense | w4 | sparse-<density>."""
    wg = _rand((d, f), seed + 1, jnp.float32, 0.05)
    wu = _rand((d, f), seed + 2, jnp.float32, 0.05)
    wd = _rand((f, d), seed + 3, jnp.float32, 0.05)
    if kind == "dense":
        return (wg.astype(jnp.bfloat16), wu.astype(jnp.bfloat16),
                wd.astype(jnp.bfloat16))
    if kind == "w4":
        return quantize(wg), quantize(wu), quantize(wd)
    density = float(kind.split("-")[1])
    return (block_sparsify_quantize(wg, density),
            block_sparsify_quantize(wu, density),
            block_sparsify_quantize(wd, density, tile_uniform=True))


TOL = dict(rtol=4e-2, atol=4e-2)


class TestFusedParity:
    """fused ≡ twin ≡ unfused-ref for every activation × weight kind."""

    @pytest.mark.parametrize("activation", ["swiglu", "geglu", "gelu"])
    @pytest.mark.parametrize("kind", ["dense", "w4", "sparse-0.5",
                                      "sparse-0.25"])
    @pytest.mark.parametrize("tokens", [1, 57])
    def test_three_impls_agree(self, activation, kind, tokens):
        d = f = 1024 if kind.startswith("sparse") else 512
        gate, up, down = _weights(kind, d, f, seed=tokens)
        x = _rand((tokens, d), seed=tokens + 9)
        ub = db = None
        if activation == "gelu":
            ub = _rand((f,), seed=31, scale=0.1)
            db = _rand((d,), seed=32, scale=0.1)
        kw = dict(activation=activation, up_bias=ub, down_bias=db)
        want = np.asarray(ops.ffn_w4a16(x, gate, up, down, impl="ref", **kw),
                          np.float32)
        for impl in ("pallas", "xla"):
            got = np.asarray(ops.ffn_w4a16(x, gate, up, down, impl=impl, **kw),
                             np.float32)
            np.testing.assert_allclose(got, want, err_msg=impl, **TOL)

    def test_leading_batch_dims(self):
        gate, up, down = _weights("w4", 256, 384)
        x = _rand((2, 3, 5, 256), seed=4)
        got = ops.ffn_w4a16(x, gate, up, down, impl="pallas")
        want = ops.ffn_w4a16(x, gate, up, down, impl="ref")
        assert got.shape == (2, 3, 5, 256)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **TOL)

    def test_block_boundary_tokens(self):
        """Token counts straddling the block cap pad correctly."""
        gate, up, down = _weights("w4", 256, 256)
        for tokens in (ffn_fused.DEFAULT_BLOCK_TOKENS - 1,
                       ffn_fused.DEFAULT_BLOCK_TOKENS,
                       ffn_fused.DEFAULT_BLOCK_TOKENS + 3):
            x = _rand((tokens, 256), seed=tokens)
            got = ops.ffn_w4a16(x, gate, up, down, impl="pallas")
            want = ops.ffn_w4a16(x, gate, up, down, impl="ref")
            np.testing.assert_allclose(np.asarray(got, np.float32),
                                       np.asarray(want, np.float32), **TOL)

    def test_sparse_skips_dropped_hidden_tiles(self):
        """With a tile-uniform sparse down, the fused grid walks only the
        kept f-blocks — result still matches the unfused oracle that
        computes every hidden tile."""
        gate, up, down = _weights("sparse-0.25", 1024, 1024)
        assert down.tile_uniform and down.kept_blocks == 2  # of 8 f-tiles
        x = _rand((8, 1024), seed=77)
        got = ffn_fused.ffn_fused_sparse_pallas(x, gate, up, down)
        want = ref.ffn_ref(x, gate, up, down)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **TOL)


class TestDispatch:
    def test_variant_selection(self):
        d = f = 1024
        fp = _weights("dense", d, f)
        q = _weights("w4", d, f)
        sp = _weights("sparse-0.5", d, f)
        assert ffn_fused.fused_variant(
            _rand((1, d)), *fp, "swiglu", None, None) == "fp"
        assert ffn_fused.fused_variant(
            _rand((1, d)), *q, "swiglu", None, None) == "quant"
        assert ffn_fused.fused_variant(
            _rand((1, d)), *sp, "swiglu", None, None) == "sparse"
        # sparse gate/up + dense-quant down is also fused
        assert ffn_fused.fused_variant(
            _rand((1, d)), sp[0], sp[1], q[2], "swiglu", None, None) == "sparse"
        # non-tile-uniform sparse down cannot fuse (falls back, stays correct)
        dn = block_sparsify_quantize(
            _rand((f, d), 9, jnp.float32, 0.05), 0.5, tile_uniform=False)
        assert ffn_fused.fused_variant(
            _rand((1, d)), sp[0], sp[1], dn, "swiglu", None, None) is None
        x = _rand((3, d), seed=5)
        with pytest.warns(ops.KernelFallbackWarning):
            got = ops.ffn_w4a16(x, sp[0], sp[1], dn, impl="pallas")
        want = ops.ffn_w4a16(x, sp[0], sp[1], dn, impl="ref")
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **TOL)

    def test_sparse_gate_up_with_16bit_down_falls_back(self):
        """A strategy may keep a kind 16-bit: sparse gate/up + plain down
        must return None (not crash on down.group_size) and stay correct."""
        d = f = 1024
        sp = _weights("sparse-0.5", d, f)
        dn16 = _rand((f, d), 9, jnp.bfloat16, 0.05)
        assert ffn_fused.fused_variant(
            _rand((1, d)), sp[0], sp[1], dn16, "swiglu", None, None) is None
        x = _rand((2, d), seed=14)
        with pytest.warns(ops.KernelFallbackWarning):
            got = ops.ffn_w4a16(x, sp[0], sp[1], dn16, impl="pallas")
        want = ops.ffn_w4a16(x, sp[0], sp[1], dn16, impl="ref")
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **TOL)

    def test_gated_bias_rejected_on_every_impl(self):
        """Biases with gated activations are a contract violation — one
        ValueError at the op boundary, not silent per-impl divergence."""
        gate, up, down = _weights("w4", 256, 256)
        x = _rand((2, 256), seed=15)
        b = _rand((256,), seed=16)
        for impl in ("pallas", "xla", "ref"):
            with pytest.raises(ValueError, match="no FFN biases"):
                ops.ffn_w4a16(x, gate, up, down, activation="swiglu",
                              down_bias=b, impl=impl)

    def test_small_group_falls_back_to_twin(self):
        """MoE-style 64-channel quant groups don't fit the kernel; the twin
        handles them with the same numerics contract."""
        d, f = 256, 256
        gq = quantize(_rand((d, f), 1, jnp.float32, 0.05), group_size=64)
        uq = quantize(_rand((d, f), 2, jnp.float32, 0.05), group_size=64)
        dq = quantize(_rand((f, d), 3, jnp.float32, 0.05), group_size=64)
        assert ffn_fused.fused_variant(
            _rand((1, d)), gq, uq, dq, "swiglu", None, None) is None
        x = _rand((4, d), seed=8)
        with pytest.warns(ops.KernelFallbackWarning):
            got = ops.ffn_w4a16(x, gq, uq, dq, impl="pallas")
        want = ops.ffn_w4a16(x, gq, uq, dq, impl="ref")
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), **TOL)


class TestMlpWiring:
    def test_dense_weights_bit_identical_to_seed_composition(self):
        """Plain 16-bit weights must keep the training path's exact
        numerics (same dots, same dtype chain)."""
        from repro.models import layers
        cfg = type("C", (), {"activation": "swiglu", "use_kernels": False})()
        d, f = 96, 160  # deliberately NOT 128-tileable
        p = {"gate": _rand((d, f), 1), "up": _rand((d, f), 2),
             "down": _rand((f, d), 3)}
        x = _rand((4, 7, d), seed=4)
        got = layers.mlp_apply(cfg, p, x)
        want = layers.linear(
            jax.nn.silu(layers.linear(x, p["gate"])) * layers.linear(x, p["up"]),
            p["down"])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_gelu_bias_bit_identical(self):
        from repro.models import layers
        cfg = type("C", (), {"activation": "gelu", "use_kernels": False})()
        d, f = 96, 160
        p = {"up": _rand((d, f), 1), "up_bias": _rand((f,), 2),
             "down": _rand((f, d), 3), "down_bias": _rand((d,), 4)}
        x = _rand((2, 5, d), seed=6)
        got = layers.mlp_apply(cfg, p, x)
        want = layers.linear(
            jax.nn.gelu(layers.linear(x, p["up"], p["up_bias"]),
                        approximate=True),
            p["down"], p["down_bias"])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_dense_use_kernels_stays_differentiable(self):
        """use_kernels=True with plain 16-bit weights must keep the seed's
        dot path (differentiable, same numerics) — the fused Pallas kernel
        is for the quantized serving path only."""
        from repro.models import layers
        cfg = type("C", (), {"activation": "swiglu", "use_kernels": True})()
        d, f = 128, 256
        p = {"gate": _rand((d, f), 1), "up": _rand((d, f), 2),
             "down": _rand((f, d), 3)}
        x = _rand((2, 4, d), seed=4)
        got = layers.mlp_apply(cfg, p, x)
        want = layers.linear(
            jax.nn.silu(layers.linear(x, p["gate"])) * layers.linear(x, p["up"]),
            p["down"])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        g = jax.grad(lambda xx: layers.mlp_apply(cfg, p, xx).astype(
            jnp.float32).sum())(x)
        assert g.shape == x.shape

    def test_quantized_weights_route_through_twin(self):
        from repro.models import layers
        cfg = type("C", (), {"activation": "swiglu", "use_kernels": False})()
        gate, up, down = _weights("w4", 256, 384)
        p = {"gate": gate, "up": up, "down": down}
        x = _rand((3, 256), seed=7)
        got = layers.mlp_apply(cfg, p, x)
        want = ffn_fused.ffn_w4a16_xla(x, gate, up, down, activation="swiglu")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestMoE:
    def test_no_dense_oracle_in_hot_path(self):
        """The quantized MoE paths dispatch through ops, not the
        dequantize-everything ref oracle."""
        import repro.models.moe as moe
        src = inspect.getsource(moe)
        assert "w4a16_matmul_ref" not in src
        assert "kref" not in src
        assert "ops.ffn_w4a16" in src

    def test_local_quantized_experts_match_dequantized(self):
        """Quantized expert FFNs (through ops.ffn_w4a16) ≈ the same MoE run
        on the dequantized weights — identical routing, group-exact FFN."""
        from repro.configs import get_smoke_config
        from repro.models import moe
        cfg = get_smoke_config("mixtral-8x22b")
        p = moe.moe_init(jax.random.PRNGKey(0), cfg)
        x = _rand((2, 8, cfg.d_model), seed=3, dtype=cfg.dtype)
        qp = dict(p)
        qp["gate"] = jax.vmap(quantize)(p["gate"].astype(jnp.float32))
        qp["up"] = jax.vmap(quantize)(p["up"].astype(jnp.float32))
        qp["down"] = jax.vmap(quantize)(p["down"].astype(jnp.float32))
        dq = dict(p)
        dq["gate"] = jax.vmap(lambda q: q.dequantize(cfg.dtype))(qp["gate"])
        dq["up"] = jax.vmap(lambda q: q.dequantize(cfg.dtype))(qp["up"])
        dq["down"] = jax.vmap(lambda q: q.dequantize(cfg.dtype))(qp["down"])
        out_q, aux_q = moe._moe_apply_local(cfg, qp, x)
        out_d, aux_d = moe._moe_apply_local(cfg, dq, x)
        np.testing.assert_allclose(np.asarray(out_q, np.float32),
                                   np.asarray(out_d, np.float32),
                                   rtol=5e-2, atol=5e-2)
        np.testing.assert_allclose(float(aux_q), float(aux_d), rtol=1e-3)


class TestTokenBlocking:
    def test_token_block_decode_shapes(self):
        assert token_block(1, 256) == 1          # B=1 decode: no 8-row pad
        assert token_block(3, 256) == 3
        assert token_block(200, 256) == 200      # exact fit below the cap
        assert token_block(256, 256) == 256
        assert token_block(1000, 256) == 256     # prefill: tile at the cap

    def test_single_token_kernels_exact_fit(self):
        """tokens=1 through both standalone kernels (the old path padded to
        8 rows; the new one runs a 1-row block)."""
        from repro.kernels.sparse_w4a16 import sparse_w4a16_matmul_pallas
        from repro.kernels.w4a16_matmul import w4a16_matmul_pallas
        x = _rand((1, 1024), seed=11)
        qt = quantize(_rand((1024, 256), 12, jnp.float32))
        st = block_sparsify_quantize(_rand((1024, 256), 13, jnp.float32), 0.5)
        np.testing.assert_allclose(
            np.asarray(w4a16_matmul_pallas(x, qt), np.float32),
            np.asarray(ref.w4a16_matmul_ref(x, qt), np.float32), **TOL)
        np.testing.assert_allclose(
            np.asarray(sparse_w4a16_matmul_pallas(x, st), np.float32),
            np.asarray(ref.sparse_w4a16_matmul_ref(x, st), np.float32), **TOL)


class TestTileUniform:
    def test_rows_identical_and_flagged(self):
        w = _rand((2048, 256), 21, jnp.float32)
        st = block_sparsify_quantize(w, 0.25, tile_uniform=True)
        idx = np.asarray(st.block_idx)
        assert st.tile_uniform
        assert (idx == idx[0]).all()
        # and the plain layout stays per-tile
        st2 = block_sparsify_quantize(w, 0.25)
        assert not st2.tile_uniform

    def test_strategy_plumbing_marks_ffn_down(self):
        """quantize_model's 4h_to_h (down) sparse tensors are tile-uniform
        so serving models hit the fused down-gather."""
        from repro.configs import get_smoke_config
        from repro.core.compiler import quantize_model
        from repro.core.sparsity import SparseQuantizedTensor
        from repro.models import api
        cfg = get_smoke_config("qwen-7b", d_model=1024, d_ff=1024,
                               vocab_size=256)
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        q = quantize_model(params, "strategy3")
        mlp = q["blocks"]["mlp"]
        assert isinstance(mlp["down"], SparseQuantizedTensor)
        assert mlp["down"].tile_uniform
        assert isinstance(mlp["gate"], SparseQuantizedTensor)
        assert not mlp["gate"].tile_uniform


class TestServingQuantizedSparse:
    """Engine-vs-oracle decode with a sparse-strategy quantized model, and
    the compile-cache bound: the fused FFN must add no executables."""

    def test_engine_token_parity_and_bounded_compiles(self):
        from repro.configs import get_smoke_config
        from repro.core.compiler import CompileCache, quantize_model
        from repro.models import api
        from repro.serving.engine import Engine, Request, reference_decode

        cfg = get_smoke_config("qwen-7b", n_layers=1, d_model=1024,
                               d_ff=1024, vocab_size=256)
        params = quantize_model(api.init_params(cfg, jax.random.PRNGKey(0)),
                                "strategy3")
        rng = np.random.default_rng(7)
        engine = Engine(cfg, params, batch_size=2, max_len=32, chunk_size=8)
        reqs = [Request(rid=i,
                        prompt=rng.integers(0, 256,
                                            int(rng.integers(3, 12))
                                            ).astype(np.int32),
                        max_new_tokens=3) for i in range(3)]
        for r in reqs:
            engine.submit(r)
        done = engine.run()
        assert len(done) == 3
        assert engine.cache_compiles.misses <= engine.compile_budget
        oracle_cc = CompileCache()
        for r in done:
            want = reference_decode(cfg, params, r.prompt, r.max_new_tokens,
                                    max_len=32, compile_cache=oracle_cc)
            assert r.output == want, f"req {r.rid} diverged from oracle"
