"""Distribution tests — run in a subprocess with 8 host devices (the main
pytest process must keep 1 device for everything else).

Covers: shard_map MoE == local MoE numerics, sharding-spec legality,
trainer grad-accum equivalence, mesh construction, hint no-op behaviour.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from jax.sharding import AxisType, PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.parallel import sharding as shd
from repro.parallel.hints import active_mesh, hint, use_mesh

_WORKER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, dataclasses
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.models import api, moe
    from repro.parallel import sharding as shd
    from repro.parallel.hints import use_mesh
    from repro.launch.mesh import make_mesh
    from repro.optim.adamw import AdamW
    from repro.train import trainer

    out = {}
    mesh = make_mesh((4, 2), ("data", "model"))

    # --- shard_map MoE vs local MoE numerics -----------------------------
    cfg = get_smoke_config("mixtral-8x22b", d_ff=64, dtype=jnp.float32)
    p = moe.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, cfg.d_model), jnp.float32)
    local_out, local_aux = moe._moe_apply_local(cfg, p, x)
    with use_mesh(mesh):
        sm_out, sm_aux = jax.jit(lambda p, x: moe.moe_apply(cfg, p, x))(p, x)
    out["moe_max_err"] = float(jnp.max(jnp.abs(local_out - sm_out)))
    out["moe_aux_err"] = float(jnp.abs(local_aux - sm_aux))

    # --- train step under mesh == train step without mesh ----------------
    cfg2 = get_smoke_config("qwen3-8b")
    opt = AdamW(lr=1e-3, grad_clip=None, weight_decay=0.0)
    params, opt_state = trainer.init_train_state(cfg2, opt, jax.random.PRNGKey(0))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(3), (8, 16), 0, cfg2.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(4), (8, 16), 0, cfg2.vocab_size),
    }
    rng = jax.random.PRNGKey(5)

    params_shape = jax.eval_shape(lambda: params)
    specs = shd.param_specs(params_shape, mesh, "train")
    step_plain = trainer.make_train_step(cfg2, opt, accum_steps=2)
    step_mesh = trainer.make_train_step(cfg2, opt, accum_steps=2, grad_specs=specs)

    p1, _, m1 = jax.jit(step_plain)(params, opt_state, batch, rng)
    with use_mesh(mesh):
        p_sh = shd.shardings_for(params_shape, mesh, "train")
        o_sh = shd.shardings_for(jax.eval_shape(lambda: opt_state), mesh, "train")
        p2, _, m2 = jax.jit(step_mesh, in_shardings=(p_sh, o_sh, None, None))(
            params, opt_state, batch, rng)
    diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
    out["train_max_param_diff"] = max(jax.tree.leaves(diffs))
    out["loss_plain"] = float(m1["loss"]); out["loss_mesh"] = float(m2["loss"])

    # --- decode step compiles + runs under serve shardings ---------------
    cfg3 = get_smoke_config("gemma-2b")
    sp = api.init_params(cfg3, jax.random.PRNGKey(7))
    cache = api.init_cache(cfg3, 8, 64)
    with use_mesh(mesh):
        c_sh = shd.kv_cache_specs(jax.eval_shape(lambda: cache), mesh, 8)
        logits, new_cache = jax.jit(
            lambda p, c, t, l: api.decode_step(cfg3, p, c, t, l),
        )(sp, cache, jnp.zeros((8, 1), jnp.int32), jnp.int32(5))
    out["decode_ok"] = bool(np.isfinite(np.asarray(logits, np.float32)).all())

    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def worker_result():
    proc = subprocess.run(
        [sys.executable, "-c", _WORKER], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=600)
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(
        f"worker failed:\nstdout={proc.stdout[-2000:]}\nstderr={proc.stderr[-3000:]}")


class TestShardMapMoE:
    def test_matches_local_path(self, worker_result):
        assert worker_result["moe_max_err"] < 2e-4
        assert worker_result["moe_aux_err"] < 1e-5


class TestDistributedTrainStep:
    def test_sharded_equals_unsharded(self, worker_result):
        assert worker_result["loss_plain"] == pytest.approx(
            worker_result["loss_mesh"], rel=1e-4)
        assert worker_result["train_max_param_diff"] < 5e-3

    def test_decode_under_mesh(self, worker_result):
        assert worker_result["decode_ok"]


class TestShardingRules:
    def _mesh(self):
        # single-device "mesh" is enough to compute specs
        return make_mesh((1, 1), ("data", "model"))

    def test_col_row_parallel_specs(self):
        mesh = self._mesh()
        tree = {
            "attn": {"wq": jax.ShapeDtypeStruct((256, 512), np.float32),
                     "wo": jax.ShapeDtypeStruct((512, 256), np.float32)},
            "ln": {"gamma": jax.ShapeDtypeStruct((256,), np.float32)},
        }
        specs = shd.param_specs(tree, mesh, "train")
        assert specs["attn"]["wq"] == P(("data",), "model")
        assert specs["attn"]["wo"] == P("model", ("data",))
        assert specs["ln"]["gamma"] == P()

    def test_moe_expert_specs_match_shard_map(self):
        mesh = self._mesh()
        tree = {"moe": {
            "gate": jax.ShapeDtypeStruct((4, 8, 256, 512), np.float32),
            "down": jax.ShapeDtypeStruct((4, 8, 512, 256), np.float32),
            "router": jax.ShapeDtypeStruct((256, 8), np.float32),
        }}
        specs = shd.param_specs(tree, mesh, "train")
        assert specs["moe"]["gate"] == P(None, None, None, ("data", "model"))
        assert specs["moe"]["down"] == P(None, None, ("data", "model"))
        assert specs["moe"]["router"] == P()

    def test_serve_mode_no_fsdp(self):
        mesh = self._mesh()
        tree = {"mlp": {"up": jax.ShapeDtypeStruct((256, 512), np.float32)}}
        specs = shd.param_specs(tree, mesh, "serve")
        assert specs["mlp"]["up"] == P(None, "model")

    def test_indivisible_dims_drop_sharding(self):
        mesh = make_mesh((1,), ("model",))
        tree = {"attn": {"wq": jax.ShapeDtypeStruct((100, 7), np.float32)}}
        specs = shd.param_specs(tree, mesh, "serve")
        assert specs["attn"]["wq"] == P(None, "model")  # 7 % 1 == 0 fine
        mesh2 = make_mesh((1, 1), ("data", "model"))
        # legalization keeps only divisible axes
        t2 = {"attn": {"wq": jax.ShapeDtypeStruct((3, 5), np.float32)}}
        s2 = shd.param_specs(t2, mesh2, "train")
        assert s2["attn"]["wq"] == P(("data",), "model")  # 1-sized axes divide

    def test_quantized_leaf_specs(self):
        from repro.core.quant import quantize
        mesh = self._mesh()
        import jax.numpy as jnp
        qt = quantize(jnp.ones((256, 512), jnp.float32))
        tree = {"mlp": {"up": qt}}
        specs = shd.param_specs(tree, mesh, "serve")
        assert specs["mlp"]["up"].packed == P(None, "model")
        assert specs["mlp"]["up"].scales == P(None, "model")


class TestHints:
    def test_noop_without_mesh(self):
        import jax.numpy as jnp
        x = jnp.ones((4, 8))
        assert active_mesh() is None
        y = hint(x, "batch", "heads")
        assert y is x  # exact object: no constraint emitted


class TestMeshConstruction:
    def test_make_host_mesh(self):
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh()
        assert set(mesh.axis_names) == {"data", "model"}
        assert all(t == AxisType.Auto for t in mesh.axis_types)

    @pytest.mark.parametrize("shape,axes", [
        ((1,), ("model",)), ((1, 1), ("data", "model")),
        ((1, 1, 1), ("pod", "data", "model"))])
    def test_make_mesh_axes_are_auto(self, shape, axes):
        mesh = make_mesh(shape, axes)
        assert mesh.axis_names == axes
        assert mesh.axis_types == (AxisType.Auto,) * len(axes)

    def test_hint_constrains_under_helper_mesh(self):
        """with_sharding_constraint inside jit is refused on Explicit axes
        (jax.make_mesh's default in jax 0.9); the helper's mesh takes it."""
        import jax.numpy as jnp
        x = jnp.ones((4, 8))
        with use_mesh(make_mesh((1, 1), ("data", "model"))):
            y = jax.jit(lambda a: hint(a * 2, "batch", "heads"))(x)
        np.testing.assert_array_equal(np.asarray(y), 2 * np.ones((4, 8)))


class TestInt8KVCache:
    """int8 KV quantization (beyond-paper): decode matches the bf16 cache
    to int8-rounding tolerance, on both the fallback and sharded paths."""

    def test_fallback_path_close_to_fp(self):
        import jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import api
        cfg_fp = get_smoke_config("qwen3-8b")
        cfg_q = get_smoke_config("qwen3-8b", kv_quant="int8")
        params = api.init_params(cfg_fp, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                    cfg_fp.vocab_size)
        l_fp, c_fp = api.prefill(cfg_fp, params, {"tokens": tokens}, 16)
        l_q, c_q = api.prefill(cfg_q, params, {"tokens": tokens}, 16)
        assert c_q["k"].dtype == np.int8
        np.testing.assert_allclose(np.asarray(l_q, np.float32),
                                   np.asarray(l_fp, np.float32),
                                   rtol=0.05, atol=0.1)
        nt = np.argmax(np.asarray(l_fp), -1).reshape(2, 1).astype(np.int32)
        import jax.numpy as jnp2
        d_fp, _ = api.decode_step(cfg_fp, params, c_fp, jnp2.asarray(nt),
                                  jnp2.int32(9))
        d_q, _ = api.decode_step(cfg_q, params, c_q, jnp2.asarray(nt),
                                 jnp2.int32(9))
        np.testing.assert_allclose(np.asarray(d_q, np.float32),
                                   np.asarray(d_fp, np.float32),
                                   rtol=0.05, atol=0.15)

    def test_sharded_path_matches_fallback(self):
        """Run inside the 8-device worker: sharded int8 decode == the
        unsharded int8 reference."""
        import subprocess, sys, os, json, textwrap
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import sys, json
            sys.path.insert(0, "src")
            import jax, jax.numpy as jnp, numpy as np
            from repro.configs import get_smoke_config
            from repro.models import api
            from repro.parallel.hints import use_mesh
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 2), ("data", "model"))
            cfg = get_smoke_config("qwen3-8b", kv_quant="int8")
            params = api.init_params(cfg, jax.random.PRNGKey(0))
            cache = api.init_cache(cfg, 8, 64)
            tok = jnp.zeros((8, 1), jnp.int32)
            ref, _ = api.decode_step(cfg, params, cache, tok, jnp.int32(5))
            with use_mesh(mesh):
                got, nc = jax.jit(lambda p, c, t, l: api.decode_step(
                    cfg, p, c, t, l))(params, cache, tok, jnp.int32(5))
            err = float(jnp.max(jnp.abs(ref - got)))
            print("RESULT " + json.dumps({"err": err,
                                          "int8": str(nc["k"].dtype)}))
        """)
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            timeout=600)
        for line in proc.stdout.splitlines():
            if line.startswith("RESULT "):
                r = json.loads(line[len("RESULT "):])
                assert r["int8"] == "int8"
                assert r["err"] < 2e-2
                return
        raise AssertionError(proc.stderr[-2000:])
