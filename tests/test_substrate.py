"""Substrate tests: data pipeline, checkpointing, fault tolerance,
instruction pipeline, op-graph, compile cache, model quantization."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core import compiler as cc
from repro.core import opgraph
from repro.core.pipeline import InstructionStream, PipelinedRunner
from repro.core.quant import QuantizedTensor
from repro.core.sparsity import SparseQuantizedTensor
from repro.data.pipeline import DataConfig, Prefetcher, SyntheticTokens
from repro.models import api
from repro.train import checkpoint as ckpt
from repro.train.fault import (PreemptionGuard, RestartPolicy,
                               StragglerWatchdog, run_resumable)


class TestDataPipeline:
    def test_deterministic_resume(self):
        gen = SyntheticTokens(DataConfig(vocab_size=100, seq_len=32,
                                         global_batch=4, seed=7))
        a = gen.batch(13)
        b = gen.batch(13)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        c = gen.batch(14)
        assert not np.array_equal(a["tokens"], c["tokens"])

    def test_labels_are_shifted_tokens(self):
        gen = SyntheticTokens(DataConfig(vocab_size=100, seq_len=32,
                                         global_batch=4))
        b = gen.batch(0)
        assert b["tokens"].shape == (4, 32)
        assert b["labels"].shape == (4, 32)

    def test_host_slicing_partitions(self):
        gen = SyntheticTokens(DataConfig(vocab_size=100, seq_len=16,
                                         global_batch=8))
        full = gen.batch(3)["tokens"]
        parts = [gen.host_slice(3, h, 4)["tokens"] for h in range(4)]
        np.testing.assert_array_equal(np.concatenate(parts), full)

    def test_prefetcher_orders_and_closes(self):
        gen = SyntheticTokens(DataConfig(vocab_size=50, seq_len=8,
                                         global_batch=2))
        pf = Prefetcher(gen.batch, start_step=5)
        steps = [next(pf)[0] for _ in range(4)]
        assert steps == [5, 6, 7, 8]
        pf.close()

    def test_motifs_make_data_learnable(self):
        """Repeated motifs => the stream has lower entropy than uniform."""
        gen = SyntheticTokens(DataConfig(vocab_size=1000, seq_len=256,
                                         global_batch=8, motif_prob=1.0))
        toks = gen.batch(0)["tokens"].ravel()
        _, counts = np.unique(toks, return_counts=True)
        p = counts / counts.sum()
        entropy = -(p * np.log(p)).sum()
        assert entropy < 0.9 * np.log(1000)


class TestCheckpoint:
    def _state(self):
        return {
            "params": {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
                       "b": jnp.ones((4,), jnp.bfloat16)},
            "step": jnp.int32(7),
        }

    def test_roundtrip(self, tmp_path):
        s = self._state()
        ckpt.save(str(tmp_path), 10, s, extra={"data_step": 10})
        like = jax.tree.map(lambda x: jnp.zeros_like(x), s)
        restored, extra = ckpt.restore(str(tmp_path), 10, like)
        assert extra == {"data_step": 10}
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)), s, restored)

    def test_quantized_leaves_roundtrip(self, tmp_path):
        from repro.core.quant import quantize
        from repro.core.sparsity import block_sparsify_quantize
        w = jnp.asarray(np.random.default_rng(0).normal(0, 1, (1024, 128)),
                        jnp.float32)
        s = {"q": quantize(w), "sq": block_sparsify_quantize(w, 0.5)}
        ckpt.save(str(tmp_path), 1, s)
        restored, _ = ckpt.restore(str(tmp_path), 1, s)
        assert isinstance(restored["q"], QuantizedTensor)
        assert isinstance(restored["sq"], SparseQuantizedTensor)
        np.testing.assert_array_equal(np.asarray(s["q"].packed),
                                      np.asarray(restored["q"].packed))
        assert restored["sq"].density == 0.5

    def test_atomic_latest_and_prune(self, tmp_path):
        s = self._state()
        for step in (1, 2, 3, 4):
            ckpt.save(str(tmp_path), step, s, keep=2)
        assert ckpt.latest_step(str(tmp_path)) == 4
        dirs = sorted(os.listdir(tmp_path))
        assert dirs == ["step_000000003", "step_000000004"]

    def test_elastic_restore_dtype_cast(self, tmp_path):
        """Restore casts to the target tree's dtypes (e.g. f32 master ->
        bf16 serving)."""
        s = {"w": jnp.ones((4, 4), jnp.float32)}
        ckpt.save(str(tmp_path), 1, s)
        like = {"w": jnp.zeros((4, 4), jnp.bfloat16)}
        restored, _ = ckpt.restore(str(tmp_path), 1, like)
        assert restored["w"].dtype == jnp.bfloat16


class TestFaultTolerance:
    def test_resume_replays_to_completion(self, tmp_path):
        calls = []

        def step_fn(state, step):
            calls.append(step)
            return {"x": state["x"] + 1}, {"loss": 0.0}

        init = lambda: {"x": jnp.float32(0)}
        state, last, done = run_resumable(
            ckpt_dir=str(tmp_path), total_steps=7, init_state=init,
            step_fn=step_fn, ckpt_every=3)
        assert done and last == 7 and float(state["x"]) == 7

        # crash-resume: wipe nothing; a rerun resumes from step 6 checkpoint
        calls.clear()
        state2, last2, done2 = run_resumable(
            ckpt_dir=str(tmp_path), total_steps=9, init_state=init,
            step_fn=step_fn, ckpt_every=3)
        assert done2 and last2 == 9
        assert calls[0] == 7  # resumed, not restarted

    def test_preemption_checkpoints_and_exits(self, tmp_path):
        guard = PreemptionGuard(signals=())
        seen = []

        def step_fn(state, step):
            seen.append(step)
            if step == 2:
                guard.request()
            return {"x": state["x"] + 1}, {}

        state, last, done = run_resumable(
            ckpt_dir=str(tmp_path), total_steps=100,
            init_state=lambda: {"x": jnp.float32(0)},
            step_fn=step_fn, ckpt_every=50, guard=guard)
        assert not done and last == 3
        assert ckpt.latest_step(str(tmp_path)) == 3

    def test_straggler_watchdog_escalates(self):
        wd = StragglerWatchdog(threshold=2.0, trip_limit=2, warmup_steps=2)
        hits = []
        for _ in range(5):
            wd.observe(1.0, on_escalate=lambda: hits.append(1))
        assert wd.incidents == 0
        wd.observe(5.0, on_escalate=lambda: hits.append(1))
        wd.observe(5.0, on_escalate=lambda: hits.append(1))
        assert wd.incidents == 2 and len(hits) == 1
        # recovery resets the consecutive counter
        wd.observe(1.0, on_escalate=lambda: hits.append(1))
        wd.observe(5.0, on_escalate=lambda: hits.append(1))
        assert len(hits) == 1

    def test_restart_policy_budget(self):
        rp = RestartPolicy(max_restarts=2, window_s=100, base_backoff_s=1)
        assert rp.record_failure(now=0.0) == 1
        assert rp.record_failure(now=1.0) == 2
        assert rp.record_failure(now=2.0) is None      # budget exhausted
        assert rp.record_failure(now=200.0) is not None  # window expired


class TestInstructionPipeline:
    def test_latency_hiding(self):
        """Host work overlaps device execution (paper Fig. 9)."""
        @jax.jit
        def device_step(x, args):
            # a deliberately slow device op
            y = x
            for _ in range(10):
                y = (y @ y) / jnp.linalg.norm(y)
            return y + args

        def host_work(k):
            time.sleep(0.01)
            return jnp.float32(k * 1e-6)

        x = jnp.eye(400) + 0.01
        device_step(x, jnp.float32(0)).block_until_ready()  # warm up

        serial = PipelinedRunner(device_step, host_work, pipelined=False)
        serial.run(x, 20)
        piped = PipelinedRunner(device_step, host_work, pipelined=True)
        piped.run(x, 20)
        # pipelined wall time must hide a meaningful part of host work
        assert piped.wall_time < serial.wall_time
        assert piped.host_time > 0.15  # host work actually happened

    def test_instruction_stream_double_buffer(self):
        stream = InstructionStream(lambda k: (lambda: k), depth=3)
        assert stream.prepared == 3
        assert stream.pop()() == 0
        assert stream.prepared == 4  # refilled


class TestOpGraph:
    def test_glm_block_is_17_steps(self):
        cfg = get_config("chatglm-6b")
        g = opgraph.block_graph(cfg)
        assert len(g) == 17
        assert [op.name.split(":")[0] for op in g][:2] == ["step1", "step2"]
        assert len(opgraph.epilogue_graph(cfg)) == 2

    def test_decode_weight_bytes_match_table2(self):
        """Dense GLM-6B block weight ~100.33 MB (paper Table II)."""
        cfg = get_config("chatglm-6b")
        g = opgraph.block_graph(cfg, tokens=1, context=128, wt_bits=4.125)
        wt = sum(op.weight_bytes for op in g if op.kind == "vmm")
        assert wt / 1e6 == pytest.approx(100.33, rel=0.12)

    def test_hbm_faster_than_ddr(self):
        cfg = get_config("chatglm-6b")
        g = opgraph.model_graph(cfg, tokens=1, context=128)
        t_hbm = opgraph.total_time_s(g, hbm_bw=460e9, ddr_bw=60e9)
        t_ddr = opgraph.total_time_s(g, hbm_bw=60e9, ddr_bw=60e9)
        # paper: decode on DDR ≈ 4x slower
        assert 2.5 < t_ddr / t_hbm < 6.0

    def test_layout_check(self):
        opgraph.check_layouts(get_config("chatglm-6b"))
        opgraph.check_layouts(get_config("qwen3-8b"))


class TestCompileCacheBuckets:
    def test_bucket_rounding(self):
        tb = cc.TokenBuckets(max_tokens=512, min_bucket=16)
        assert tb.bucket(1) == 16
        assert tb.bucket(17) == 32
        assert tb.bucket(512) == 512
        with pytest.raises(ValueError):
            tb.bucket(513)
        assert tb.all_buckets() == [16, 32, 64, 128, 256, 512]

    def test_cache_hit_miss(self):
        cache = cc.CompileCache()
        builds = []
        for n in (10, 20, 10):
            cache.get("f", cc.TokenBuckets(64).bucket(n),
                      lambda: builds.append(1) or len(builds))
        assert cache.misses == 2 and cache.hits == 1


class TestQuantizeModel:
    def test_quantizes_expected_leaves(self):
        cfg = get_smoke_config("qwen3-8b")
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        q = cc.quantize_model(params, "dense")
        blk = q["blocks"]
        assert isinstance(blk["attn"]["wq"], QuantizedTensor)
        assert isinstance(blk["mlp"]["down"], QuantizedTensor)
        assert isinstance(q["lm_head"], QuantizedTensor)
        # never-quantized leaves stay arrays
        assert not isinstance(q["embed"], QuantizedTensor)
        assert not isinstance(blk["ln_attn"]["gamma"], QuantizedTensor)

    def test_sparse_strategy_changes_types_and_bytes(self):
        # d_model 512 -> 4 contraction blocks, enough for k-of-4 sparsity
        cfg = get_smoke_config("chatglm-6b", d_model=512, d_ff=1024,
                               n_heads=2, n_kv_heads=1, head_dim=128)
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        dense = cc.quantize_model(params, "dense")
        s3 = cc.quantize_model(params, "strategy3")
        assert isinstance(s3["blocks"]["mlp"]["gate"], SparseQuantizedTensor)
        assert cc.quantized_bytes(s3) < cc.quantized_bytes(dense)

    def test_quantized_forward_close_to_dense(self):
        cfg = get_smoke_config("qwen1.5-4b")
        params = api.init_params(cfg, jax.random.PRNGKey(1))
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                    cfg.vocab_size)
        ref_logits, _ = api.forward(cfg, params, {"tokens": tokens})
        q = cc.quantize_model(params, "dense")
        q_logits, _ = api.forward(cfg, q, {"tokens": tokens})
        # int4 quantization error is bounded; correlation must stay high.
        # (Random-init weights are the worst case — no outlier structure for
        # the block scales to absorb; trained weights track much tighter.)
        a = np.asarray(ref_logits, np.float32).ravel()
        b = np.asarray(q_logits, np.float32).ravel()
        corr = np.corrcoef(a, b)[0, 1]
        assert corr > 0.9

    @pytest.mark.parametrize("arch", ["qwen-7b", "chatglm-6b"])
    def test_init_quantized_equals_quantize_model(self, arch):
        """Leaf by leaf on the host, packed layer by layer: the same tree
        as packing the whole jitted init at once."""
        cfg = get_smoke_config(arch, d_model=512, d_ff=1024, n_heads=4,
                               n_kv_heads=2, head_dim=128)
        init = lambda k: api.init_params(cfg, k)
        key = jax.random.PRNGKey(3)
        want = cc.quantize_model(jax.jit(init)(key), "strategy2")
        got = cc.init_quantized(init, key, "strategy2")
        assert jax.tree.structure(got) == jax.tree.structure(want)
        assert isinstance(got["blocks"]["mlp"]["gate"], SparseQuantizedTensor)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_init_quantized_packs_expert_stacks(self):
        """(L, E, d, f) expert leaves: lax.map over layers, vmap over
        experts.  Packing is per (d, f) matrix either way; a division fused
        differently may land a value on the other side of a rounding tie,
        so the nibbles are compared as near-equal (one tie in 10^5)."""
        cfg = get_smoke_config("mixtral-8x22b")
        init = lambda k: api.init_params(cfg, k)
        key = jax.random.PRNGKey(0)
        want = cc.quantize_model(jax.jit(init)(key), "strategy2")
        got = cc.init_quantized(init, key, "strategy2")
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.shape == b.shape and a.dtype == b.dtype
            assert np.mean(np.asarray(a) != np.asarray(b)) < 1e-4
