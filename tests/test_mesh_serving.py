"""Sharded serving tests: block homes, the mesh dispatch gate, and
single-device parity for the sequence-sharded decode paths.

The expensive parity checks run in a subprocess with 8 host devices (the
main pytest process keeps 1 device — same pattern as test_distribution).
The sharded paths must agree with the single-device dispatch at the token
level (argmax — the psum merge may reorder float additions) and at the
POOL level bitwise (every pool row is written by exactly one shard, with
masked rows absorbed by the null row's home exactly like the single-device
write path).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.parallel import decode_attn
from repro.parallel.hints import use_mesh
from repro.serving.prefix import BlockAllocator


# ---------------------------------------------------------------- allocator

class TestBlockAllocatorHomes:
    def test_partition_geometry(self):
        # 39 blocks + null row = 40 rows, 4 homes of 10
        alloc = BlockAllocator(39, n_homes=4)
        assert alloc.rows_per_home == 10
        assert alloc.home(0) == 0 and alloc.home(9) == 0
        assert alloc.home(10) == 1 and alloc.home(38) == 3
        assert alloc.home(39) == 3, "null row must land in the last home"
        alloc.check()

    def test_indivisible_pool_rejected(self):
        with pytest.raises(ValueError):
            BlockAllocator(40, n_homes=4)   # 41 rows % 4 != 0

    def test_round_robin_lease_balances(self):
        alloc = BlockAllocator(39, n_homes=4)
        leased = [alloc.lease() for _ in range(36)]
        per_home = [0] * 4
        for blk in leased:
            per_home[alloc.home(blk)] += 1
        assert per_home == [9, 9, 9, 9]
        alloc.check()

    def test_targeted_lease_and_exhaustion(self):
        alloc = BlockAllocator(39, n_homes=4)
        got = [alloc.lease(home=2) for _ in range(10)]
        assert all(alloc.home(b) == 2 for b in got)
        # home 2 held rows 20..29; all ten leased, so it is now empty
        assert alloc.free_by_home()[2] == 0
        with pytest.raises(RuntimeError, match="home 2"):
            alloc.lease(home=2)
        # other homes still serve
        assert alloc.home(alloc.lease(home=0)) == 0
        for b in got:
            alloc.decref(b)
        alloc.check()

    def test_free_by_home_sums_to_free(self):
        alloc = BlockAllocator(39, n_homes=4)
        for _ in range(7):
            alloc.lease()
        assert sum(alloc.free_by_home()) == len(alloc.free)
        alloc.check()

    def test_single_home_matches_legacy(self):
        # n_homes=1 must behave exactly like the pre-home allocator: LIFO
        a = BlockAllocator(10)
        b = BlockAllocator(10, n_homes=1)
        sa = [a.lease() for _ in range(5)]
        sb = [b.lease() for _ in range(5)]
        assert sa == sb
        a.check(), b.check()


# ------------------------------------------------------- paged_homes / gate

class TestPagedHomes:
    def _mesh(self):
        return make_mesh((1, 1), ("data", "model"))

    def test_no_mesh_is_unsharded(self):
        assert decode_attn.paged_homes(None, 4, 40) == 1

    def test_window_disables_sharding(self):
        assert decode_attn.paged_homes(self._mesh(), 4, 40, window=16) == 1

    def test_agrees_with_usable(self):
        # the engine ctor and the dispatch gate derive from the same
        # function; on any mesh, homes > 1 implies usable(paged=True)
        mesh = self._mesh()
        lens = jnp.zeros((4,), jnp.int32)
        for rows in (40, 39, 8, 7):
            homes = decode_attn.paged_homes(mesh, 4, rows)
            if homes > 1:
                assert decode_attn.usable(mesh, 4, 8, 8, rows, lens,
                                          paged=True)

    def test_slot_usable_accepts_vector_lengths(self):
        # satellite regression: per-row (B,) lengths must not be rejected
        mesh = self._mesh()
        lens = jnp.asarray([3, 9, 17, 33], jnp.int32)
        assert decode_attn.usable(mesh, 4, 8, 8, 64, lens)
        assert decode_attn.usable(mesh, 4, 8, 8, 64, jnp.int32(5))


def test_paged_dispatch_reaches_mesh_gate(monkeypatch):
    """Regression for the dead ``paged=`` gate: a paged config decoded
    under a mesh must actually consult ``usable(..., paged=True)`` with the
    pool's row count — before PR 10 the dispatch returned early and the
    gate was unreachable."""
    seen = []
    real = decode_attn.usable

    def recorder(mesh, batch, hq, hkv, S, lengths, *, paged=False):
        seen.append({"paged": paged, "S": S, "mesh": mesh is not None})
        return real(mesh, batch, hq, hkv, S, lengths, paged=paged)

    monkeypatch.setattr(decode_attn, "usable", recorder)
    cfg = get_smoke_config("qwen-7b", d_model=64, d_ff=128, vocab_size=256,
                           kv_layout="paged", kv_block_size=8,
                           kv_pool_blocks=39)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    cache = api.init_cache(cfg, 2, 32)
    tok = jnp.zeros((2, 1), jnp.int32)
    with use_mesh(make_mesh((1, 1), ("data", "model"))):
        api.decode_step(cfg, params, cache, tok,
                        jnp.asarray([3, 5], jnp.int32))
    paged_calls = [c for c in seen if c["paged"]]
    assert paged_calls, "paged decode never consulted the sharded gate"
    assert all(c["mesh"] for c in paged_calls)
    # S must be the pool's ROW count (null block included)
    assert paged_calls[0]["S"] == 40


def test_paged_sharded_one_shard_matches_single_device():
    """A 1-shard mesh exercises the full shard_map paged path; its tokens
    must match the single-device dispatch at the argmax and its pools
    bitwise (including the null-row absorption of masked writes)."""
    rng = np.random.default_rng(0)
    cfg = get_smoke_config("qwen-7b", d_model=64, d_ff=128, vocab_size=256,
                           kv_layout="paged", kv_block_size=8,
                           kv_pool_blocks=39)
    B, max_len = 3, 32
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    cache = api.init_cache(cfg, B, max_len)
    perm = rng.permutation(39)
    n_pages = max_len // cfg.kv_block_size
    table = jnp.asarray(perm[:B * n_pages].reshape(B, n_pages)
                        .astype(np.int32))
    lengths = jnp.asarray([9, 17, 25], jnp.int32)
    wmask = jnp.asarray([True, True, False])
    tok = jnp.asarray(rng.integers(0, 256, (B, 1)), jnp.int32)

    l_ref, c_ref = api.decode_step(cfg, params, cache, tok, lengths,
                                   page_table=table, write_mask=wmask)
    with use_mesh(make_mesh((1, 1), ("data", "model"))):
        l_sh, c_sh = jax.jit(lambda p, c, t, l, pt, wm: api.decode_step(
            cfg, p, c, t, l, page_table=pt, write_mask=wm))(
            params, cache, tok, lengths, table, wmask)

    assert bool((jnp.argmax(l_ref, -1) == jnp.argmax(l_sh, -1)).all())
    np.testing.assert_allclose(np.asarray(l_sh, np.float32),
                               np.asarray(l_ref, np.float32),
                               rtol=2e-5, atol=2e-5)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(c_ref),
            jax.tree_util.tree_leaves(c_sh)):
        assert (np.asarray(a) == np.asarray(b)).all(), \
            f"pool mismatch at {jax.tree_util.keystr(path)}"


# ------------------------------------------- 8-device subprocess parity

_WORKER = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.models import api
    from repro.parallel.hints import use_mesh
    from repro.launch.mesh import make_mesh

    out = {}
    mesh = make_mesh((1, 8), ("data", "model"))
    rng = np.random.default_rng(0)

    def pool_ok(a, b):
        # leaf leading axis is the layer: layer-0 writes are projections of
        # identical inputs so they must be BITWISE equal; deeper layers see
        # the psum-merged attention output of the layer below, whose float
        # additions the mesh may reorder — those stay within rounding (one
        # int8 step for quantized pools)
        ok = True
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            x, y = np.asarray(x), np.asarray(y)
            ok &= bool((x[0] == y[0]).all())
            atol = 1.0 if x.dtype == np.int8 else 5e-4
            ok &= bool(np.allclose(x.astype(np.float32),
                                   y.astype(np.float32), atol=atol))
        return ok

    # --- slot layout, per-row lengths --------------------------------
    cfg_s = get_smoke_config("qwen-7b", d_model=64, d_ff=128,
                             vocab_size=256)
    ps = api.init_params(cfg_s, jax.random.PRNGKey(0))
    cache_s = api.init_cache(cfg_s, 4, 64)          # S=64, 8 per shard
    tok = jnp.asarray(rng.integers(0, 256, (4, 1)), jnp.int32)
    lens = jnp.asarray([5, 17, 33, 64], jnp.int32)
    l_ref, c_ref = api.decode_step(cfg_s, ps, cache_s, tok, lens)
    with use_mesh(mesh):
        l_sh, c_sh = jax.jit(lambda p, c, t, l: api.decode_step(
            cfg_s, p, c, t, l))(ps, cache_s, tok, lens)
    out["slot_argmax"] = bool((jnp.argmax(l_ref, -1)
                               == jnp.argmax(l_sh, -1)).all())
    out["slot_err"] = float(jnp.max(jnp.abs(l_ref - l_sh)))
    out["slot_cache_ok"] = pool_ok(c_ref, c_sh)

    # --- paged layout (fp + int8), scrambled tables ------------------
    for tag, quant in (("paged", "none"), ("paged_int8", "int8")):
        cfg_p = get_smoke_config("qwen-7b", d_model=64, d_ff=128,
                                 vocab_size=256, kv_layout="paged",
                                 kv_block_size=8, kv_pool_blocks=39,
                                 kv_quant=quant)
        pp = api.init_params(cfg_p, jax.random.PRNGKey(1))
        cache_p = api.init_cache(cfg_p, 4, 32)      # 40 rows, 5 per home
        n_pages = 4
        perm = rng.permutation(39)
        table = jnp.asarray(perm[:16].reshape(4, n_pages).astype(np.int32))
        plens = jnp.asarray([7, 15, 23, 31], jnp.int32)
        wm = jnp.asarray([True, False, True, True])
        ptok = jnp.asarray(rng.integers(0, 256, (4, 1)), jnp.int32)
        l_r, c_r = api.decode_step(cfg_p, pp, cache_p, ptok, plens,
                                   page_table=table, write_mask=wm)
        with use_mesh(mesh):
            l_s, c_s = jax.jit(lambda p, c, t, l, pt, w: api.decode_step(
                cfg_p, p, c, t, l, page_table=pt, write_mask=w))(
                pp, cache_p, ptok, plens, table, wm)
        out[tag + "_argmax"] = bool((jnp.argmax(l_r, -1)
                                     == jnp.argmax(l_s, -1)).all())
        out[tag + "_cache_ok"] = pool_ok(c_r, c_s)

    # --- fragmented page-table fuzz ----------------------------------
    cfg_f = get_smoke_config("qwen-7b", d_model=64, d_ff=128,
                             vocab_size=256, kv_layout="paged",
                             kv_block_size=8, kv_pool_blocks=39)
    pf = api.init_params(cfg_f, jax.random.PRNGKey(2))
    fuzz_ok = True
    step = jax.jit(lambda p, c, t, l, pt: api.decode_step(
        cfg_f, p, c, t, l, page_table=pt))
    for trial in range(5):
        cache_f = api.init_cache(cfg_f, 4, 32)
        perm = rng.permutation(39)[:16].reshape(4, 4).astype(np.int32)
        table = jnp.asarray(perm)
        flens = jnp.asarray(rng.integers(1, 33, (4,)), jnp.int32)
        ftok = jnp.asarray(rng.integers(0, 256, (4, 1)), jnp.int32)
        l_r, c_r = api.decode_step(cfg_f, pf, cache_f, ftok, flens,
                                   page_table=table)
        with use_mesh(mesh):
            l_s, c_s = step(pf, cache_f, ftok, flens, table)
        fuzz_ok &= bool((jnp.argmax(l_r, -1) == jnp.argmax(l_s, -1)).all())
        fuzz_ok &= pool_ok(c_r, c_s)
    out["fuzz_ok"] = bool(fuzz_ok)

    # --- engine-level: sharded engine == single-device engine --------
    from repro.serving.engine import Engine, Request
    cfg_e = get_smoke_config("qwen-7b", d_model=64, d_ff=128,
                             vocab_size=256, kv_layout="paged",
                             kv_block_size=8, kv_pool_blocks=39)
    pe = api.init_params(cfg_e, jax.random.PRNGKey(3))

    def run_engine(in_mesh):
        reqs = [Request(rid=i,
                        prompt=np.random.default_rng(100 + i).integers(
                            0, 256, 5 + 3 * i).astype(np.int32),
                        max_new_tokens=4 + i)
                for i in range(5)]
        eng = Engine(cfg_e, pe, batch_size=3, max_len=48, chunk_size=8,
                     audit_every=1)
        if in_mesh:
            assert eng.n_homes == 8, eng.n_homes
        else:
            assert eng.n_homes == 1
        for r in reqs:
            eng.submit(r)
        while not all(r.done for r in reqs):
            eng.run(max_steps=4)
            eng.audit()
            assert eng.steps < 500
        return [list(r.output) for r in reqs]

    ref_out = run_engine(False)
    with use_mesh(mesh):
        mesh_out = run_engine(True)
    out["engine_tokens_equal"] = ref_out == mesh_out

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


class TestMeshParity:
    def test_slot_per_row_lengths(self, worker_result):
        assert worker_result["slot_argmax"]
        assert worker_result["slot_err"] < 2e-4
        assert worker_result["slot_cache_ok"]

    def test_paged(self, worker_result):
        assert worker_result["paged_argmax"]
        assert worker_result["paged_cache_ok"]

    def test_paged_int8(self, worker_result):
        assert worker_result["paged_int8_argmax"]
        assert worker_result["paged_int8_cache_ok"]

    def test_fragmented_table_fuzz(self, worker_result):
        assert worker_result["fuzz_ok"]

    def test_engine_token_streams_bitwise_equal(self, worker_result):
        assert worker_result["engine_tokens_equal"]
