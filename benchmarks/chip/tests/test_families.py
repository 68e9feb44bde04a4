"""The model family a configuration names: the dense family reads as the
harness did before families (numbers recorded at that commit), and a new
family plugs in as one new file, found by its name."""
import hashlib
import json
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench
import check
import tiny
import weights

# Tick rows (resident tokens, tokens advanced): a whole prefill chunk, a
# decode batch, idle and partial rows, long contexts beside a chunk.
ROWS = [[(0, 64)], [(100, 1)] * 32, [(10, 1), (0, 5), (3, 0)],
        [(2000, 1), (37, 64), (511, 1)]]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
# Read with counts.py before the harness went through families:
# (weight_bytes, kv_bytes_per_token, per row set (tick_flops, tick_bytes,
# tick_floor_s)).
BEFORE = {
    "qwen-7b": (3280134144, 524288, [
        (427536941056, 2069857024, 0.0025272979536019534),
        (254124490752, 3739959296, 0.004566494866910867),
        (42365616128, 2044519936, 0.002496361338217338),
        (445872865280, 3407415552, 0.00416045854945055)]),
    "chatglm2-6b": (2279480576, 28672, [
        (400784621568, 1749293312, 0.0021358892698412697),
        (218177208320, 1843895552, 0.0022513987203907205),
        (38511443968, 1747571968, 0.0021337875067155066),
        (416567197696, 1822683392, 0.002225498647130647)]),
}
SEED = 2 ** 33 + 5
# sha256 over (path, bytes) of every leaf of the tiny cell's packed tree at
# SEED, before families
PACKED_BEFORE = \
    "326e001e0365901ab49600bf326b7dbaf8185e096f244a116658abf4452fa18a"
# check.gaps of the tiny cell at SEED on three seeded requests, before
# families: (program's widest gap, float8 control's, tokens compared)
GAPS_BEFORE = (6.474088191986084, 0.16411423683166504, 25)


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_dense_counts_read_as_before(name):
    config = json.loads((tiny.CHIP / "configs" / f"{name}.json").read_text())
    fam = bench.load_family(config)
    m, s = config["model"], config["served"]
    wb, kv, ticks = BEFORE[name]
    assert fam.weight_bytes(m, s) == wb
    assert fam.kv_bytes_per_token(m) == kv
    assert [(fam.tick_flops(m, s, r), fam.tick_bytes(m, s, r),
             fam.tick_floor_s(m, s, r, PEAKS)) for r in ROWS] == ticks


def _served_tree(spec, pack):
    from repro.models import api

    cfg = bench.program_config(spec["config"], spec["family"])
    shapes = jax.eval_shape(lambda k: api.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    return jax.jit(lambda k: weights.build_served(
        k, shapes, spec["family"], spec["config"]["model"],
        spec["config"]["served"], pack))(weights.base_key(SEED))


def test_dense_leaves_equal_layer_leaf():
    spec = tiny.spec(1.0)
    served = spec["config"]["served"]
    tree = _served_tree(spec, lambda t: t)
    key = weights.base_key(SEED)
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    assert {weights.path_str(p) for p, _ in flat} == set(weights.ROLES)
    for p, leaf in flat:
        path = weights.path_str(p)
        if path.startswith("blocks/"):
            want = [weights.layer_leaf(key, path, i, leaf.shape[1:], served)
                    for i in range(leaf.shape[0])]
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.stack(want), err_msg=path)
        else:
            np.testing.assert_array_equal(
                np.asarray(leaf),
                weights.layer_leaf(key, path, 0, leaf.shape, served),
                err_msg=path)


def test_dense_packed_tree_is_bit_for_bit_as_before():
    from repro.core.compiler import quantize_model

    spec = tiny.spec(1.0)
    strategy = spec["config"]["served"]["strategy"]
    tree = _served_tree(spec, lambda t: quantize_model(t, strategy))
    h = hashlib.sha256()
    for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(weights.path_str(p).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == PACKED_BEFORE


def test_dense_gaps_read_as_before():
    spec = tiny.spec(1.0)
    rng = np.random.default_rng(11)
    reqs = [{"prompt": rng.integers(0, 4096, n).tolist(),
             "out": rng.integers(0, 4096, k).tolist(), "prompt_len": n}
            for n, k in ((30, 8), (12, 5), (40, 12))]
    gap, low, n = check.gaps(spec["family"], weights.base_key(SEED),
                             spec["config"]["model"],
                             spec["config"]["served"], reqs, True)
    # float32 sums that another CPU's kernels may order otherwise; a wrong
    # reference or wrong weights read far off
    assert (gap, low) == pytest.approx(GAPS_BEFORE[:2], rel=1e-5)
    assert n == GAPS_BEFORE[2]


# -- a family that is not the dense one, in a file of its own ---------------

TOY = '''
"""A toy family: experts stacked (E, in, out) in one group of layers, a
second group of one leading layer, a reference whose best token at
position p is 7p mod V (7p + 1 in float8), and counts of 1000 operations a
token."""
import jax.numpy as jnp

import weights

ROLES = {"embed": "embed", "blocks/moe/up": "expert_up",
         "lead/mlp/up": "up"}


def _experts(key, shape, served):
    return weights.per_expert(key, shape[0], lambda k: weights.projection(
        k, shape[1], shape[2], served["density"]["expert_up"], False))


GENERATORS = {"expert_up": _experts}


def groups(model):
    return {"blocks": model["n_layers"], "lead": model["n_lead"]}


def program_sizes(cfg):
    return {"n_layers": cfg.n_layers}


def logits(key, model, served, seqs, positions, precision="f32"):
    shift = 1 if precision == "fp8" else 0
    return [_one_hot((7 * jnp.asarray(p) + shift) % model["vocab_size"],
                     model["vocab_size"]) for p in positions]


def _one_hot(tokens, n):
    return (tokens[:, None] == jnp.arange(n)[None, :]).astype(jnp.float32)


def tick_flops(model, served, rows):
    return 1000 * sum(q for _, q in rows)


def tick_bytes(model, served, rows):
    return 10 * sum(q for _, q in rows)


def tick_floor_s(model, served, rows, peaks):
    return max(tick_flops(model, served, rows) / peaks["bf16_flops"],
               tick_bytes(model, served, rows) / peaks["hbm_bytes_per_s"])


def weight_bytes(model, served):
    return 0


def kv_bytes_per_token(model):
    return 0
'''
TOY_MODEL = {"n_layers": 3, "n_lead": 1, "vocab_size": 16}
TOY_SERVED = {"density": {"expert_up": 1.0, "up": 0.5}}


@pytest.fixture(scope="module")
def toy_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("families")
    (d / "toy_moe.py").write_text(textwrap.dedent(TOY))
    (d / "partial.py").write_text("ROLES = {}\n")
    return d


@pytest.fixture(scope="module")
def toy(toy_dir):
    return bench.load_family({"name": "toy", "family": "toy_moe"}, toy_dir)


def test_loader_finds_a_family_by_its_file_name(toy, toy_dir):
    assert toy.__file__ == str(toy_dir / "toy_moe.py")
    assert toy.groups(TOY_MODEL) == {"blocks": 3, "lead": 1}


def test_build_served_makes_a_new_familys_leaves(toy):
    sds = jax.ShapeDtypeStruct
    shapes = {"embed": sds((16, 128), jnp.bfloat16),
              "blocks": {"moe": {"up": sds((3, 4, 128, 256), jnp.bfloat16)}},
              "lead": {"mlp": {"up": sds((1, 128, 256), jnp.bfloat16)}}}
    key = weights.base_key(SEED)
    out = jax.jit(lambda k: weights.build_served(
        k, shapes, toy, TOY_MODEL, TOY_SERVED, lambda t: t))(key)
    assert jax.tree.map(lambda a: a.shape, out) == jax.tree.map(
        lambda s: s.shape, shapes)
    up = np.asarray(out["blocks"]["moe"]["up"])
    for layer in range(3):
        lk = weights.leaf_key(key, "blocks/moe/up", layer)
        for e in range(4):
            np.testing.assert_array_equal(up[layer, e], weights.projection(
                jax.random.fold_in(lk, e), 128, 256, 1.0, False))
    assert not np.array_equal(up[0, 0], up[0, 1])
    np.testing.assert_array_equal(
        np.asarray(out["lead"]["mlp"]["up"][0]),
        weights.role_leaf(weights.leaf_key(key, "lead/mlp/up", 0), "up",
                          (128, 256), TOY_SERVED))


def test_check_gaps_runs_a_new_familys_reference(toy):
    # served tokens are the toy reference's best, so its gap is 0 and the
    # float8 control's (another token first everywhere) is 1
    reqs = [{"prompt": list(range(n)),
             "out": [(7 * (n - 1 + i)) % 16 for i in range(k)],
             "prompt_len": n} for n, k in ((5, 4), (9, 3))]
    assert check.gaps(toy, weights.base_key(SEED), TOY_MODEL, TOY_SERVED,
                      reqs, True) == (0.0, 1.0, 7)


def test_step_mfu_reads_a_new_familys_counts(toy):
    ticks = [(1.0, 1.5, False, [(0, 5), (3, 1)]),
             (2.0, 2.5, True, [(6, 64), (4, 0)])]
    run = bench.Run(ws=0.0, we=10.0, ticks=ticks, model=TOY_MODEL,
                    served=TOY_SERVED, family=toy,
                    peaks={"bf16_flops": 1e6, "hbm_bytes_per_s": 1e3})
    assert bench.reader("step_mfu")(run) == pytest.approx(
        1000 * 70 / (1.0 * 1e6) * 100)
    assert bench.reader("step_roofline_share")(run) == pytest.approx(
        (10 * 6 / 1e3 + 10 * 64 / 1e3) / 1.0 * 100)


@pytest.mark.parametrize("family,says", [
    (None, "'some-model' names no model family: give it \"family\", one of"),
    ("moe_v9", "which has no file families/moe_v9.py; known:"),
    ("../dense", "which has no file"),
])
def test_config_without_a_known_family_stops(family, says):
    config = {"name": "some-model"}
    if family is not None:
        config["family"] = family
    with pytest.raises(SystemExit) as e:
        bench.load_family(config)
    assert says in str(e.value) and "'dense'" in str(e.value), e.value


def test_family_file_lacking_a_part_stops(toy_dir):
    with pytest.raises(SystemExit) as e:
        bench.load_family({"name": "some-model", "family": "partial"},
                          toy_dir)
    assert "lacks ['GENERATORS', 'groups', 'program_sizes'" in str(e.value)
