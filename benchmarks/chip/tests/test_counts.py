"""The operation and byte counts behind step_mfu and step_roofline_share,
against hand counts at a small width and against the program's own packing."""
import jax
import pytest

import counts
import tiny
import weights

MODEL = dict(tiny.spec(1.0)["config"]["model"], n_layers=2, vocab_size=512)
SERVED = tiny.spec(1.0)["config"]["served"]


def test_projection_bytes_by_hand():
    # wq 256x256 dense int4: values + one bf16 scale per 128-row group
    assert counts.projection_bytes(256, 256, 1.0) == 256 * 256 // 2 + 2 * 256 * 2
    # wo 256x256 at density 0.5: m=2, one of two blocks kept per tile
    assert counts.projection_bytes(256, 256, 0.5) == 128 * 256 // 2 + 256 * 2 + 2 * 4
    # gate 256x512 at 0.25: no m gives a whole kept block, so dense int4
    assert counts.projection_bytes(256, 512, 0.25) == 256 * 512 // 2 + 2 * 512 * 2
    # down 512x256 at 0.5: m=4, two of four kept
    assert counts.projection_bytes(512, 256, 0.5) == 256 * 256 // 2 + 2 * 256 * 2 + 2 * 2 * 4


def test_tick_counts_by_hand():
    rows = [(10, 1), (0, 5), (3, 0)]
    per_token = 2 * 2 * (3 * 256 * 256 + 256 * 256 // 2 + 2 * 256 * 512
                         + 512 * 256 // 2)
    head = 2 * 256 * 512
    attn = 4 * 2 * 2 * 128 * (11 + 15)
    assert counts.tick_flops(MODEL, SERVED, rows) == 6 * per_token + 2 * head + attn
    layer = (3 * 33792 + 16904 + 2 * 67584 + 33808 + 2 * 256 * 2
             + 6 * 128 * 2)
    streamed = 2 * layer + 67584 + 256 * 2
    kv = 2 * 2 * 2 * 128 * 2
    assert counts.tick_bytes(MODEL, SERVED, rows) == (
        streamed + 10 * kv + 6 * kv + 6 * 256 * 2 + 2 * 512 * 2)
    assert counts.tick_bytes(MODEL, SERVED, [(5, 0)]) == 0


def test_weight_bytes_match_the_program_packing():
    from repro.core.compiler import quantize_model, quantized_bytes
    from repro.models import api

    spec = tiny.spec(1.0)
    import bench
    cfg = bench.program_config(spec["config"], spec["family"])
    shapes = jax.eval_shape(lambda k: api.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    packed = jax.eval_shape(lambda k: weights.build_served(
        k, shapes, spec["family"], spec["config"]["model"], SERVED,
        lambda t: quantize_model(t, SERVED["strategy"])),
        jax.random.PRNGKey(0))
    assert quantized_bytes(packed) == counts.weight_bytes(
        spec["config"]["model"], SERVED)


@pytest.mark.parametrize("n,q", [(0, 1), (100, 1), (7, 64)])
def test_attention_flops_count_every_key_once(n, q):
    keys = sum(n + j + 1 for j in range(q))
    assert counts.attention_flops(MODEL, n, q) == 4 * 2 * 2 * 128 * keys
