"""A whole run of the harness on the CPU at a small size, with the look for
a chip skipped: it is correct as the program stands, and it comes out not
correct when the timed path is broken underneath it."""
import jax
import numpy as np
import pytest

import bench
import tiny

PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
LIMIT = 0.05   # at this size sound runs read 0 to 0.002, the control 0.15 up


def run(kind="poisson", seed=7, trace=False):
    return bench.run_cell(tiny.spec(LIMIT, kind), seed, 1.5, trace,
                          jax.devices()[0], PEAKS, log=lambda *a, **k: None)


@pytest.mark.parametrize("kind", ["poisson", "closed"])
def test_sound_run_is_correct_and_reports_every_metric(kind):
    r = run(kind, seed=2 ** 33 + 5)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    if kind == "poisson":       # the window holds the arrivals it drew
        assert r["attempted"] == round(20.0 * 1.5)
    names = {"ttft_p95_s", "itl_p50_ms", "itl_p95_ms", "setup_s"}
    assert names <= set(r["metrics"])
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"


def test_traced_run_reports_the_per_layer_metrics():
    r = run(trace=True)
    assert r["correct"]
    assert {"queue_wait_p95_s", "slot_occupancy", "tick_ms.decode",
            "tick_ms.mixed", "step_mfu", "step_roofline_share"} <= set(
                r["metrics"])
    assert 0 < r["metrics"]["step_mfu"]["value"] < 100


def test_altered_token_is_not_correct(monkeypatch):
    from repro.serving import engine as eng

    emit = eng.Engine._emit

    def altered(self, idx, token, completed, first):
        slot = self._slots[idx]
        if len(slot.req.output) == 2:
            token = (token + 1) % self.cfg.vocab_size
        return emit(self, idx, token, completed, first)

    monkeypatch.setattr(eng.Engine, "_emit", altered)
    assert not run()["correct"]


def test_step_that_keeps_its_state_is_not_correct(monkeypatch):
    from repro.models import api

    for name in ("mixed_step", "decode_step"):
        step = getattr(api, name)

        def stale(cfg, params, cache, *a, _step=step, **k):
            logits, _ = _step(cfg, params, cache, *a, **k)
            return logits, jax.tree.map(lambda c: c + 0, cache)

        monkeypatch.setattr(api, name, stale)
    assert not run()["correct"]


def test_time_to_first_token_is_waited_for_past_the_close():
    reqs = [{"due": 1.0, "first": 2.0}, {"due": 9.0, "first": 14.0},
            {"due": 9.5, "first": None}, {"due": 10.0, "first": 10.5}]
    run = bench.Run(ws=0.0, we=10.0, te=20.0, requests=reqs)
    assert run.ttfts() == [1.0, 5.0, 10.5]
    assert run.ttfts_to_close() == [1.0, 1.0, 0.5]


def test_seeds_above_32_bits_change_the_weights():
    import weights
    a = weights.role_leaf(weights.base_key(5), "bias", (64,), {})
    b = weights.role_leaf(weights.base_key(5 + 2 ** 32), "bias", (64,), {})
    assert not np.array_equal(np.asarray(a), np.asarray(b))
