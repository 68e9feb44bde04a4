"""The trace reduction: busy union, idle share, top device operations and
idle gaps attributed to host spans."""
import time

import jax
import jax.numpy as jnp
import pytest

import trace


def test_reduce_by_hand():
    ops = {"/device:TPU:0": [("a", 0, 10), ("b", 5, 20), ("a", 30, 40)]}
    spans = [("bench.tick", 0, 50), ("bench.wait", 20, 30)]
    r = trace.reduce(ops, spans)
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["idle_share"] == pytest.approx(0.4)
    assert r["device_ops"] == [["a", pytest.approx(20e-9)],
                               ["b", pytest.approx(15e-9)]]
    assert [g[0] for g in r["idle_gaps"]] == ["bench.wait", "bench.tick"]
    assert [g[1] for g in r["idle_gaps"]] == [pytest.approx(10e-9)] * 2


def test_reduce_averages_devices_and_clips_to_the_window():
    ops = {"d0": [("x", -5, 5)], "d1": [("x", 0, 10), ("y", 8, 30)]}
    r = trace.reduce(ops, [("bench.tick", 0, 20)])
    assert r["busy_s"] == pytest.approx((5e-9 + 20e-9) / 2)


def test_nothing_to_read_gives_nothing():
    assert trace.reduce({}, [("bench.tick", 0, 5)]) is None
    assert trace.reduce({"d": [("x", 0, 1)]}, []) is None


def test_load_finds_host_spans_in_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.tick"):
            f(x).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    ops, spans = trace.load(str(tmp_path))
    assert [s[0] for s in spans] == ["bench.tick"] * 3
    assert all(e > s for _, s, e in spans)
    assert ops == {}        # no TPU plane on the CPU: nothing to reduce
