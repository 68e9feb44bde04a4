"""The reduction by the program's own instrumentation (``phases.py``) and
the readers of its metrics, on hand-built traces and runs."""
import glob
import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import phases
import trace
from bench import HERE, Run

DEV = "/device:TPU:0"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def data(scoped, engine=(), modules=(), bench=(("bench.tick", 0, 200),)):
    return {"bench": list(bench), "engine": list(engine),
            "ops": {DEV: [op[:3] for op in scoped]},
            "scoped": {DEV: list(scoped)}, "modules": {DEV: list(modules)}}


# a layer scan (while) whose body is attention then ffn, then the lm_head
# and an unscoped copy; the device idles in 130-150 and 190-200
NESTED = [("while.1", 0, 100, None), ("fusion.1", 0, 40, "attention"),
          ("fusion.2", 40, 100, "ffn"), ("copy.1", 100, 110, None),
          ("dot.1", 110, 130, "lm_head"), ("copy.2", 150, 190, None)]


def test_scope_shares_count_leaves_only_and_sum_to_one():
    assert [o[0] for o in phases.leaves(NESTED)] == [
        "fusion.1", "fusion.2", "copy.1", "dot.1", "copy.2"]
    p = phases.reduce(data(NESTED))
    assert p["busy_s"] == pytest.approx(170e-9)
    assert p["scope_s"] == {"attention": pytest.approx(40e-9),
                            "ffn": pytest.approx(60e-9),
                            None: pytest.approx(50e-9),
                            "lm_head": pytest.approx(20e-9)}
    run = Run(phases=p)
    shares = {s: phases.share(run, s)
              for s in ("attention", "ffn", "lm_head", None)}
    assert sum(shares.values()) == pytest.approx(100.0)
    assert shares["ffn"] == pytest.approx(60 / 170 * 100)
    for name, scope in (("attention", "attention"), ("ffn", "ffn"),
                        ("lm_head", "lm_head"), ("unscoped", None)):
        assert reader(f"device_share.{name}")(run) == shares[scope]


def test_scope_is_the_innermost_named_part_of_the_op_path():
    path = "jit(mixed_step)/while/body/closed_call/attention/dot_general"
    assert phases.scope_of(path) == "attention"
    assert phases.scope_of("jit(mixed_step)/lm_head/jit(clip)/max") == \
        "lm_head"
    assert phases.scope_of("jit(mixed_step)/while/body/add") is None


def test_op_paths_come_from_the_hlo_the_trace_keeps(tmp_path):
    def mixed_step(x):
        with jax.named_scope("attention"):
            y = jnp.sin(x) @ x
        with jax.named_scope("ffn"):
            return jnp.tanh(y) @ y

    f = jax.jit(mixed_step)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with open(path, "rb") as fh:
        paths = phases.op_paths(fh.read())
    (program,) = [k for k in paths if k.startswith("jit_mixed_step(")]
    scopes = {phases.scope_of(p) for p in paths[program].values()}
    assert {"attention", "ffn"} <= scopes
    dots = {phases.scope_of(p): k for k, p in paths[program].items()
            if p.endswith("/dot_general")}
    assert set(dots) == {"attention", "ffn"}
    # an op takes the scope of its instruction in the program whose run
    # covers it; outside every run it has none
    runs = [(program, 0, 100), ("jit_other(1)", 100, 200)]
    ops = [("%" + dots["ffn"], 10, 20), ("%" + dots["attention"], 120, 130),
           ("%" + dots["attention"], 300, 310)]
    assert [o[3] for o in phases._scopes(ops, runs, paths)] == [
        "ffn", None, None]


def test_idle_gap_under_fetch_logits_names_it_and_the_window_stays():
    ops = [("fusion.1", 0, 100), ("fusion.2", 130, 200)]
    bench = [("bench.tick", 0, 200)]
    engine = [("engine.tick", 5, 195, {"kind": "decode", "w": 1}),
              ("engine.sync", 20, 100, {}),
              ("engine.fetch_logits", 100, 128, {}),
              ("engine.consume", 128, 190, {})]
    p = phases.reduce(data([o + ("ffn",) for o in ops], engine,
                           bench=bench))
    assert p["idle_gaps"] == [["engine.fetch_logits", pytest.approx(30e-9)]]
    # trace.py's reduction of the same trace reads exactly as before
    before = trace.reduce({DEV: ops}, bench)
    assert before["idle_gaps"] == [["bench.tick", pytest.approx(30e-9)]]
    assert before["window_s"] == pytest.approx(200e-9)
    assert before["idle_share"] == pytest.approx(0.15)
    assert p["busy_s"] == pytest.approx(before["busy_s"])


def test_tick_and_step_readers():
    engine = [
        ("engine.tick", 0, 100, {"kind": "mixed", "w": 64, "live": 2}),
        ("engine.admit", 0, 5, {}), ("engine.sync", 10, 90, {}),
        ("engine.tick", 100, 160, {"kind": "decode", "w": 1, "live": 2}),
        ("engine.sync", 110, 150, {}),
        ("engine.tick", 160, 170, {}),          # found nothing to run
        ("engine.tick", 190, 260, {"kind": "decode"}),  # past the window
    ]
    modules = [("jit_mixed_step", 10, 80), ("jit_decode_step", 110, 140),
               ("jit_decode_step", 150, 180), ("jit_decode_step", 190, 250)]
    p = phases.reduce(data(NESTED, engine, modules))
    assert [t["kind"] for t in p["ticks"]] == ["mixed", "decode"]
    assert p["ticks"][0]["phases"] == {
        "engine.admit": pytest.approx(5e-9),
        "engine.sync": pytest.approx(80e-9)}
    run = Run(phases=p)
    # host time: (100 - 80) and (60 - 40) ns
    assert reader("tick_host_ms")(run) == pytest.approx(20e-6)
    assert reader("step_device_ms.mixed")(run) == pytest.approx(70e-6)
    assert reader("step_device_ms.decode")(run) == pytest.approx(30e-6)


def test_padding_and_admission_readers():
    run = Run(positions=(29, 68))
    assert reader("padding_share")(run) == pytest.approx((1 - 29 / 68) * 100)
    reqs = [{"due": 1.0, "admitted_at": 1.5},
            {"due": 2.0, "admitted_at": None},      # waiting at the close
            {"due": 9.0, "admitted_at": 12.0}]      # admitted past it
    run = Run(ws=0.0, we=10.0, requests=reqs)
    w = [0.5, 8.0, 1.0]
    assert reader("admit_wait_p95_s")(run) == pytest.approx(
        float(np.percentile(w, 95)))


NAMES = ("device_share.attention", "device_share.ffn",
         "device_share.lm_head", "device_share.unscoped",
         "step_device_ms.mixed", "step_device_ms.decode", "tick_host_ms",
         "padding_share", "admit_wait_p95_s")


@pytest.mark.parametrize("name", NAMES)
def test_readers_find_nothing_in_a_run_without_the_instrumentation(name):
    # the harness's Run as it stands carries neither phases nor positions,
    # and a program without named scopes, modules or spans yields none
    bare = Run(ws=0.0, we=10.0, requests=[{"due": 1.0, "admitted": 2.0}])
    assert reader(name)(bare) is None
    unscoped = phases.reduce(data([op[:3] + (None,) for op in NESTED]))
    assert unscoped["scope_s"] is None
    assert unscoped["step_ms"] == {} and unscoped["ticks"] == []
    p = Run(ws=0.0, we=10.0, requests=[{"due": 1.0, "admitted": 2.0}],
            phases=unscoped, positions=None)
    assert reader(name)(p) is None


@pytest.mark.filterwarnings("ignore:Engine.run hit max_steps")
def test_load_finds_engine_spans_with_their_arguments(tmp_path):
    from repro.configs import get_smoke_config
    from repro.models import api
    from repro.serving.engine import Engine, Request

    cfg = get_smoke_config("qwen-7b", d_model=64, d_ff=128, vocab_size=256)
    eng = Engine(cfg, api.init_params(cfg, jax.random.PRNGKey(0)),
                 batch_size=2, max_len=32, chunk_size=16)
    req = Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                  max_new_tokens=2)
    eng.submit(req)
    eng.run(max_steps=1)            # compiles the mixed step
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.tick"):
        eng.run()
    jax.profiler.stop_trace()
    d = phases.load(str(tmp_path))
    assert [s[0] for s in d["bench"]] == ["bench.tick"]
    ticks = [s for s in d["engine"] if s[0] == "engine.tick"]
    assert ticks[0][3] == {"kind": "decode", "w": 1, "live": 1,
                           "live_positions": 1}
    (lo, hi) = d["bench"][0][1:]
    assert all(lo <= s <= e <= hi for _, s, e, _ in d["engine"])
    assert d["ops"] == {}           # no TPU plane on the CPU
