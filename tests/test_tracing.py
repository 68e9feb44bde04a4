"""The serving engine's own spans and counters: ``engine.*`` host spans on
the profiler's clock, named executables and named scopes in the step, the
position counters behind the padding share, and the admission time."""

import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.models import api
from repro.serving.engine import SPANS, Engine, Request


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen-7b", d_model=64, d_ff=128, vocab_size=256,
                           kv_layout="paged", kv_block_size=8,
                           kv_pool_blocks=24)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


class FakeClock:
    """Injectable engine clock: time moves only when the test says so."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _two_rows(cfg, engine, sample=None):
    """Two rows of a 2-row engine with one 16-wide chunk bucket: a 5-token
    and a 20-token prompt, three new tokens each."""
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, max_new_tokens=3,
                    prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32))
            for i, n in enumerate((5, 20))]
    for r in reqs:
        engine.submit(r)
    assert engine.run(sample=sample).drained
    return reqs


def _engine_spans(logdir):
    """(name, start_ns, end_ns, args) of every ``engine.*`` span in the
    newest trace under ``logdir``, by start."""
    path = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats))
                       for e in line.events if e.name.startswith("engine."))
    return sorted(out, key=lambda s: s[1])


def test_position_counters_equal_a_hand_count(setup):
    cfg, params = setup
    eng = Engine(cfg, params, batch_size=2, max_len=64, chunk_size=16)
    assert eng.chunk_buckets.all_buckets() == [16]
    _two_rows(cfg, eng)
    # tick 1, mixed: chunks 5 and 16 (row 0 emits its first token)
    # tick 2, mixed: row 0 decodes, row 1's last chunk of 4
    # tick 3, decode: both rows;  tick 4, decode: row 1 alone
    assert eng.steps == 4 and eng.mixed_ticks == 2
    assert eng.positions_computed == 2 * 16 + 2 * 16 + 2 + 2
    assert eng.positions_live == (5 + 16) + (1 + 4) + 2 + 1


def _argmax(row):
    return int(np.argmax(row))


@pytest.mark.parametrize("sample", [None, _argmax], ids=["greedy", "hook"])
def test_engine_spans_nest_in_order_on_the_profiler_clock(setup, tmp_path,
                                                          sample):
    cfg, params = setup
    plain = Engine(cfg, params, batch_size=2, max_len=64, chunk_size=16)
    want = [r.output for r in _two_rows(cfg, plain)]
    eng = Engine(cfg, params, batch_size=2, max_len=64, chunk_size=16,
                 compile_cache=plain.cache_compiles)
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = [r.output for r in _two_rows(cfg, eng, sample)]
    finally:
        jax.profiler.stop_trace()
    assert got == want              # an active profiler changes no token

    spans = _engine_spans(str(tmp_path))
    assert {s[0] for s in spans} <= set(SPANS)
    ticks = [s for s in spans if s[0] == "engine.tick"]
    # four dispatching passes, then the pass that finds nothing to run
    assert len(ticks) == 5
    args = [(t[3].get("kind"), t[3].get("w"), t[3].get("live"),
             t[3].get("live_positions")) for t in ticks]
    assert args == [("mixed", 16, 2, 21), ("mixed", 16, 2, 5),
                    ("decode", 1, 2, 2), ("decode", 1, 1, 1),
                    (None, None, None, None)]
    for i, (_, lo, hi, _) in enumerate(ticks):
        kids = [s[0] for s in spans
                if s[0] != "engine.tick" and lo <= s[1] and s[2] <= hi]
        if i == len(ticks) - 1:
            assert kids == ["engine.admit"]
            continue
        # no journal is attached; the logits reach the host (under
        # engine.fetch_logits) only for a sample hook: check_finite, on by
        # default, reads the step's per-row flag with the tokens
        copy = ["engine.fetch_logits"] if sample is not None else []
        assert kids == ["engine.admit", "engine.plan", "engine.prepare",
                        "engine.dispatch", "engine.sync", *copy,
                        "engine.consume"]
        assert kids == [n for n in SPANS if n in kids]


def _step_args(eng, w):
    c = eng.cache
    if w == 1:
        return (eng.params, c, jnp.zeros((eng.batch, 1), jnp.int32),
                jnp.ones(eng.batch, jnp.int32),
                jnp.asarray(eng._page_table), jnp.ones(eng.batch, bool))
    return (eng.params, c, jnp.zeros((eng.batch, w), jnp.int32),
            jnp.zeros(eng.batch, jnp.int32), jnp.ones(eng.batch, jnp.int32),
            jnp.asarray(eng._page_table))


def _ops(compiled_text: str) -> list[str]:
    """The compiled module's computations and instructions without their
    metadata (op names, source lines) or the stack-frame tables, each
    ``%name`` renumbered by first use (the converter names instructions
    from their source locations)."""
    names: dict[str, str] = {}

    def rename(m):
        return names.setdefault(m.group(0), f"%v{len(names)}")

    return [re.sub(r"%[\w.\-]+", rename,
                   re.sub(r",? ?metadata=\{[^}]*\}", "", line))
            for line in compiled_text.splitlines()
            if line.startswith(("  ", "%", "ENTRY", "}"))]


@pytest.mark.parametrize("kind", ["mixed", "decode"])
def test_steps_are_named_and_scoped_and_scopes_are_metadata_only(
        setup, kind, monkeypatch):
    cfg, params = setup
    eng = Engine(cfg, params, batch_size=2, max_len=64, chunk_size=16)
    build = eng._build_mixed if kind == "mixed" else eng._build_decode
    args = _step_args(eng, 16 if kind == "mixed" else 1)
    lowered = build().lower(*args)
    text = lowered.as_text(debug_info=True)
    assert f"module @jit_{kind}_step" in text
    names = set(re.findall(r'loc\("([^"]*)"', text))
    for scope in ("embed", "attention", "ffn", "lm_head"):
        assert any(scope in n.split("/") for n in names), scope
    compiled = lowered.compile().as_text()
    assert f'op_name="jit({kind}_step)/' in compiled
    assert "/attention/" in compiled and "/ffn/" in compiled

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = build().lower(*args)
    assert bare.as_text() == lowered.as_text()
    assert _ops(bare.compile().as_text()) == _ops(compiled)


@pytest.mark.filterwarnings("ignore:Engine.run hit max_steps")
def test_admitted_at_is_set_once_and_survives_preemption_and_restore(
        setup, tmp_path):
    cfg, params = setup
    clock = FakeClock(10.0)
    wd = str(tmp_path / "snaps")
    eng = Engine(cfg, params, batch_size=1, max_len=64, chunk_size=16,
                 max_preemptions=1, clock=clock, snapshot_dir=wd)
    rng = np.random.default_rng(1)
    low = Request(rid=0, max_new_tokens=12,
                  prompt=rng.integers(0, 256, 6).astype(np.int32))
    eng.submit(low)
    assert low.admitted_at is None
    clock.t = 12.0
    eng.run(max_steps=3)
    assert low.admitted_at == 12.0
    high = Request(rid=1, max_new_tokens=2, priority=1,
                   prompt=rng.integers(0, 256, 6).astype(np.int32))
    clock.t = 14.0
    eng.submit(high)
    clock.t = 15.0
    eng.run(max_steps=1)
    assert low.preemptions == 1 and high.admitted_at == 15.0
    clock.t = 20.0
    eng.run(max_steps=3)            # high finishes, low comes back
    assert eng._slots[0].req is low
    assert low.admitted_at == 12.0  # the first admission, not the second

    eng.snapshot()
    restored = Engine.restore(wd, params, clock=FakeClock(500.0),
                              compile_cache=eng.cache_compiles)
    (req,) = [r for r in [s.req for s in restored._slots] +
              list(restored._queue) if r is not None]
    assert req.admitted_at - req.submitted_at == pytest.approx(2.0)
    s = Engine.summarize([low, high])
    assert s["queue_wait_p50_s"] == pytest.approx(1.5)    # waits 2 s, 1 s
    assert s["queue_wait_p99_s"] == pytest.approx(1.99)
