"""The finiteness check on the device: each step returns a (B,) flag, the
host reads it with the tokens, and the (B, vocab) logits reach the host only
on ticks with a ``sample`` hook (``Engine.logits_fetches`` counts them).
Quarantine decisions are compared with the host-side check the engine ran
before, kept here as ``_HostCheckEngine``."""

import json
import os

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.compiler import CompileCache
from repro.models import api
from repro.serving import snapshot as snaplib
from repro.serving.chaos import ChaosMonkey
from repro.serving.engine import Engine, Request, reference_decode

# reference executables bake in the cache layout: one compile cache each
_REF_CC = {"slot": CompileCache(), "paged": CompileCache()}


def _cfg(layout):
    kw = ({"kv_layout": "paged", "kv_block_size": 8, "kv_pool_blocks": 24}
          if layout == "paged" else {})
    return get_smoke_config("qwen-7b", d_model=64, d_ff=128, vocab_size=256,
                            **kw)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg("paged")
    return cfg, api.init_params(cfg, jax.random.PRNGKey(0))


class _HostCheckEngine(Engine):
    """The check as it ran on the host: every tick copies the logits,
    poisons the rows chaos hits, and tests each advancing row with
    ``np.isfinite`` (the device flag is ignored)."""

    def _check_rows(self, finite_np, logits, live, chunks, decoding, sample):
        logits_np = np.asarray(logits).copy()
        advancing = [i for i in live if chunks[i] or i in decoding]
        if self.chaos is not None:
            logits_np[self.chaos.corrupt_rows(advancing)] = np.nan
        bad = set()
        if self.check_finite:
            bad = {i for i in advancing
                   if not np.isfinite(logits_np[i]).all()}
        return logits_np, bad


def _reqs(cfg, seed, n, max_new=6):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, max_new_tokens=max_new,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(4, 17))
                                        ).astype(np.int32))
            for i in range(n)]


def _argmax(row):
    return int(np.argmax(row))


def _serve(engine, reqs, sample=None):
    for r in reqs:
        engine.submit(r)
    assert engine.run(sample=sample).drained
    return {r.rid: (r.status, r.error, list(r.output)) for r in reqs}


@pytest.mark.parametrize("layout,spec_k", [("slot", 0), ("paged", 0),
                                           ("slot", 3), ("paged", 3)])
def test_greedy_run_equals_oracle_without_copying_logits(layout, spec_k):
    cfg = _cfg(layout)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    reqs = _reqs(cfg, 1, 4)
    oracle = {r.rid: reference_decode(cfg, params, r.prompt,
                                      r.max_new_tokens, max_len=64,
                                      compile_cache=_REF_CC[layout])
              for r in reqs}
    eng = Engine(cfg, params, batch_size=2, max_len=64, chunk_size=16,
                 spec_k=spec_k)
    got = _serve(eng, reqs)
    assert got == {rid: ("done", None, out) for rid, out in oracle.items()}
    assert eng.steps > 0 and eng.logits_fetches == 0


def test_sample_hook_copies_once_a_tick_and_keeps_its_tokens(setup):
    cfg, params = setup
    plain = Engine(cfg, params, batch_size=2, max_len=64, chunk_size=16)
    want = _serve(plain, _reqs(cfg, 2, 4))
    hooked = Engine(cfg, params, batch_size=2, max_len=64, chunk_size=16,
                    compile_cache=plain.cache_compiles)
    assert _serve(hooked, _reqs(cfg, 2, 4), sample=_argmax) == want
    assert hooked.logits_fetches == hooked.steps > 0
    assert plain.logits_fetches == 0


def test_device_nan_row_is_quarantined_alone_without_a_copy(setup):
    """A NaN in one token's embedding row makes that request's logits
    non-finite on the device; its neighbour, whose prompt and output never
    hold that token, decodes exactly as with clean weights."""
    cfg, params = setup
    good = Request(rid=0, max_new_tokens=3,
                   prompt=np.array([5, 9, 13, 17], np.int32))
    want = reference_decode(cfg, params, good.prompt, 3, max_len=64,
                            compile_cache=_REF_CC["paged"])
    tok = min(set(range(cfg.vocab_size)) - set(good.prompt.tolist()) -
              set(want))
    poisoned = dict(params, embed=params["embed"].at[tok].set(np.nan))
    bad = Request(rid=1, max_new_tokens=3,
                  prompt=np.array([7, tok, 11], np.int32))
    runs, engines = {}, {}
    for cls in (Engine, _HostCheckEngine):
        eng = engines[cls] = cls(cfg, poisoned, batch_size=2, max_len=64,
                                 chunk_size=16, audit_every=1)
        reqs = [Request(rid=r.rid, max_new_tokens=3, prompt=r.prompt)
                for r in (good, bad)]
        runs[cls] = _serve(eng, reqs)
        assert eng.row_faults == 1
    assert runs[Engine] == runs[_HostCheckEngine] == {
        0: ("done", None, want), 1: ("error", "non-finite logits", [])}
    assert engines[Engine].logits_fetches == 0      # the flag alone saw it


@pytest.mark.parametrize("hook", [None, _argmax], ids=["greedy", "hook"])
@pytest.mark.parametrize("seed", [0, 1])
def test_seeded_chaos_quarantines_as_the_host_check_did(setup, hook, seed):
    cfg, params = setup
    runs, stats = {}, {}
    for cls in (Engine, _HostCheckEngine):
        monkey = ChaosMonkey(seed=seed, nan_rate=0.1)
        eng = cls(cfg, params, batch_size=2, max_len=64, chunk_size=16,
                  chaos=monkey, audit_every=1)
        runs[cls] = _serve(eng, _reqs(cfg, 3, 6), sample=hook)
        stats[cls] = (monkey.stats(), eng.row_faults, eng.steps,
                      eng.logits_fetches)
    assert runs[Engine] == runs[_HostCheckEngine]
    chaos, faults, steps, fetches = stats[Engine]
    assert (chaos, faults, steps) == stats[_HostCheckEngine][:3]
    outcomes = [s for s, _, _ in runs[Engine].values()]
    assert "error" in outcomes and "done" in outcomes
    assert fetches == (0 if hook is None else steps)


def test_logits_fetches_round_trips_through_snapshot(setup, tmp_path):
    cfg, params = setup
    wd = str(tmp_path / "snaps")
    eng = Engine(cfg, params, batch_size=2, max_len=64, chunk_size=16,
                 snapshot_dir=wd)
    _serve(eng, _reqs(cfg, 4, 3), sample=_argmax)
    assert eng.logits_fetches == eng.steps > 0
    eng.snapshot()
    restored = Engine.restore(wd, params, compile_cache=eng.cache_compiles)
    assert restored.logits_fetches == eng.logits_fetches
    # a snapshot from before the counter restores it as 0
    host = os.path.join(snaplib.latest_snapshot(wd)[1], "host.json")
    with open(host) as f:
        doc = json.load(f)
    del doc["counters"]["logits_fetches"]
    with open(host, "w") as f:
        json.dump(doc, f)
    older = Engine.restore(wd, params, compile_cache=eng.cache_compiles)
    assert older.logits_fetches == 0 and older.steps == eng.steps
