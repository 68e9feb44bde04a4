"""Serving benchmark: chunked-prefill mixed batching vs stall-prefill, and
batched continuous decode vs the seed's per-request loop.

Two cuts:

* **Throughput** (``--mode throughput``): decode tokens/s as a function of
  slot-batch size and queue depth — the slot engine's ONE dispatch per tick
  vs the seed's per-request batch-1 loop (``reference_decode``).

* **Mixed load** (``--mode mixed``, the default): a resident decode load
  plus a burst of prompt admissions, measured under two admission policies
  of the SAME engine:

  - ``stall``  — the seed's schedule: while any prompt is mid-prefill only
    it advances; decode rows stall (head-of-line blocking), and queued
    prompts serialize behind it.
  - ``mixed``  — chunked-prefill admission fused into the decode tick
    (Sarathi-style): every mid-prefill row advances one chunk bucket per
    tick WHILE decode rows keep emitting, and multiple admissions chunk
    together in one dispatch.

  Reported: TTFT p50/p99 over the admission burst, inter-token latency p99
  over the resident decoders, decode tokens/s.  Both policies share one
  compile cache, so the delta isolates the schedule — the serving analogue
  of EdgeLLM keeping the FPGA saturated with one fixed executable set.

``--smoke`` writes BENCH_serving.json (the CI trend record, uploaded next
to BENCH_decode.json).

Run:  PYTHONPATH=src python benchmarks/serving_bench.py [--mode mixed]
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke_config
from repro.core.compiler import CompileCache, TokenBuckets, quantize_model
from repro.models import api
from repro.serving.engine import Engine, Request

try:                       # module run (python -m benchmarks.serving_bench)
    from benchmarks.common import kv_cache_bytes
except ImportError:        # direct script run (python benchmarks/...)
    from common import kv_cache_bytes


def _workload(cfg, n_requests: int, max_new: int, seed: int = 0,
              lo: int = 4, hi: int = 28):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(0, cfg.vocab_size,
                      int(rng.integers(lo, hi))).astype(np.int32), max_new)
        for _ in range(n_requests)
    ]


# ---------------------------------------------------------------------------
# throughput mode (batched engine vs per-request loop)
# ---------------------------------------------------------------------------

def bench_batched(cfg, params, workload, batch: int, max_len: int,
                  chunk_size: int = 16):
    """Slot engine: timed after a warmup run compiles the executable set."""
    def submit_all(engine):
        for rid, (prompt, max_new) in enumerate(workload):
            engine.submit(Request(rid=rid, prompt=prompt,
                                  max_new_tokens=max_new))

    warm = Engine(cfg, params, batch_size=batch, max_len=max_len,
                  chunk_size=chunk_size)
    submit_all(warm)
    warm.run()

    engine = Engine(cfg, params, batch_size=batch, max_len=max_len,
                    chunk_size=chunk_size,
                    compile_cache=warm.cache_compiles)  # same (cfg, shapes)
    submit_all(engine)
    t0 = time.perf_counter()
    done = engine.run()
    dt = time.perf_counter() - t0
    tokens = sum(len(r.output) - 1 for r in done)  # decode tokens only
    return {
        "tokens": tokens,
        "tokens_per_s": tokens / dt,
        "steps": engine.steps,
        "occupancy": engine.slot_occupancy,
    }


def _seed_decode(cfg, params, prompt, max_new_tokens, *, max_len, cc):
    """The seed engine's inner loop: ONE bucketed batch-1 prefill + greedy
    decode.  Kept as the throughput baseline so BENCH trend numbers stay
    comparable across PRs — ``reference_decode`` is now the exact
    teacher-forced ORACLE (O(len) dispatches) and would overstate the
    batched engine's speedup if timed as the baseline."""
    buckets = TokenBuckets(max_tokens=max_len)
    bucket = buckets.bucket(len(prompt))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, -len(prompt):] = prompt
    pf = cc.get("base_prefill", bucket, lambda: jax.jit(
        lambda p, b: api.prefill(cfg, p, b, max_len)))
    logits, cache = pf(params, {"tokens": jnp.asarray(padded)})
    out = [int(np.argmax(np.asarray(logits[0])))]
    dec = cc.get("base_decode", 1, lambda: jax.jit(
        lambda p, c, t, l: api.decode_step(cfg, p, c, t, l)))
    length = bucket
    while len(out) < max_new_tokens and length < max_len:
        length += 1
        logits, cache = dec(params, cache,
                            jnp.asarray([[out[-1]]], jnp.int32),
                            jnp.asarray([length], jnp.int32))
        out.append(int(np.argmax(np.asarray(logits[0]))))
    return out


def bench_per_request(cfg, params, workload, max_len: int):
    """Seed baseline: sequential batch-1 greedy loops (shared compile cache)."""
    cc = CompileCache()
    for prompt, max_new in workload:                  # warm/compile pass
        _seed_decode(cfg, params, prompt, max_new, max_len=max_len, cc=cc)
    t0 = time.perf_counter()
    tokens = 0
    for prompt, max_new in workload:
        out = _seed_decode(cfg, params, prompt, max_new, max_len=max_len,
                           cc=cc)
        tokens += len(out) - 1
    dt = time.perf_counter() - t0
    return {"tokens": tokens, "tokens_per_s": tokens / dt}


# ---------------------------------------------------------------------------
# mixed-load mode (chunked admission vs stall-prefill)
# ---------------------------------------------------------------------------

def _mixed_workload(cfg, *, residents: int, burst: int, max_len: int,
                    seed: int = 0):
    """Resident decoders (short prompt, long generation) + an admission
    burst of long prompts arriving mid-decode."""
    rng = np.random.default_rng(seed)
    res = [Request(rid=i,
                   prompt=rng.integers(0, cfg.vocab_size, 6).astype(np.int32),
                   max_new_tokens=48)
           for i in range(residents)]
    prompt_len = max(8, int(max_len * 0.6))
    bur = [Request(rid=100 + i,
                   prompt=rng.integers(0, cfg.vocab_size,
                                       prompt_len).astype(np.int32),
                   max_new_tokens=4)
           for i in range(burst)]
    return res, bur


def bench_mixed_load(cfg, params, *, policy: str, batch: int, max_len: int,
                     chunk_size: int, burst: int,
                     compile_cache: CompileCache | None = None):
    """One mixed-load trial; returns latency metrics + the compile cache."""
    engine = Engine(cfg, params, batch_size=batch, max_len=max_len,
                    chunk_size=chunk_size, prefill_policy=policy,
                    compile_cache=compile_cache)
    # residents on half the slots; the burst admits into the free half WHILE
    # they decode — that concurrency is exactly what the two policies differ on
    residents, burst_reqs = _mixed_workload(
        cfg, residents=max(1, batch // 2), burst=burst, max_len=max_len)
    for r in residents:
        engine.submit(r)
    engine.run(max_steps=4)          # residents admitted + decoding
    for r in burst_reqs:             # the burst arrives mid-decode
        engine.submit(r)
    t0 = time.perf_counter()
    engine.run()
    dt = time.perf_counter() - t0

    ttft = [r.first_token_at - r.submitted_at for r in burst_reqs]
    itl = [d for r in residents for d in np.diff(r.token_times).tolist()]
    tokens = sum(len(r.output) - 1 for r in residents + burst_reqs)
    return {
        "policy": policy,
        "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
        "ttft_p99_ms": float(np.percentile(ttft, 99) * 1e3),
        "itl_p50_ms": float(np.percentile(itl, 50) * 1e3),
        "itl_p99_ms": float(np.percentile(itl, 99) * 1e3),
        "decode_tokens_per_s": tokens / dt,
        "steps": engine.steps,
        "mixed_ticks": engine.mixed_ticks,
        "compile_misses": engine.cache_compiles.misses,
        "compile_budget": engine.compile_budget,
    }, engine.cache_compiles


def run_mixed(cfg, params, *, batch: int = 4, max_len: int = 128,
              chunk_size: int = 16, burst: int = 6) -> dict:
    """Warm both policies on a shared compile cache, then measure each."""
    _, cc = bench_mixed_load(cfg, params, policy="mixed", batch=batch,
                             max_len=max_len, chunk_size=chunk_size,
                             burst=burst)                       # warm/compile
    stall, cc = bench_mixed_load(cfg, params, policy="stall", batch=batch,
                                 max_len=max_len, chunk_size=chunk_size,
                                 burst=burst, compile_cache=cc)
    mixed, cc = bench_mixed_load(cfg, params, policy="mixed", batch=batch,
                                 max_len=max_len, chunk_size=chunk_size,
                                 burst=burst, compile_cache=cc)
    return {
        "config": {"arch": cfg.name, "batch": batch, "max_len": max_len,
                   "chunk_size": chunk_size, "burst": burst},
        "stall_prefill": stall,
        "mixed": mixed,
        "ttft_p99_speedup": stall["ttft_p99_ms"] / mixed["ttft_p99_ms"],
        "itl_p99_speedup": stall["itl_p99_ms"] / mixed["itl_p99_ms"],
    }


# ---------------------------------------------------------------------------
# paged-KV capacity mode (resident tokens at equal HBM budget)
# ---------------------------------------------------------------------------

def _capacity_trial(cfg, params, *, batch: int, max_len: int,
                    n_requests: int, chunk_size: int = 8, seed: int = 3):
    """One engine run over a short-request workload; returns the capacity
    metrics (peak resident tokens, admission stalls) plus throughput."""
    rng = np.random.default_rng(seed)
    engine = Engine(cfg, params, batch_size=batch, max_len=max_len,
                    chunk_size=chunk_size)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(8, 15))
                                        ).astype(np.int32),
                    max_new_tokens=8)
            for i in range(n_requests)]
    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    done = engine.run()
    dt = time.perf_counter() - t0
    cache_tokens = (engine.pool_blocks * engine.block_size if engine.paged
                    else batch * max_len)
    out = {
        "kv_layout": cfg.kv_layout,
        "batch_slots": batch,
        "hbm_cache_tokens": cache_tokens,
        "hbm_cache_bytes": kv_cache_bytes(
            cache_tokens, cfg.n_kv_heads, cfg.head_dim,
            cfg.kv_quant == "int8") * cfg.n_layers,
        "peak_resident_tokens": engine.peak_resident_tokens,
        "admission_stalls": engine.admission_stalls,
        "completed": len(done),
        "steps": engine.steps,
        "tokens_per_s": sum(len(r.output) for r in done) / dt,
    }
    if engine.paged:
        out["block_size"] = engine.block_size
        out["pool_blocks"] = engine.pool_blocks
    return out


def run_paged_capacity(cfg, params, *, max_len: int = 64,
                       slot_batch: int = 4, paged_batch: int = 12,
                       block_size: int = 16, n_requests: int = 18) -> dict:
    """Slot vs paged at EQUAL KV HBM budget.

    The slot engine reserves ``max_len`` rows per slot, so its resident
    batch is capped at ``slot_batch`` regardless of how short requests are.
    The paged engine gets the SAME pool of cache tokens
    (``slot_batch * max_len``) carved into blocks, plus more slots — short
    requests lease only the blocks they touch, so more of them fit
    resident; reservation pressure shows up as admission stalls instead of
    wasted rows."""
    import dataclasses
    pool_blocks = slot_batch * max_len // block_size   # equal token budget
    cfg_paged = dataclasses.replace(cfg, kv_layout="paged",
                                    kv_block_size=block_size,
                                    kv_pool_blocks=pool_blocks)
    slot = _capacity_trial(cfg, params, batch=slot_batch, max_len=max_len,
                           n_requests=n_requests)
    paged = _capacity_trial(cfg_paged, params, batch=paged_batch,
                            max_len=max_len, n_requests=n_requests)
    return {
        "config": {"arch": cfg.name, "max_len": max_len,
                   "block_size": block_size, "n_requests": n_requests},
        "slot": slot,
        "paged": paged,
        "resident_tokens_gain": (paged["peak_resident_tokens"] /
                                 max(slot["peak_resident_tokens"], 1)),
    }


# ---------------------------------------------------------------------------
# mesh mode (sharded paged serving: resident capacity across a device mesh)
# ---------------------------------------------------------------------------

_MESH_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import contextlib, json, sys, time
sys.path.insert(0, "src")
import jax
import numpy as np
from repro.configs import get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.parallel.hints import use_mesh
from repro.serving.engine import Engine, Request

P_DEV, BS, MAX_LEN, N_REQ = 11, 8, 64, 24
n_dev = jax.device_count()

def mk_cfg(pool_blocks):
    return get_smoke_config("qwen-7b", d_model=64, d_ff=128, vocab_size=256,
                            kv_layout="paged", kv_block_size=BS,
                            kv_pool_blocks=pool_blocks)

# single-device engine holds P_DEV + 1 pool rows (null included); the
# sharded engine holds the SAME rows PER SHARD: n_dev * (P_DEV + 1) rows
cfg_one = mk_cfg(P_DEV)
cfg_mesh = mk_cfg(n_dev * (P_DEV + 1) - 1)
params = api.init_params(cfg_one, jax.random.PRNGKey(0))

def workload():
    rng = np.random.default_rng(3)
    return [Request(rid=i,
                    prompt=rng.integers(0, 256, int(rng.integers(8, 15))
                                        ).astype(np.int32),
                    max_new_tokens=8)
            for i in range(N_REQ)]

def trial(cfg, batch, ctx):
    reqs = workload()
    with ctx:
        engine = Engine(cfg, params, batch_size=batch, max_len=MAX_LEN,
                        chunk_size=8, audit_every=4)
        for r in reqs:
            engine.submit(r)
        t0 = time.perf_counter()
        done = engine.run()
        dt = time.perf_counter() - t0
        engine.audit()
    return {
        "batch_slots": batch,
        "pool_blocks": engine.pool_blocks,
        "n_homes": engine.n_homes,
        "per_device_pool_rows": (engine.pool_blocks + 1) // engine.n_homes,
        "peak_resident_tokens": engine.peak_resident_tokens,
        "admission_stalls": engine.admission_stalls,
        "completed": len(done),
        "steps": engine.steps,
        "tokens_per_s": sum(len(r.output) for r in done) / dt,
        "outputs": {r.rid: [int(t) for t in r.output] for r in reqs},
    }

single = trial(cfg_one, 4, contextlib.nullcontext())
mesh = make_mesh((1, n_dev), ("data", "model"))
sharded = trial(cfg_mesh, 16, use_mesh(mesh))
tokens_equal = single.pop("outputs") == sharded.pop("outputs")
print("RESULT " + json.dumps({
    "n_devices": n_dev,
    "single_device": single,
    "sharded": sharded,
    "resident_tokens_gain": (sharded["peak_resident_tokens"] /
                             max(single["peak_resident_tokens"], 1)),
    "tokens_equal": tokens_equal,
}))
"""


def run_mesh() -> dict:
    """Sharded paged serving vs a single device at EQUAL per-device KV
    budget (the PR 10 acceptance cut).

    Runs in a CPU subprocess with 8 forced host devices: the single-device
    engine gets ``P_DEV + 1`` pool rows; the sharded engine gets the same
    rows on EACH of the 8 shards (block homes), so resident batch scales
    with total mesh memory.  Token streams must be identical — the mesh
    buys capacity, never different tokens."""
    import os
    import subprocess
    import sys
    # a CPU capacity count: the child is pinned to the host platform, so it
    # never competes for an accelerator the parent process already holds
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_WORKER], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=900)
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(
        f"mesh bench worker failed:\nstdout={proc.stdout[-2000:]}\n"
        f"stderr={proc.stderr[-3000:]}")


# ---------------------------------------------------------------------------
# prefix-sharing mode (shared system prompt, radix cache + CoW paged KV)
# ---------------------------------------------------------------------------

def _prefix_workload(cfg, *, n_requests: int, system_len: int, max_new: int,
                     user_lo: int = 4, user_hi: int = 13, seed: int = 5):
    """The multi-tenant shape prefix sharing targets: every request opens
    with the SAME system prompt and differs only in a short user turn."""
    rng = np.random.default_rng(seed)
    system = rng.integers(0, cfg.vocab_size, system_len)
    return [Request(rid=i,
                    prompt=np.concatenate(
                        [system,
                         rng.integers(0, cfg.vocab_size,
                                      int(rng.integers(user_lo, user_hi)))]
                    ).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(n_requests)]


def _prefix_trial(cfg, params, *, prefix_cache: bool, batch: int,
                  max_len: int, workload, chunk_size: int = 8,
                  compile_cache: CompileCache | None = None):
    """One engine run over the shared-system-prompt workload.

    ``cached_ttft_p50_ms`` is the headline: TTFT over the requests admitted
    AFTER the first batch — the ones whose system prompt is already cached
    when sharing is on (the cache warms as the first wave's prefills
    finish), measured identically for the no-sharing baseline."""
    engine = Engine(cfg, params, batch_size=batch, max_len=max_len,
                    chunk_size=chunk_size, prefix_cache=prefix_cache,
                    compile_cache=compile_cache)
    reqs = [Request(rid=r.rid, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens) for r in workload]
    for r in reqs:
        engine.submit(r)
    t0 = time.perf_counter()
    done = engine.run()
    dt = time.perf_counter() - t0
    ttft = [r.first_token_at - r.submitted_at for r in reqs]
    late = [r.first_token_at - r.submitted_at for r in reqs
            if r.rid >= batch]
    out = {
        "prefix_cache": engine.prefix_sharing,
        "completed": len(done),
        "ttft_p50_ms": float(np.percentile(ttft, 50) * 1e3),
        "cached_ttft_p50_ms": float(np.percentile(late, 50) * 1e3),
        "tokens_per_s": sum(len(r.output) for r in done) / dt,
        "steps": engine.steps,
        "mixed_ticks": engine.mixed_ticks,
        "occupancy": engine.slot_occupancy,
        "admission_stalls": engine.admission_stalls,
        "peak_pool_blocks": engine.peak_pool_blocks,
        "pool_blocks": engine.pool_blocks,
        "outputs": {r.rid: [int(t) for t in r.output] for r in done},
    }
    if engine.prefix_sharing:
        out["prefix"] = engine.prefix_stats()
        st = engine.pool_stats()
        out["shared_blocks"] = st["shared_blocks"]
        out["cow_copies"] = st["cow_copies"]
        out["prefix_hit_tokens"] = st["prefix_hit_tokens"]
    return out, engine.cache_compiles


def run_prefix_sharing(cfg, params, *, batch: int = 4, max_len: int = 96,
                       block_size: int = 8, system_len: int = 48,
                       n_requests: int = 16, max_new: int = 8) -> dict:
    """Sharing ON vs OFF on the same workload at EQUAL KV HBM budget.

    The pool is sized so the no-sharing engine can hold only ~2 requests'
    worst case at once (reservation pressure): sharing admits the common
    system prompt by page-table copy, so the same pool holds the full batch
    concurrently — stalls collapse, slot occupancy rises, and cached-prefix
    TTFT drops to the cost of the user-turn suffix.  Outputs are checked
    token-identical between the two runs (sharing is exact)."""
    import dataclasses
    worst = -(-(system_len + 12 + max_new) // block_size)
    pool_blocks = 2 * worst + 6          # ~2 concurrent without sharing
    cfg_paged = dataclasses.replace(cfg, kv_layout="paged",
                                    kv_block_size=block_size,
                                    kv_pool_blocks=pool_blocks)
    workload = _prefix_workload(cfg_paged, n_requests=n_requests,
                                system_len=system_len, max_new=max_new)
    kw = dict(batch=batch, max_len=max_len, workload=workload)
    _, cc = _prefix_trial(cfg_paged, params, prefix_cache=True, **kw)  # warm
    off, cc = _prefix_trial(cfg_paged, params, prefix_cache=False,
                            compile_cache=cc, **kw)
    on, cc = _prefix_trial(cfg_paged, params, prefix_cache=True,
                           compile_cache=cc, **kw)
    outputs_match = off.pop("outputs") == on.pop("outputs")
    return {
        "config": {"arch": cfg.name, "batch": batch, "max_len": max_len,
                   "block_size": block_size, "system_len": system_len,
                   "n_requests": n_requests, "pool_blocks": pool_blocks},
        "no_sharing": off,
        "sharing": on,
        "outputs_match": outputs_match,
        "cached_ttft_p50_speedup": (off["cached_ttft_p50_ms"] /
                                    max(on["cached_ttft_p50_ms"], 1e-9)),
        "occupancy_gain": on["occupancy"] / max(off["occupancy"], 1e-9),
    }


# ---------------------------------------------------------------------------
# speculative-decoding mode (prompt-lookup drafts through the mixed dispatch)
# ---------------------------------------------------------------------------

def _spec_workload(cfg, n_requests: int, max_new: int, seed: int = 0):
    """Repetition-heavy cut: prompts are a short n-gram pattern tiled a few
    times, and the generation budget is long.  Tiled prompts give the
    prompt-lookup drafter immediate matches, and a deterministic greedy
    model run long enough falls into token cycles the drafter then predicts
    from the row's own emitted history — the synthetic stand-in for the
    copied spans / boilerplate / format scaffolding that make real LLM
    output locally repetitive."""
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n_requests):
        pat = rng.integers(0, cfg.vocab_size, int(rng.integers(3, 6)))
        reqs.append((np.tile(pat, 4).astype(np.int32), max_new))
    return reqs


def run_spec(cfg, params, *, batch: int = 4, max_len: int = 128,
             max_new: int = 96, n_requests: int = 12,
             ks: tuple = (2, 4, 8), repeats: int = 7) -> dict:
    """Plain-decode baseline vs draft depths K — same workload, same greedy
    outputs (checked), fewer weight streams per emitted token.

    Each depth's run is only ~100 dispatches at smoke scale, so a single
    wall-clock sample is scheduler noise.  Every depth (baseline included)
    is re-run ``repeats`` times with the runs INTERLEAVED round-robin, and
    each speedup is the MEDIAN of per-cycle PAIRED ratios (depth-K's sample
    over the baseline sample from the SAME cycle): machine state is shared
    within a cycle, so load/frequency drift cancels out of each ratio, and
    the median rejects cycles that drifted mid-cycle.  ``tokens_per_s`` is
    best-of for each depth (tokens, dispatches and acceptance are
    deterministic across runs)."""
    workload = _spec_workload(cfg, n_requests, max_new)
    depths = (0,) + tuple(ks)
    # spec and non-spec engines bind DIFFERENT executables under the same
    # ("mixed", W) keys — one shared compile cache per variant, not per K
    caches = {False: CompileCache(), True: CompileCache()}

    def run_once(k):
        engine = Engine(cfg, params, batch_size=batch, max_len=max_len,
                        chunk_size=16, spec_k=k,
                        compile_cache=caches[bool(k)])
        for rid, (prompt, mn) in enumerate(workload):
            engine.submit(Request(rid=rid, prompt=prompt,
                                  max_new_tokens=mn))
        t0 = time.perf_counter()
        done = engine.run()
        return time.perf_counter() - t0, engine, done

    results = {}
    for k in depths:                     # warm pass compiles + records stats
        _, engine, done = run_once(k)
        results[k] = {
            "spec_k": k,
            "tokens": sum(len(r.output) - 1 for r in done),
            "dispatches": engine.dispatches,
            "outputs": {r.rid: [int(t) for t in r.output] for r in done},
        }
        if k:
            s = engine.spec_stats()
            results[k].update(
                {f: s[f] for f in ("draft_tokens", "accepted_tokens",
                                   "acceptance_rate",
                                   "accepted_per_dispatch", "rewinds")})
    samples = {k: [] for k in depths}
    for _ in range(repeats):             # interleaved timing cycles
        for k in depths:
            samples[k].append(run_once(k)[0])
    for k in depths:
        results[k]["tokens_per_s"] = results[k]["tokens"] / min(samples[k])

    base = results[0]
    base_outputs = base.pop("outputs")
    trials = []
    for k in ks:
        r = results[k]
        r["outputs_match_baseline"] = r.pop("outputs") == base_outputs
        ratios = sorted(samples[0][i] / samples[k][i]
                        for i in range(repeats))
        r["speedup_vs_plain"] = ratios[repeats // 2]
        trials.append(r)
    return {
        "config": {"arch": cfg.name, "batch": batch, "max_len": max_len,
                   "max_new": max_new, "n_requests": n_requests,
                   "repeats": repeats},
        "baseline": base,
        "spec": trials,
        "best_speedup": max(t["speedup_vs_plain"] for t in trials),
    }


# ---------------------------------------------------------------------------
# overload mode (past-capacity: stall-only vs preemption + deadlines)
# ---------------------------------------------------------------------------

def _overload_workload(cfg, *, hogs: int, interactive: int, hog_new: int,
                       int_new: int, deadline_s: float, seed: int = 9):
    """Past-capacity mix: ``hogs`` low-priority long generations that FIFO
    admission seats first and that hold their slots for ~``hog_new`` ticks,
    plus ``interactive`` high-priority short requests with a deadline that
    only fits if they do NOT wait behind the hogs."""
    rng = np.random.default_rng(seed)
    hog_reqs = [Request(rid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            8).astype(np.int32),
                        max_new_tokens=hog_new, priority=0)
                for i in range(hogs)]
    int_reqs = [Request(rid=100 + i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            8).astype(np.int32),
                        max_new_tokens=int_new, priority=1,
                        deadline_s=deadline_s)
                for i in range(interactive)]
    return hog_reqs, int_reqs


def _overload_trial(cfg, params, *, resilient: bool, batch: int,
                    max_len: int, deadline_s: float, hog_new: int,
                    int_new: int, hogs: int, interactive: int,
                    compile_cache: CompileCache | None = None):
    """One past-capacity run.  ``resilient`` turns on bounded preemption +
    deadline enforcement; the baseline is the stall-only engine (requests
    keep their deadlines for POST-HOC goodput accounting, but nothing is
    evicted or expired).  Goodput counts only tokens of requests that
    finished ``done`` within their deadline."""
    engine = Engine(cfg, params, batch_size=batch, max_len=max_len,
                    chunk_size=16,
                    max_preemptions=1 if resilient else 0,
                    enforce_deadlines=resilient,
                    compile_cache=compile_cache)
    hog_reqs, int_reqs = _overload_workload(
        cfg, hogs=hogs, interactive=interactive, hog_new=hog_new,
        int_new=int_new, deadline_s=deadline_s)
    for r in hog_reqs + int_reqs:       # hogs first: FIFO seats them
        engine.submit(r)
    t0 = time.perf_counter()
    engine.run()
    dt = time.perf_counter() - t0
    reqs = hog_reqs + int_reqs

    def in_deadline(r):
        return (r.deadline_s is None or
                (r.finished_at or 1e30) - r.submitted_at <= r.deadline_s)

    good = sum(len(r.output) for r in reqs
               if r.status == "done" and in_deadline(r))
    misses = sum(1 for r in int_reqs
                 if r.status != "done" or not in_deadline(r))
    ttft = [r.first_token_at - r.submitted_at for r in int_reqs
            if r.first_token_at is not None]
    return {
        "resilient": resilient,
        "wall_s": dt,
        "goodput_tokens_per_s": good / dt,
        "goodput_tokens": good,
        "total_tokens": sum(len(r.output) for r in reqs),
        "deadline_miss_rate": misses / len(int_reqs),
        "interactive_ttft_p99_ms": (float(np.percentile(ttft, 99) * 1e3)
                                    if ttft else None),
        "preemptions": engine.preemptions,
        "deadline_kills": engine.deadline_misses,
        "admission_stalls": engine.admission_stalls,
        "steps": engine.steps,
    }, engine.cache_compiles


def run_overload(cfg, params, *, batch: int = 4, max_len: int = 128,
                 hogs: int = 4, interactive: int = 8, hog_new: int = 64,
                 int_new: int = 6, deadline_ticks: int = 40) -> dict:
    """Sustained past-capacity load: stall-only vs preemption + deadlines.

    The offered load is 3x slot capacity (12 concurrent requests on 4
    slots) and FIFO seats the hogs first, so the stall baseline makes every
    interactive request wait ~``hog_new`` ticks for a slot — far past its
    deadline.  The resilient engine priority-preempts hogs (losslessly,
    bounded at 1 each) so interactive requests run immediately and meet it.
    Deadlines are wall-clock, so the budget is calibrated in TICKS: a warm
    probe run measures the per-tick wall time and ``deadline_ticks`` (less
    than the hogs' slot-holding time, multiples of the interactive service
    time) converts to seconds."""
    # warm compiles the executable set; the probe then measures the true
    # per-tick wall time (compilation excluded — it would inflate the
    # deadline budget ~10x and nothing would ever miss)
    warm = dict(batch=batch, max_len=max_len, deadline_s=1e9,
                hog_new=hog_new, int_new=int_new, hogs=hogs,
                interactive=interactive)
    _, cc = _overload_trial(cfg, params, resilient=True, **warm)
    probe, cc = _overload_trial(cfg, params, resilient=True,
                                compile_cache=cc, **warm)
    tick_s = probe["wall_s"] / probe["steps"]
    deadline_s = deadline_ticks * tick_s
    kw = dict(batch=batch, max_len=max_len, deadline_s=deadline_s,
              hog_new=hog_new, int_new=int_new, hogs=hogs,
              interactive=interactive, compile_cache=cc)
    stall, cc = _overload_trial(cfg, params, resilient=False, **kw)
    kw["compile_cache"] = cc
    resilient, cc = _overload_trial(cfg, params, resilient=True, **kw)
    return {
        "config": {"arch": cfg.name, "batch": batch, "max_len": max_len,
                   "hogs": hogs, "interactive": interactive,
                   "hog_new": hog_new, "int_new": int_new,
                   "deadline_ticks": deadline_ticks,
                   "deadline_ms": deadline_s * 1e3,
                   "offered_load_x": (hogs + interactive) / batch},
        "stall_baseline": stall,
        "resilient": resilient,
        "goodput_gain": (resilient["goodput_tokens_per_s"] /
                         max(stall["goodput_tokens_per_s"], 1e-9)),
        "miss_rate_drop": (stall["deadline_miss_rate"] -
                           resilient["deadline_miss_rate"]),
    }


# ---------------------------------------------------------------------------
# restart mode (snapshot cost, recovery latency, warm vs cold TTFT)
# ---------------------------------------------------------------------------

def _probe_ttft(engine, rid: int, prompt, max_new: int = 4) -> tuple:
    """Submit ONE probe request into an idle engine and run it to completion;
    returns (ttft_seconds, output tokens)."""
    req = Request(rid=rid, prompt=prompt, max_new_tokens=max_new)
    engine.submit(req)
    engine.run()
    return req.first_token_at - req.submitted_at, [int(t) for t in req.output]


def run_restart(cfg, params, *, batch: int = 4, max_len: int = 96,
                block_size: int = 8, system_len: int = 48,
                n_requests: int = 10, max_new: int = 8,
                save_repeats: int = 3) -> dict:
    """Durability cost/benefit: snapshot save time, ``Engine.restore``
    latency, and what the restored state buys — warm-restore TTFT (prefix
    cache + executables back) vs cold-start TTFT (same process, empty
    cache) on an identical probe prompt.

    All engines share ONE compile cache, so every TTFT delta isolates
    STATE (the radix prefix cache restored from the snapshot) rather than
    re-jit — the cost a cold process actually pays twice.  Probe prompts
    share the workload's system prompt with a fresh user turn, so each
    probe hits exactly the system-prefix chain (never a previous probe's).
    The restored and cold probes use the SAME prompt and must emit the
    same greedy tokens (``outputs_match``)."""
    import dataclasses
    import os
    import shutil
    import tempfile

    worst = -(-(system_len + 12 + max_new) // block_size)
    pool_blocks = 2 * worst + 6
    cfg_paged = dataclasses.replace(cfg, kv_layout="paged",
                                    kv_block_size=block_size,
                                    kv_pool_blocks=pool_blocks)
    workload = _prefix_workload(cfg_paged, n_requests=n_requests,
                                system_len=system_len, max_new=max_new)
    system = workload[0].prompt[:system_len]
    rng = np.random.default_rng(11)
    probe_x = np.concatenate(
        [system, rng.integers(0, cfg.vocab_size, 8)]).astype(np.int32)
    probe_y = np.concatenate(
        [system, rng.integers(0, cfg.vocab_size, 8)]).astype(np.int32)
    kw = dict(batch_size=batch, max_len=max_len, chunk_size=8,
              prefix_cache=True)

    # warm pass: compile the executable set every later engine reuses
    warm = Engine(cfg_paged, params, **kw)
    for r in workload:
        warm.submit(Request(rid=r.rid, prompt=r.prompt,
                            max_new_tokens=r.max_new_tokens))
    warm.run()
    _probe_ttft(warm, 9000, probe_x)
    cc = warm.cache_compiles

    # live engine: serve the workload, measure the warm cached-prefix TTFT,
    # then snapshot (save_repeats times for a median save cost)
    workdir = tempfile.mkdtemp(prefix="bench_restart_")
    engine = Engine(cfg_paged, params, compile_cache=cc,
                    snapshot_dir=workdir, snapshot_every=0, **kw)
    for r in workload:
        engine.submit(Request(rid=r.rid, prompt=r.prompt,
                              max_new_tokens=r.max_new_tokens))
    engine.run()
    prekill_ttft, _ = _probe_ttft(engine, 9001, probe_x)
    saves = []
    for _ in range(save_repeats):
        t0 = time.perf_counter()
        engine.snapshot()
        saves.append(time.perf_counter() - t0)
    from repro.serving import snapshot as snaplib
    _, snapdir = snaplib.latest_snapshot(workdir)
    snap_bytes = sum(os.path.getsize(os.path.join(dp, f))
                     for dp, _, fs in os.walk(snapdir) for f in fs)

    # the process "dies" here: the live engine is abandoned unflushed and a
    # fresh one recovers everything from disk
    t0 = time.perf_counter()
    restored = Engine.restore(workdir, params, compile_cache=cc)
    restore_s = time.perf_counter() - t0
    restored_ttft, out_restored = _probe_ttft(restored, 9002, probe_y)

    # cold start: same executables, but no durable state — the probe pays
    # the full system-prompt prefill again
    cold = Engine(cfg_paged, params, compile_cache=cc, **kw)
    cold_ttft, out_cold = _probe_ttft(cold, 9003, probe_y)

    shutil.rmtree(workdir, ignore_errors=True)
    return {
        "config": {"arch": cfg.name, "batch": batch, "max_len": max_len,
                   "block_size": block_size, "system_len": system_len,
                   "n_requests": n_requests, "pool_blocks": pool_blocks},
        "snapshot_save_ms": float(np.median(saves) * 1e3),
        "snapshot_bytes": snap_bytes,
        "restore_ms": restore_s * 1e3,
        "prekill_cached_ttft_ms": prekill_ttft * 1e3,
        "restored_ttft_ms": restored_ttft * 1e3,
        "cold_ttft_ms": cold_ttft * 1e3,
        "warm_restore_ttft_speedup": cold_ttft / max(restored_ttft, 1e-9),
        "restored_vs_prekill": restored_ttft / max(prekill_ttft, 1e-9),
        "outputs_match": out_restored == out_cold,
        "restored_prefix_hit_tokens": restored.prefix_hit_tokens,
    }


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def rows() -> list[tuple[str, float, str]]:
    """benchmarks.run driver entry: us/token + mixed-load latency cut."""
    cfg = get_smoke_config("qwen-7b", d_model=128, d_ff=256, vocab_size=512)
    params = quantize_model(api.init_params(cfg, jax.random.PRNGKey(0)),
                            "dense")
    workload = _workload(cfg, 6, 8)
    base = bench_per_request(cfg, params, workload, max_len=64)
    batched = bench_batched(cfg, params, workload, batch=4, max_len=64)
    # same engine with an int8 KV cache: decode runs the fused-dequant
    # blocked/pallas path end to end (decode_bench has the kernel-level cut)
    cfg_q = get_smoke_config("qwen-7b", d_model=128, d_ff=256, vocab_size=512,
                             kv_quant="int8")
    batched_q = bench_batched(cfg_q, params, workload, batch=4, max_len=64)
    mixed = run_mixed(cfg, params)
    out = [
        ("serving/per_request_tok", 1e6 / base["tokens_per_s"],
         f"tok_s={base['tokens_per_s']:.1f}"),
        ("serving/batched_b4_tok", 1e6 / batched["tokens_per_s"],
         f"tok_s={batched['tokens_per_s']:.1f} "
         f"occup={batched['occupancy']:.2f} "
         f"speedup={batched['tokens_per_s'] / base['tokens_per_s']:.2f}x"),
        ("serving/batched_b4_int8kv_tok", 1e6 / batched_q["tokens_per_s"],
         f"tok_s={batched_q['tokens_per_s']:.1f} "
         f"occup={batched_q['occupancy']:.2f}"),
        ("serving/mixed_ttft_p99_us", mixed["mixed"]["ttft_p99_ms"] * 1e3,
         f"vs_stall={mixed['ttft_p99_speedup']:.2f}x"),
        ("serving/mixed_itl_p99_us", mixed["mixed"]["itl_p99_ms"] * 1e3,
         f"vs_stall={mixed['itl_p99_speedup']:.2f}x"),
    ]
    spec = run_spec(cfg, params, n_requests=4, max_new=32, ks=(4,))
    k4 = spec["spec"][0]
    out.append(
        ("serving/spec_k4_tok", 1e6 / k4["tokens_per_s"],
         f"tok_s={k4['tokens_per_s']:.1f} "
         f"accept={k4['acceptance_rate']:.2f} "
         f"speedup={k4['speedup_vs_plain']:.2f}x "
         f"match={k4['outputs_match_baseline']}"))
    pfx = run_prefix_sharing(cfg, params, n_requests=10)
    out.append(
        ("serving/prefix_cached_ttft_p50_us",
         pfx["sharing"]["cached_ttft_p50_ms"] * 1e3,
         f"vs_cold={pfx['cached_ttft_p50_speedup']:.2f}x "
         f"hit_tokens={pfx['sharing']['prefix_hit_tokens']} "
         f"cow={pfx['sharing']['cow_copies']} "
         f"match={pfx['outputs_match']}"))
    ovl = run_overload(cfg, params)
    out.append(
        ("serving/overload_goodput_tok",
         1e6 / max(ovl["resilient"]["goodput_tokens_per_s"], 1e-9),
         f"goodput_gain={ovl['goodput_gain']:.2f}x "
         f"miss={ovl['resilient']['deadline_miss_rate']:.2f}"
         f"<-{ovl['stall_baseline']['deadline_miss_rate']:.2f} "
         f"preempt={ovl['resilient']['preemptions']}"))
    rst = run_restart(cfg, params, n_requests=8)
    out.append(
        ("serving/restore_us", rst["restore_ms"] * 1e3,
         f"save={rst['snapshot_save_ms']:.1f}ms "
         f"warm_ttft={rst['restored_ttft_ms']:.1f}ms "
         f"vs_cold={rst['warm_restore_ttft_speedup']:.2f}x "
         f"match={rst['outputs_match']}"))
    return out


def run_smoke(path: str = "BENCH_serving.json") -> dict:
    """CI trend record: mixed-load latency, chunked vs stall-prefill."""
    cfg = get_smoke_config("qwen-7b", d_model=128, d_ff=256, vocab_size=512)
    params = quantize_model(api.init_params(cfg, jax.random.PRNGKey(0)),
                            "dense")
    record = run_mixed(cfg, params)
    workload = _workload(cfg, 6, 8)
    base = bench_per_request(cfg, params, workload, max_len=64)
    batched = bench_batched(cfg, params, workload, batch=4, max_len=64)
    record["decode_tokens_per_s"] = {
        "per_request": base["tokens_per_s"],
        "batched_b4": batched["tokens_per_s"],
    }
    # paged-KV capacity cut: strictly more admissible resident tokens than
    # the slot layout at the same KV HBM budget (the acceptance record)
    record["paged_capacity"] = run_paged_capacity(cfg, params)
    # speculative-decoding cut: accepted tokens/dispatch and decode tok/s at
    # K in {2, 4, 8} on the repetition-heavy workload, plain decode baseline
    record["speculative"] = run_spec(cfg, params)
    # prefix-sharing cut: shared-system-prompt workload, sharing ON vs OFF
    # at equal KV HBM budget (cached TTFT + concurrency, outputs checked)
    record["prefix_sharing"] = run_prefix_sharing(cfg, params)
    # overload cut: past-capacity workload, stall-only baseline vs bounded
    # preemption + deadline enforcement (goodput must strictly dominate)
    record["overload"] = run_overload(cfg, params)
    # restart cut: snapshot save cost, Engine.restore latency, and the
    # warm-restore vs cold-start TTFT gap the durable prefix cache buys
    record["restart"] = run_restart(cfg, params)
    # mesh cut (subprocess, 8 forced host devices): sharded paged serving
    # must fit >= 1.5x the resident tokens of one device at equal
    # per-device KV budget, with identical token streams
    record["mesh"] = run_mesh()
    with open(path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
    print(json.dumps(record, indent=2, sort_keys=True))
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="mixed",
                    choices=["mixed", "throughput", "spec", "prefix",
                             "overload", "restart", "mesh"])
    ap.add_argument("--arch", default="qwen-7b")
    ap.add_argument("--batches", default="1,2,4,8")
    ap.add_argument("--queue-depths", default="8,16")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--chunk-size", type=int, default=16)
    ap.add_argument("--burst", type=int, default=6)
    ap.add_argument("--quantize", default="dense")
    ap.add_argument("--kv-quant", default="none", choices=["none", "int8"],
                    help="int8 = fused-dequant decode path end to end")
    ap.add_argument("--smoke", action="store_true",
                    help="mixed-load latency smoke -> BENCH_serving.json")
    ap.add_argument("--paged-capacity", action="store_true",
                    help="slot vs paged resident-token capacity at equal "
                         "KV HBM budget")
    args = ap.parse_args()

    if args.smoke:
        run_smoke()
        return

    cfg = get_smoke_config(args.arch, d_model=128, d_ff=256, vocab_size=512,
                           kv_quant=args.kv_quant)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    if args.quantize != "none":
        params = quantize_model(params, args.quantize)

    if args.paged_capacity:
        rec = run_paged_capacity(cfg, params, max_len=args.max_len)
        print(json.dumps(rec, indent=2, sort_keys=True))
        gain = rec["resident_tokens_gain"]
        print(f"paged resident-token capacity: {gain:.2f}x the slot layout "
              f"at equal HBM (stalls: paged={rec['paged']['admission_stalls']}"
              f" slot={rec['slot']['admission_stalls']})")
        return

    if args.mode == "mesh":
        rec = run_mesh()
        print(f"{rec['n_devices']} devices, equal per-device KV budget "
              f"({rec['single_device']['per_device_pool_rows']} pool rows "
              f"each)")
        print(f"{'engine':>14} {'slots':>6} {'pool':>6} {'homes':>6} "
              f"{'resident':>9} {'stalls':>7} {'steps':>6} {'tok/s':>8}")
        for key, name in (("single_device", "single"),
                          ("sharded", "sharded")):
            r = rec[key]
            print(f"{name:>14} {r['batch_slots']:>6} {r['pool_blocks']:>6} "
                  f"{r['n_homes']:>6} {r['peak_resident_tokens']:>9} "
                  f"{r['admission_stalls']:>7} {r['steps']:>6} "
                  f"{r['tokens_per_s']:>8.1f}")
        print(f"sharded paged serving holds "
              f"{rec['resident_tokens_gain']:.2f}x the resident tokens of "
              f"one device (tokens_equal={rec['tokens_equal']})")
        return

    if args.mode == "prefix":
        rec = run_prefix_sharing(cfg, params, max_len=args.max_len)
        c = rec["config"]
        print(f"arch={cfg.name} system_prompt={c['system_len']} tokens, "
              f"{c['n_requests']} requests, pool={c['pool_blocks']} blocks "
              f"x {c['block_size']} (equal HBM both runs)")
        print(f"{'sharing':>8} {'cached_ttft_p50':>15} {'stalls':>7} "
              f"{'occup':>6} {'peak_blk':>8} {'steps':>6}")
        for key in ("no_sharing", "sharing"):
            r = rec[key]
            print(f"{str(r['prefix_cache']):>8} "
                  f"{r['cached_ttft_p50_ms']:>14.1f}m "
                  f"{r['admission_stalls']:>7} {r['occupancy']:>6.2f} "
                  f"{r['peak_pool_blocks']:>8} {r['steps']:>6}")
        on = rec["sharing"]
        print(f"cached-prefix TTFT p50 {rec['cached_ttft_p50_speedup']:.2f}x "
              f"faster, occupancy {rec['occupancy_gain']:.2f}x at equal pool "
              f"(outputs_match={rec['outputs_match']}); "
              f"{on['prefix']['hits']} hits, "
              f"{on['prefix_hit_tokens']} prompt tokens reused, "
              f"{on['cow_copies']} CoW copies, "
              f"{on['shared_blocks']} blocks shared at end")
        return

    if args.mode == "overload":
        rec = run_overload(cfg, params, max_len=args.max_len)
        c = rec["config"]
        print(f"arch={cfg.name} offered load {c['offered_load_x']:.1f}x "
              f"slot capacity ({c['hogs']} hogs x {c['hog_new']} tokens + "
              f"{c['interactive']} interactive x {c['int_new']}, deadline "
              f"{c['deadline_ms']:.0f} ms = {c['deadline_ticks']} ticks)")
        print(f"{'engine':>10} {'goodput/s':>10} {'miss':>6} {'ttft_p99':>9} "
              f"{'preempt':>8} {'kills':>6} {'stalls':>7} {'steps':>6}")
        for key, name in (("stall_baseline", "stall"),
                          ("resilient", "resilient")):
            r = rec[key]
            t = (f"{r['interactive_ttft_p99_ms']:>8.1f}m"
                 if r["interactive_ttft_p99_ms"] is not None else f"{'-':>9}")
            print(f"{name:>10} {r['goodput_tokens_per_s']:>10.1f} "
                  f"{r['deadline_miss_rate']:>6.2f} {t} "
                  f"{r['preemptions']:>8} {r['deadline_kills']:>6} "
                  f"{r['admission_stalls']:>7} {r['steps']:>6}")
        print(f"preemption+deadlines: {rec['goodput_gain']:.2f}x goodput, "
              f"miss rate -{rec['miss_rate_drop']:.2f} vs stall-only")
        return

    if args.mode == "restart":
        rec = run_restart(cfg, params, max_len=args.max_len)
        c = rec["config"]
        print(f"arch={cfg.name} {c['n_requests']} requests, system prompt "
              f"{c['system_len']} tokens, pool={c['pool_blocks']} blocks "
              f"(snapshot={rec['snapshot_bytes'] / 1024:.0f} KiB)")
        print(f"snapshot save      {rec['snapshot_save_ms']:>8.1f} ms "
              f"(median of 3, atomic)")
        print(f"Engine.restore     {rec['restore_ms']:>8.1f} ms "
              f"(device state + host replay + warm executables)")
        print(f"TTFT  pre-kill     {rec['prekill_cached_ttft_ms']:>8.1f} ms "
              f"(cached prefix, live engine)")
        print(f"TTFT  warm restore {rec['restored_ttft_ms']:>8.1f} ms "
              f"({rec['restored_vs_prekill']:.2f}x pre-kill; prefix cache "
              f"survived the crash)")
        print(f"TTFT  cold start   {rec['cold_ttft_ms']:>8.1f} ms "
              f"(no durable state)")
        print(f"warm restore beats cold start "
              f"{rec['warm_restore_ttft_speedup']:.2f}x on TTFT "
              f"(outputs_match={rec['outputs_match']})")
        return

    if args.mode == "spec":
        rec = run_spec(cfg, params, max_len=args.max_len)
        print(f"arch={cfg.name} max_len={args.max_len} "
              f"workload={rec['config']['n_requests']} reqs x "
              f"{rec['config']['max_new']} new tokens (repetition-heavy)")
        print(f"{'spec_k':>6} {'tok/s':>8} {'disp':>6} {'accept':>7} "
              f"{'acc/disp':>8} {'rewinds':>7} {'speedup':>8} {'match':>6}")
        b = rec["baseline"]
        print(f"{0:>6} {b['tokens_per_s']:>8.1f} {b['dispatches']:>6} "
              f"{'-':>7} {'-':>8} {'-':>7} {'1.00x':>8} {'-':>6}")
        for t in rec["spec"]:
            print(f"{t['spec_k']:>6} {t['tokens_per_s']:>8.1f} "
                  f"{t['dispatches']:>6} {t['acceptance_rate']:>7.2f} "
                  f"{t['accepted_per_dispatch']:>8.2f} {t['rewinds']:>7} "
                  f"{t['speedup_vs_plain']:>7.2f}x "
                  f"{str(t['outputs_match_baseline']):>6}")
        print(f"best decode throughput: {rec['best_speedup']:.2f}x plain "
              f"decode (same greedy outputs)")
        return

    if args.mode == "mixed":
        rec = run_mixed(cfg, params, max_len=args.max_len,
                        chunk_size=args.chunk_size, burst=args.burst)
        print(f"arch={cfg.name} max_len={args.max_len} "
              f"chunk={args.chunk_size} burst={args.burst}")
        print(f"{'policy':>8} {'ttft_p50':>9} {'ttft_p99':>9} "
              f"{'itl_p50':>9} {'itl_p99':>9} {'tok/s':>8}")
        for key in ("stall_prefill", "mixed"):
            r = rec[key]
            print(f"{r['policy']:>8} {r['ttft_p50_ms']:>8.1f}m "
                  f"{r['ttft_p99_ms']:>8.1f}m {r['itl_p50_ms']:>8.1f}m "
                  f"{r['itl_p99_ms']:>8.1f}m {r['decode_tokens_per_s']:>8.1f}")
        print(f"chunked admission: ttft_p99 {rec['ttft_p99_speedup']:.2f}x, "
              f"itl_p99 {rec['itl_p99_speedup']:.2f}x vs stall-prefill")
        return

    depths = [int(d) for d in args.queue_depths.split(",")]
    batches = [int(b) for b in args.batches.split(",")]
    print(f"arch={cfg.name} max_new={args.max_new_tokens} max_len={args.max_len}")
    print(f"{'queue':>6} {'mode':>14} {'batch':>6} {'tok/s':>9} "
          f"{'steps':>6} {'occup':>6}")
    for depth in depths:
        workload = _workload(cfg, depth, args.max_new_tokens)
        base = bench_per_request(cfg, params, workload, args.max_len)
        print(f"{depth:>6} {'per-request':>14} {1:>6} "
              f"{base['tokens_per_s']:>9.1f} {base['tokens']:>6} {'-':>6}")
        for batch in batches:
            r = bench_batched(cfg, params, workload, batch, args.max_len,
                              chunk_size=args.chunk_size)
            speedup = r["tokens_per_s"] / base["tokens_per_s"]
            print(f"{depth:>6} {'batched':>14} {batch:>6} "
                  f"{r['tokens_per_s']:>9.1f} {r['steps']:>6} "
                  f"{r['occupancy']:>6.2f}  ({speedup:.2f}x vs per-request)")


if __name__ == "__main__":
    main()
