"""Serving launcher: ``python -m repro.launch.serve --arch qwen-7b ...``

Builds a quantized model (the paper's compiler), starts the slot-based
continuous-batching engine and runs a synthetic request workload — the
container-scale stand-in for the paper's LAN client/server deployment.
One jitted decode call advances all slots per step; finished rows are
evicted and refilled from the queue mid-flight.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.compiler import init_quantized, quantized_bytes
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.serving.engine import Engine, Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen-7b")
    ap.add_argument("--strategy", default="strategy2",
                    choices=["none", "dense", "strategy1", "strategy2",
                             "strategy3"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--kv-layout", default="slot", choices=["slot", "paged"],
                    help="paged = shared block pool + per-slot page tables")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="tokens per KV page (paged layout)")
    ap.add_argument("--kv-pool-blocks", type=int, default=0,
                    help="shared-pool blocks (0 = batch * pages per slot)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative decoding: prompt-lookup drafts "
                         "verified through the mixed dispatch")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens per verify row (with --spec)")
    ap.add_argument("--drafter", default="plookup",
                    help="draft proposer registry name (serving/draft.py)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="share cached prompt-prefix KV blocks across "
                         "requests (paged transformer families)")
    ap.add_argument("--system-prompt-len", type=int, default=24,
                    help="shared synthetic system-prompt tokens prepended "
                         "to every request (exercises --prefix-cache)")
    # resilience / lifecycle knobs (ISSUE 8)
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline in seconds after submit; "
                         "expired requests finish status=deadline_missed "
                         "(queued or mid-flight)")
    ap.add_argument("--priority", type=int, default=0,
                    help="priority assigned to every synthetic request "
                         "(higher admits/keeps first under preemption)")
    ap.add_argument("--max-preemptions", type=int, default=0,
                    help="evict-and-requeue bound per request; 0 disables "
                         "preemption (stall-only admission, the old "
                         "behavior).  Preemption is lossless: accepted "
                         "output folds into the prompt and, under "
                         "--prefix-cache, the victim's KV blocks are "
                         "donated so re-admission is a page-table copy")
    ap.add_argument("--audit-every", type=int, default=0,
                    help="run Engine.audit() (allocator partition, "
                         "reservation, page-table coherence) every N "
                         "ticks; 0 disables")
    ap.add_argument("--chaos", action="store_true",
                    help="attach a seeded ChaosMonkey (serving/chaos.py): "
                         "deterministic fault injection into this run")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--chaos-deny-rate", type=float, default=0.05,
                    help="P(reservation denied) per admission attempt")
    ap.add_argument("--chaos-preempt-rate", type=float, default=0.05,
                    help="P(forced preemption) per tick (needs "
                         "--max-preemptions > 0)")
    ap.add_argument("--chaos-nan-rate", type=float, default=0.01,
                    help="P(logits row -> NaN) per advancing row; faulted "
                         "rows quarantine with status=error")
    # durability knobs (ISSUE 9)
    ap.add_argument("--snapshot-dir", default=None,
                    help="durable state root: atomic point-in-time engine "
                         "snapshots plus a write-ahead request journal, "
                         "fsync'd once per tick")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot every N ticks (0 = only the baseline "
                         "snapshot at startup; needs --snapshot-dir)")
    ap.add_argument("--restore", action="store_true",
                    help="recover the engine from --snapshot-dir (latest "
                         "complete snapshot + journal replay) instead of "
                         "starting fresh; in-flight requests resume and no "
                         "new synthetic requests are submitted")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    if args.restore and not args.snapshot_dir:
        ap.error("--restore requires --snapshot-dir")

    enable_compile_cache()
    kv = dict(kv_layout=args.kv_layout, kv_block_size=args.kv_block_size,
              kv_pool_blocks=args.kv_pool_blocks)
    cfg = (get_config(args.arch, **kv) if args.full
           else get_smoke_config(args.arch, **kv))
    key = jax.random.PRNGKey(0)
    if args.strategy == "none":
        params = api.init_params(cfg, key)
    else:
        params = init_quantized(lambda k: api.init_params(cfg, k), key,
                                args.strategy)
    print(f"arch={cfg.name} packed={quantized_bytes(params)/1e6:.1f} MB "
          f"strategy={args.strategy}")

    chaos = None
    if args.chaos:
        from repro.serving.chaos import ChaosConfig, ChaosMonkey
        chaos = ChaosMonkey(ChaosConfig(
            seed=args.chaos_seed, deny_rate=args.chaos_deny_rate,
            preempt_rate=args.chaos_preempt_rate,
            nan_rate=args.chaos_nan_rate))
    if args.restore:
        engine = Engine.restore(args.snapshot_dir, params, chaos=chaos)
        d = engine.durability_stats()
        live = len(engine._queue) + sum(s.req is not None
                                        for s in engine._slots)
        print(f"restored from {args.snapshot_dir} (epoch {d['epoch']}): "
              f"{live} live requests resume, {d['restored_terminal']} "
              f"already terminal replayed from the journal")
    else:
        engine = Engine(cfg, params, batch_size=args.batch,
                        max_len=args.max_len,
                        spec_k=args.spec_k if args.spec else 0,
                        drafter=args.drafter, prefix_cache=args.prefix_cache,
                        max_preemptions=args.max_preemptions,
                        audit_every=args.audit_every, chaos=chaos,
                        snapshot_dir=args.snapshot_dir,
                        snapshot_every=args.snapshot_every)
    if args.spec and not engine.spec_k:
        print(f"speculation requested but family {cfg.family!r} has no "
              "rewindable sequence dimension — plain decode fallback")
    if args.prefix_cache and not engine.prefix_sharing:
        print(f"prefix cache requested but family {cfg.family!r} / layout "
              f"{cfg.kv_layout!r} cannot share KV blocks — running without")
    if not args.restore:
        rng = np.random.default_rng(0)
        system = (rng.integers(0, cfg.vocab_size, args.system_prompt_len)
                  if args.prefix_cache else rng.integers(0, cfg.vocab_size, 0))
        for rid in range(args.requests):
            user = rng.integers(0, cfg.vocab_size, int(rng.integers(4, 32)))
            engine.submit(Request(
                rid=rid,
                prompt=np.concatenate([system, user]).astype(np.int32),
                max_new_tokens=args.max_new_tokens,
                priority=args.priority, deadline_s=args.deadline_s))
    done = engine.run()
    if not done.drained:
        print(f"NOT drained: truncated={done.truncated} "
              f"stalled={done.stalled} in_flight={done.in_flight} "
              f"queued={done.queued}")
    summary = Engine.summarize(done)
    print("summary:", summary)
    r = engine.resilience_stats()
    print(f"resilience: {r['preemptions']} preemptions "
          f"(bound {r['max_preemptions']}/req), "
          f"{r['deadline_misses']} deadline misses, "
          f"{r['row_faults']} quarantined rows, {r['audits']} audits"
          + (f", chaos={r['chaos']}" if chaos is not None else ""))
    if args.snapshot_dir:
        d = engine.durability_stats()
        print(f"durability: {d['snapshots_taken']} snapshots under "
              f"{d['snapshot_dir']} (epoch {d['epoch']}, every "
              f"{d['snapshot_every'] or 'startup-only'} ticks), "
              f"journal={'on' if d['journal'] else 'off'} — recover with "
              f"--restore --snapshot-dir {d['snapshot_dir']}")
    padding = (1 - engine.positions_live / engine.positions_computed
               if engine.positions_computed else 0.0)
    print(f"scheduler: {engine.steps} ticks, {engine.dispatches} dispatches "
          f"(1 per tick, {engine.mixed_ticks} mixed), slot occupancy "
          f"{engine.slot_occupancy:.2f}, padding share {padding:.2f}, "
          f"admission wait p50 {summary.get('queue_wait_p50_s', 0.0):.4f} s "
          f"p99 {summary.get('queue_wait_p99_s', 0.0):.4f} s")
    if engine.spec_k:
        s = engine.spec_stats()
        print(f"speculation: K={s['spec_k']} drafter={args.drafter} — "
              f"{s['accepted_tokens']}/{s['draft_tokens']} drafts accepted "
              f"({s['acceptance_rate']:.2f}), "
              f"{s['accepted_per_dispatch']:.2f} accepted tokens/dispatch "
              f"over {s['spec_ticks']} verify ticks, "
              f"{s['rewinds']} rewinds")
    print(f"compile cache: {sorted(engine.cache_compiles.keys())} "
          f"({engine.cache_compiles.hits} hits, "
          f"misses by kind {engine.cache_compiles.misses_by_name})")
    if engine.paged:
        print(f"paged KV: {engine.pool_blocks} blocks x "
              f"{engine.block_size} tokens, peak resident "
              f"{engine.peak_resident_tokens} tokens, "
              f"{engine.admission_stalls} admission stalls, "
              f"pool {engine.pool_stats()}")
    if engine.prefix_sharing:
        p = engine.prefix_stats()
        print(f"prefix cache: {p['hits']} hits "
              f"({p['hit_tokens']} prompt tokens reused), "
              f"{p['shared_blocks']} shared blocks, "
              f"{p['cow_copies']} CoW copies, "
              f"{p['cached_blocks']} cached, {p['evictions']} evicted")


if __name__ == "__main__":
    main()
