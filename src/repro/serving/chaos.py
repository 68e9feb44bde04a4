"""Fault-injection harness for the serving engine (ISSUE 8).

The engine's resilience claims — preemption is lossless and bounded, a bad
row quarantines without touching its batch-mates, ``sum(reserve) <= free``
and the allocator's refcount partition survive anything — are only worth
stating if they hold under ADVERSARIAL schedules, not just the happy path.
``ChaosMonkey`` injects deterministic, rate-configurable faults at exactly
the host seams the engine defends:

* **reservation denials** (``deny_rate``) — ``_admit_head`` treats a denial
  as a shortfall-with-no-victim: the head stalls a tick.  Exercises the
  stall/retry path and the admission-order bookkeeping under flapping.
* **forced preemptions** (``preempt_rate``) — a random running (and still
  preemptable) slot is evicted-and-requeued at the front.  Exercises the
  donate/fold/re-admit cycle far more often than organic pool pressure
  would.
* **NaN logit rows** (``nan_rate``) — a random advancing row's logits are
  overwritten with NaN host-side, exactly as a device fault would surface.
  The engine must quarantine that row (``status="error"``) and NOT donate
  its blocks.
* **garbage drafts** (``garbage_draft_rate``) — a verify row's draft tokens
  are replaced with random vocab ids of the same length.  Greedy
  verification must reject them and stay bitwise lossless.

Every fault stream is driven by one seeded ``np.random.default_rng`` so a
soak run is REPRODUCIBLE: same seed, same faults, same final state.  The
injection counters (``stats()``) ride along in
``Engine.resilience_stats()``.

``run_soak`` is the acceptance harness: for every family mixture (slot vs
paged, int8-KV, speculation, prefix sharing) it runs a faulted engine with
``audit_every=1`` (allocator/reservation/page-table invariants checked
EVERY tick) and asserts each surviving request's token stream is bitwise
equal to the ``reference_decode`` oracle on its ORIGINAL prompt — faults
may kill a row, they may never corrupt a neighbour.  Runnable directly:

    PYTHONPATH=src python -m repro.serving.chaos --seed 0 --out stats.json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
from typing import Any

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.core.compiler import CompileCache
from repro.models import api
from repro.models.config import ModelConfig
from repro.serving.engine import Engine, Request, reference_decode


@dataclasses.dataclass
class ChaosConfig:
    """Per-seam injection rates (probability per opportunity, in [0, 1])."""
    seed: int = 0
    deny_rate: float = 0.0           # P(reservation denied) per admit try
    preempt_rate: float = 0.0        # P(forced preemption) per tick
    nan_rate: float = 0.0            # P(row -> NaN) per advancing row
    garbage_draft_rate: float = 0.0  # P(draft garbled) per verify row
    kill_rate: float = 0.0           # P(process death) per tick top
    kill_after: int | None = None    # deterministic death at the Nth tick


class EngineKilled(RuntimeError):
    """Simulated process death, raised by ``ChaosMonkey.maybe_kill`` at the
    top of a tick.  Everything the engine had not journaled or snapshotted
    dies with the process; ``Engine.restore`` must recover the rest — the
    kill/restore soak asserts the recovered streams are bitwise the
    never-killed oracle's."""


class ChaosMonkey:
    """Deterministic fault injector the engine consults at its host seams.

    Construct from a ``ChaosConfig`` or keyword rates; attach via
    ``Engine(..., chaos=monkey)``.  All randomness flows from one seeded
    generator, so identical (seed, workload) pairs inject identical faults.
    """

    def __init__(self, config: ChaosConfig | None = None, **rates: Any):
        self.config = config if config is not None else ChaosConfig(**rates)
        self._rng = np.random.default_rng(self.config.seed)
        self.injected = {"denials": 0, "preemptions": 0,
                         "nan_rows": 0, "garbled_drafts": 0, "kills": 0}
        self._ticks_to_kill = self.config.kill_after

    # -- seams (called by Engine.run / Engine._admit_head) -----------------

    def maybe_kill(self) -> None:
        """Once per tick, at the TOP (after the previous tick's journal
        fsync): simulated process death.  Draws from the rng only when
        enabled, so a kill-free monkey's other fault streams are unchanged
        from pre-kill seeds."""
        if self._ticks_to_kill is not None:
            self._ticks_to_kill -= 1
            if self._ticks_to_kill <= 0:
                self._ticks_to_kill = None
                self.injected["kills"] += 1
                raise EngineKilled("chaos: process killed at tick "
                                   f"{self.config.kill_after} (scheduled)")
        if (self.config.kill_rate and
                self._rng.random() < self.config.kill_rate):
            self.injected["kills"] += 1
            raise EngineKilled("chaos: process killed at tick top")

    def deny_reservation(self) -> bool:
        """One admission attempt: True = pretend the pool cannot reserve."""
        if self._rng.random() < self.config.deny_rate:
            self.injected["denials"] += 1
            return True
        return False

    def forced_preempt(self, eligible: list[int]) -> int | None:
        """Once per tick: pick a running slot to evict, or None.  Only
        slots still under their preemption bound are offered."""
        if eligible and self._rng.random() < self.config.preempt_rate:
            self.injected["preemptions"] += 1
            return int(self._rng.choice(eligible))
        return None

    def corrupt_rows(self, advancing: list[int]) -> list[int]:
        """Once per tick: the subset of advancing rows whose logits turn
        NaN this dispatch (independent draw per row)."""
        hit = [i for i in advancing
               if self._rng.random() < self.config.nan_rate]
        self.injected["nan_rows"] += len(hit)
        return hit

    def garble_draft(self, draft: list[int], vocab: int) -> list[int]:
        """Maybe replace one verify row's draft with same-length junk
        (length is load-bearing: the engine sized its leases by it)."""
        if self._rng.random() < self.config.garbage_draft_rate:
            self.injected["garbled_drafts"] += 1
            return self._rng.integers(0, vocab, len(draft)).tolist()
        return draft

    def stats(self) -> dict[str, Any]:
        return {**dataclasses.asdict(self.config), **self.injected}


# -- soak harness ----------------------------------------------------------

# every engine mixture the resilience contract must survive: (label,
# kv_layout, kv_quant, spec_k, prefix_cache, mesh).  The mesh cell forces
# the engine under a device mesh so decode routes through the sequence-
# sharded paged path (block homes, per-home reservations) — on a 1-device
# host it degenerates to a 1-shard shard_map, which still exercises the
# sharded dispatch end to end; CI runs it with 8 forced host devices.
SOAK_CELLS = [
    ("slot",            "slot",  "none", 0, False, False),
    ("paged",           "paged", "none", 0, False, False),
    ("paged-int8",      "paged", "int8", 0, False, False),
    ("paged-spec",      "paged", "none", 3, False, False),
    ("paged-prefix",    "paged", "none", 0, True,  False),
    ("paged-all",       "paged", "int8", 3, True,  False),
    ("paged-mesh",      "paged", "none", 0, True,  True),
]


def _tiny_cfg(kv_layout: str, kv_quant: str,
              mesh: bool = False) -> ModelConfig:
    over = {}
    if kv_layout == "paged":
        # the mesh cell needs pool ROWS (blocks + null) divisible by the
        # shard count, so block homes actually activate: 39 + 1 = 40 rows
        over = {"kv_block_size": 8,
                "kv_pool_blocks": 39 if mesh else 40}
    return get_smoke_config("qwen-7b", d_model=64, d_ff=128, vocab_size=256,
                            kv_layout=kv_layout, kv_quant=kv_quant, **over)


def _mesh_ctx(mesh: bool):
    """The forced-mesh cell's engine context: a (1, n_devices) mesh (the
    oracle always runs OUTSIDE it — parity must be vs the single-device
    reference).  Pool rows not divisible by the device count just means
    ``paged_homes`` returns 1 and the cell degrades to the unsharded path
    — still green, by the balance-not-correctness contract."""
    import contextlib

    from repro.launch.mesh import make_mesh
    from repro.parallel.hints import use_mesh
    if not mesh:
        return contextlib.nullcontext()
    return use_mesh(make_mesh((1, jax.device_count()), ("data", "model")))


# oracle executables close over their cfg, so compile caches are shared
# ONLY within an identical (layout, quant) cell key — same idiom as the
# paged/prefix test suites
_ORACLE_CC: dict[tuple, CompileCache] = {}


def _oracle_cc(key: tuple) -> CompileCache:
    return _ORACLE_CC.setdefault(key, CompileCache())


def run_soak_cell(label: str, kv_layout: str, kv_quant: str,
                  spec_k: int, prefix_cache: bool, mesh: bool = False,
                  *, seed: int = 0,
                  n_requests: int = 10, compile_cache: CompileCache
                  | None = None) -> dict[str, Any]:
    """One soak cell: a faulted engine vs the unfaulted oracle.

    Asserts (1) every request reached a terminal state, (2) every
    ``done`` request's output is bitwise ``reference_decode`` on its
    ORIGINAL prompt, (3) every faulted/expired request's partial output is
    a strict prefix of its oracle stream (the fault cut it short, never
    corrupted it), and (4) the per-tick ``audit_every=1`` invariant checks
    stayed green (they raise otherwise).  Returns the cell's stats.
    """
    rng = np.random.default_rng(seed)
    cfg = _tiny_cfg(kv_layout, kv_quant, mesh)
    params = api.init_params(cfg, jax.random.PRNGKey(seed))
    cc = (compile_cache if compile_cache is not None
          else _oracle_cc((kv_layout, kv_quant, spec_k, mesh)))
    monkey = ChaosMonkey(ChaosConfig(
        seed=seed + 1, deny_rate=0.10, preempt_rate=0.15, nan_rate=0.02,
        garbage_draft_rate=0.5 if spec_k else 0.0))
    max_len = 96

    # oracles run OUTSIDE the mesh context: parity is vs the single-device
    # reference, and the snapshot is taken BEFORE submit (preemption folds
    # output into the prompt)
    shared = rng.integers(0, cfg.vocab_size, 24)   # hot prefix for sharing
    reqs, oracle = [], {}
    for rid in range(n_requests):
        if rid % 3 == 0 and prefix_cache:
            prompt = np.concatenate(
                [shared, rng.integers(0, cfg.vocab_size, rng.integers(2, 9))])
        else:
            prompt = rng.integers(0, cfg.vocab_size, rng.integers(4, 33))
        r = Request(rid=rid, prompt=prompt.astype(np.int64),
                    max_new_tokens=int(rng.integers(4, 13)))
        oracle[rid] = reference_decode(cfg, params, prompt,
                                       r.max_new_tokens, max_len=max_len,
                                       compile_cache=cc)
        reqs.append(r)

    with _mesh_ctx(mesh):
        engine = Engine(cfg, params, batch_size=4, max_len=max_len,
                        chunk_size=16, prefill_token_budget=32,
                        spec_k=spec_k, prefix_cache=prefix_cache,
                        max_preemptions=2, audit_every=1, chaos=monkey,
                        compile_cache=cc)
        for r in reqs:
            engine.submit(r)
        done = engine.run(max_steps=4000)
    assert done.drained, (
        f"{label}: soak did not drain (truncated={done.truncated} "
        f"stalled={done.stalled} in_flight={done.in_flight})")
    engine.audit()                       # one final full audit
    outcomes: dict[str, int] = {}
    for r in reqs:
        assert r.done and r.status in ("done", "error"), (
            f"{label}: rid {r.rid} not terminal: {r.status}")
        outcomes[r.status] = outcomes.get(r.status, 0) + 1
        ref = oracle[r.rid]
        if r.status == "done":
            assert r.output == ref, (
                f"{label}: rid {r.rid} (preempted {r.preemptions}x) "
                f"diverged from oracle:\n  got {r.output}\n  ref {ref}")
        else:   # faulted: output up to the fault must still be the oracle's
            assert r.output == ref[:len(r.output)], (
                f"{label}: faulted rid {r.rid} corrupted before its fault")
        assert r.preemptions <= 2, f"{label}: preemption bound violated"
    if kv_layout == "paged":
        assert engine.alloc.n_free == engine.pool_blocks - (
            len(engine.prefix.blocks()) if engine.prefix is not None else 0), (
            f"{label}: leaked blocks after drain")
    return {"cell": label, "outcomes": outcomes,
            "n_homes": getattr(engine, "n_homes", 1),
            **engine.resilience_stats()}


def run_soak(seed: int = 0, n_requests: int = 10) -> list[dict[str, Any]]:
    """All cells; compile caches are shared per (layout, quant, spec) key —
    executables bake their cfg in, so cross-cfg sharing would be wrong."""
    return [run_soak_cell(*cell, seed=seed, n_requests=n_requests)
            for cell in SOAK_CELLS]


# -- kill/restore soak (ISSUE 9) --------------------------------------------

def run_restart_cell(label: str, kv_layout: str, kv_quant: str,
                     spec_k: int, prefix_cache: bool, mesh: bool = False,
                     *, seed: int = 0,
                     n_requests: int = 10,
                     max_lives: int = 12) -> dict[str, Any]:
    """One kill/restore cell: the full fault mix PLUS seeded process kills.

    The engine runs with snapshots + write-ahead journal; every
    ``EngineKilled`` abandons the live engine (the in-process stand-in for
    a dead process) and a fresh ``Engine.restore`` picks up from disk.
    Asserts the DURABLE record (``snapshot.journaled_streams`` across every
    journal epoch): each request reaches a terminal state exactly once, a
    ``done`` stream is bitwise ``reference_decode`` on the ORIGINAL prompt,
    a faulted stream is a strict prefix of it, ``audit()`` is green on the
    final engine, and no pool block leaked across any restart boundary.
    After ``max_lives`` deaths the monkey stops killing so the soak always
    drains."""
    import shutil
    import tempfile

    from repro.serving import snapshot as snaplib

    rng = np.random.default_rng(seed)
    cfg = _tiny_cfg(kv_layout, kv_quant, mesh)
    params = api.init_params(cfg, jax.random.PRNGKey(seed))
    cc = _oracle_cc((kv_layout, kv_quant, spec_k, mesh))

    def monkey(life: int) -> ChaosMonkey:
        # Life 0 dies DETERMINISTICALLY at tick 7 — one tick past the first
        # periodic snapshot (every 6), so recovery always exercises snapshot
        # + journal-tail replay regardless of seed.  Later lives die
        # probabilistically until ``max_lives`` caps the soak.
        return ChaosMonkey(ChaosConfig(
            seed=seed + 100 + life, deny_rate=0.05, preempt_rate=0.10,
            nan_rate=0.02, garbage_draft_rate=0.5 if spec_k else 0.0,
            kill_after=7 if life == 0 else None,
            kill_rate=0.08 if 0 < life < max_lives else 0.0))

    max_len = 96
    workdir = tempfile.mkdtemp(prefix=f"restart_{label}_")

    shared = rng.integers(0, cfg.vocab_size, 24)   # hot prefix for sharing
    reqs, oracle = [], {}
    for rid in range(n_requests):
        if rid % 3 == 0 and prefix_cache:
            prompt = np.concatenate(
                [shared, rng.integers(0, cfg.vocab_size, rng.integers(2, 9))])
        else:
            prompt = rng.integers(0, cfg.vocab_size, rng.integers(4, 33))
        r = Request(rid=rid, prompt=prompt.astype(np.int64),
                    max_new_tokens=int(rng.integers(4, 13)))
        oracle[rid] = reference_decode(cfg, params, prompt,
                                       r.max_new_tokens, max_len=max_len,
                                       compile_cache=cc)
        reqs.append(r)

    # restores happen INSIDE the mesh context too: a snapshot taken under a
    # mesh records its home count, and the restoring engine must derive the
    # same one (snapshot._load_host enforces it)
    with _mesh_ctx(mesh):
        engine = Engine(cfg, params, batch_size=4, max_len=max_len,
                        chunk_size=16, prefill_token_budget=32,
                        spec_k=spec_k, prefix_cache=prefix_cache,
                        max_preemptions=2, audit_every=1, chaos=monkey(0),
                        compile_cache=cc,
                        snapshot_dir=workdir, snapshot_every=6)
        for r in reqs:
            engine.submit(r)

        lives = 1
        while True:
            try:
                res = engine.run(max_steps=4000)
                break
            except EngineKilled:
                # the killed engine object is abandoned wholesale — the
                # restore may only consult what reached disk
                engine = Engine.restore(workdir, params,
                                        chaos=monkey(lives),
                                        compile_cache=cc)
                lives += 1
    assert res.drained, (
        f"{label}: restart soak did not drain (truncated={res.truncated} "
        f"stalled={res.stalled} in_flight={res.in_flight})")
    engine.audit()
    kills = lives - 1
    assert kills >= 1, (
        f"{label}: no kill fired — raise kill_rate or max_steps")

    streams, status = snaplib.journaled_streams(workdir)
    outcomes: dict[str, int] = {}
    for rid in range(n_requests):
        st = status.get(rid)
        assert st in ("done", "error"), (
            f"{label}: rid {rid} durable status {st!r} not terminal")
        outcomes[st] = outcomes.get(st, 0) + 1
        ref = oracle[rid]
        got = streams.get(rid, [])
        if st == "done":
            assert got == ref, (
                f"{label}: rid {rid} durable stream diverged across "
                f"{kills} restart(s):\n  got {got}\n  ref {ref}")
        else:   # faulted: the stream up to the fault is still the oracle's
            assert got == ref[:len(got)], (
                f"{label}: faulted rid {rid} corrupted before its fault")
    if kv_layout == "paged":
        assert engine.alloc.n_free == engine.pool_blocks - (
            len(engine.prefix.blocks()) if engine.prefix is not None else 0), (
            f"{label}: leaked blocks across the restart boundary")
    stats = {"cell": label, "lives": lives, "kills": kills,
             "snapshots_taken": engine.snapshots_taken,
             "outcomes": outcomes, "n_homes": getattr(engine, "n_homes", 1),
             **engine.resilience_stats()}
    shutil.rmtree(workdir, ignore_errors=True)
    return stats


def run_restart_soak(seed: int = 0,
                     n_requests: int = 10) -> list[dict[str, Any]]:
    """Kill/restore chaos across all six engine mixtures."""
    return [run_restart_cell(*cell, seed=seed, n_requests=n_requests)
            for cell in SOAK_CELLS]


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--n-requests", type=int, default=10)
    p.add_argument("--restart", action="store_true",
                   help="run the kill/restore soak (snapshots + journal + "
                        "seeded process kills) instead of the in-process one")
    p.add_argument("--out", default=None,
                   help="write per-cell stats JSON here (CI artifact)")
    args = p.parse_args()
    soak = run_restart_soak if args.restart else run_soak
    stats = soak(seed=args.seed, n_requests=args.n_requests)
    for s in stats:
        print(json.dumps(s))
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"seed": args.seed, "cells": stats}, f, indent=2)
        print(f"wrote {args.out}")
    kind = "kill/restore" if args.restart else "chaos"
    print(f"{kind} soak OK: {len(stats)} cells green")


if __name__ == "__main__":
    main()
