"""Serving engine: chunked-prefill continuous batching over one slot cache
(EdgeLLM §IV-B, Fig. 9 — plus the §IV "one data shape for every operator"
contract applied to admission).

The paper keeps the FPGA saturated by giving every operator the same data
shape so one fixed executable processes any token stream.  The seed engine
broke that contract at admission time: each new prompt ran a *separate*
batch-1 bucketed prefill that head-of-line-blocked every live decode slot
for the whole prompt.  This engine fuses admission into the per-step decode
dispatch instead:

* **One resident cache.**  ``api.init_cache(cfg, B, max_len)`` allocates a
  single slot-based cache (KV: ``(layers, B, heads, L, hd)``; recurrent
  families: per-row state) that lives on device for the engine's lifetime.
  Requests do not own cache pytrees — they *lease a slot*.

* **One mixed-batch dispatch per tick.**  ``api.mixed_step`` advances ALL
  ``B`` slots in a single jitted call; row ``b`` advances by ``q_lens[b]``
  tokens — 1 for a decoding row, up to C (the chunk bucket) for a row
  mid-prefill.  Prompts are split into chunk-bucket pieces (Sarathi-style
  token budget) and co-scheduled with decode rows, so admission costs ZERO
  extra dispatches and decode rows never stall behind a long prompt.  Ticks
  with no prefill work degrade to the classic ``api.decode_step``
  executable — bit-identical to the batch-1 oracle.

* **True-length accounting.**  Slots track the request's TRUE token count
  (not a padded bucket): K/V land at the row's real positions
  (``dynamic_update_slice`` at its current length — no left-pad writes),
  decode never attends over pad tokens, and cache room is measured exactly
  — a prompt is admissible whenever ``len(prompt) <= max_len``.

* **True recurrent prefill.**  Chunks run the prompt *through the
  cache-updating step path*, so ssm/hybrid slots hold the REAL post-prompt
  recurrent state (the old forward-as-prefill gap is closed); admission
  first resets the leased slot via ``api.request_cache`` +
  ``insert_request`` for the families that need it (recurrent state, audio
  cross-KV).

* **Continuous batching.**  Finished rows are retired mid-flight and
  immediately refilled from the queue; the batch never drains to restart.

* **Bounded compilation.**  Executables memoize in ``CompileCache`` under
  ``("mixed", W)`` (one per chunk-width bucket W), ``("decode", B)`` and
  ``("insert", B)`` — misses are bounded by ``n_chunk_buckets + 2``
  regardless of traffic (audio adds one ``("admit", F)`` encoder
  executable), the XLA analogue of the paper's per-token-length instruction
  streams with a MAX-token address space.

* **Speculative decoding (``spec_k > 0``).**  Decode is bandwidth-bound:
  every tick streams the whole weight set to advance each row by one token.
  A model-free prompt-lookup drafter (``serving/draft.py``) proposes up to
  K continuation tokens per decode row from the row's own token history;
  the engine packs ``[last_token, d_1..d_K]`` as a ``q_lens[b] = K+1``
  chunk into the SAME ``("mixed", W)`` dispatch (verify rows co-scheduled
  with decode rows and mid-prefill chunks under one token budget — zero
  new executable shapes), and ``mixed_step(all_logits=True)`` returns
  every position's greedy token so acceptance — the longest draft prefix
  agreeing with the model's own greedy choices — costs zero extra device
  round-trips.  Accepted tokens emit ``a + 1`` per dispatch (the ``+1`` is
  the model's token at the first disagreement, so every verify tick
  emits at least what plain decode would); the rejected tail is rolled
  back host-side by ``_rewind_slot`` — ``lengths[b]`` shrinks (stale K/V
  past it hides behind true-length masking) and, under paging, wholly
  dead tail blocks are re-nulled in the table and returned to the free
  list.  Greedy acceptance is LOSSLESS: outputs are token-for-token the
  ``reference_decode`` oracle's, speculation only changes how many
  dispatches they take.  Families without a rewindable sequence dimension
  (ssm/hybrid recurrent state) fail ``api.supports_speculation`` and fall
  back to plain decode; a ``sample`` hook disables speculation for the
  call (acceptance is defined against greedy).  A verify row that fully
  rejects still costs a W-wide tick, so drafting is ADAPTIVE: a slot whose
  drafts keep missing backs off exponentially (skipping drafting for 1, 2,
  4, ... up to ``_DRAFT_BACKOFF_MAX`` ticks) and any accepted token resets
  it — cold rows decode plainly, repetitive rows speculate at full depth.

* **Paged KV (``cfg.kv_layout == "paged"``).**  KV leaves become ONE shared
  block pool; each slot addresses it through a row of the HOST-side page
  table, which rides into every dispatch as a plain operand (the dispatch
  shapes — and so the executable set — are unchanged: the paper's
  one-data-shape contract survives paging).  Allocation is on-demand: a row
  leases a block when its length crosses a block boundary, and retirement
  returns the row's blocks to the free list.  Admission reserves each
  request's WORST-CASE block count (``ceil(min(len + max_new, max_len) /
  bs)``) up front — a request is only admitted when the unreserved free
  blocks cover it, so a live row can always lease its next block and the
  pool can never deadlock; requests held back by reservation count as
  ``admission_stalls``.  Because slots no longer pin ``max_len`` rows each,
  ``batch_size`` may exceed ``pool_tokens / max_len`` — short requests stop
  paying for long ones, which is the capacity lever
  ``benchmarks/serving_bench.py --paged-capacity`` measures.

* **Prefix sharing (``prefix_cache=True``, paged transformer families).**
  The free list becomes a refcounted ``BlockAllocator`` and a host-side
  ``RadixPrefixCache`` maps block-aligned prompt prefixes to the physical
  blocks that already hold their K/V (``serving/prefix.py``).  Admission of
  a request whose prompt walks a cached path is a PAGE-TABLE COPY: the
  shared blocks are increfed into the slot's table rows and only the
  uncovered suffix streams through chunked prefill — no new executables and
  no kernel changes, because the page table already rides in as a plain
  operand and kernels only ever READ through it.  Serving writes are
  append-only, so copy-on-write fires at most once per admission: when the
  suffix starts mid-block, the engine leases a fresh block, duplicates the
  shared one on device (the single ``("cow", 0)`` executable) and overwrites
  its tail through the normal chunk writer.  Worst-case reservation shrinks
  by the shared block count (the CoW page leases normally, so the "+1 CoW
  block" stays inside the reservation) and ``sum(reserve) <= free`` stays
  the deadlock-free invariant.  A finished prompt donates its fully-written
  blocks back to the cache (one cache-held reference each), so hot system
  prompts stay resident after their first author retires; under pool
  pressure, admission evicts cold cache leaves LRU-first — but only blocks
  the cache is the SOLE holder of, so shared residents are evicted last.
  Sharing is exact: ``mixed_step`` is bitwise equal to sequential decode,
  so cached K/V is bit-identical to a recompute and token streams match the
  cache-OFF engine and ``reference_decode`` token for token.

* **Resilience: lifecycle, preemption, fault quarantine.**  Every request
  walks an explicit state machine — ``queued -> running -> {done, error,
  cancelled, deadline_missed}`` (preemption loops a running request back to
  ``queued``) — and pool pressure has a second answer beyond admission
  stalls: with ``max_preemptions > 0``, a FIFO head that cannot reserve
  (after LRU prefix eviction already ran) PREEMPTS the youngest /
  lowest-priority running slot.  Preemption is lossless and cheap: the
  victim's fully-written blocks are donated to the radix prefix cache
  (prompt AND accepted output — so re-admission is mostly a page-table
  copy), its accepted output is folded into its prompt, and it requeues
  just behind the head; the slot layout falls back to plain
  evict-and-recompute.  Each request is preempted at most
  ``max_preemptions`` times, then becomes immune — so admission-triggered
  eviction can never starve anyone.  ``deadline_s`` requests are swept
  every tick (queued or running) once ``enforce_deadlines`` is on, and
  ``cancel(rid)`` retires a request at any point in the lifecycle.  Faults
  stay inside their row: non-finite logits (``check_finite``: each step
  returns a (B,) finiteness flag computed on the device and read with the
  tokens, so only a ``sample`` hook's ticks copy logits to the host) and a
  throwing ``sample`` hook quarantine ONLY the offending slot (terminal
  ``status="error"``, blocks freed, allocator invariants intact) instead of
  propagating out of the tick — and a poisoned row's blocks are never
  donated to the prefix cache.  ``audit_every=N`` self-checks the
  allocator partition, reservation invariant and page-table/ownership
  coherence every N ticks; ``serving/chaos.py`` injects deterministic
  faults (reservation denials, forced preemptions, NaN rows, garbage
  drafts) against exactly these seams.  ``run()`` returns a ``RunResult``
  (a list) whose ``truncated``/``in_flight``/``queued`` fields make a
  ``max_steps`` budget hit explicit instead of silently dropping work.

* **Durability (``snapshot_dir=``, ``serving/snapshot.py``).**  Process
  death is a routine edge operating condition, so serving state is
  persistable: atomic point-in-time snapshots (device KV pool + the full
  host control plane — slots, page tables, allocator refcounts, radix
  cache, request lifecycle fields with deadlines as REMAINING budget,
  drafter history, compile keys for warm re-jit) plus an append-only
  write-ahead journal of submit/emit/terminal events, fsync'd once per
  tick.  ``Engine.restore(dir, params)`` loads the latest complete
  snapshot, replays the journal — post-snapshot output re-folds into
  prompts via the ``_fold_slot`` preemption primitive, so re-admission is
  mostly prefix-cache page-table copies — and resumes with token streams
  BITWISE equal to the never-killed engine's.  A snapshot interrupted
  mid-write is never observed (the previous complete one wins), and the
  injectable ``clock`` keeps restored deadlines counting down from what
  was left, not from a dead process's monotonic base.
"""

from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.compiler import CompileCache, TokenBuckets
from repro.models import api
from repro.models.config import ModelConfig
from repro.serving.prefix import BlockAllocator, RadixPrefixCache


@dataclasses.dataclass
class _PrefixPlan:
    """Host-side admission plan from a radix-cache hit (see module doc).

    ``shared`` blocks map read-only into the slot's page table (one incref
    each); ``cow`` is the one cached block whose matched HEAD is reused via
    copy-on-write (None when the suffix starts block-aligned); ``consumed``
    prompt tokens are covered without recompute — always < len(prompt), so
    at least one prompt token runs and produces the first-token logits."""
    shared: list[int]
    cow: int | None
    consumed: int


# request lifecycle: queued -> running -> one terminal state (preemption
# loops running back to queued; ``done`` stays True exactly on terminals)
TERMINAL_STATES = ("done", "error", "cancelled", "deadline_missed")

# host spans of one pass of ``Engine.run``'s loop, parent first, then its
# children in the order they run; fetch_logits and journal appear only in
# ticks that copy logits to the host (a ``sample`` hook is set) or touch
# audit, journal or snapshot.
# They are ``jax.profiler.TraceAnnotation``s: on the profiler's clock, in
# the same trace as the device's ops, while a trace runs, and about a
# microsecond each when none does.  ``engine.tick`` carries ``kind``
# (mixed/decode), ``w`` (the bucket width, 1 for decode), ``live`` (rows
# holding a request) and ``live_positions`` (positions that advance a row).
SPANS = ("engine.tick", "engine.admit", "engine.plan", "engine.prepare",
         "engine.dispatch", "engine.sync", "engine.fetch_logits",
         "engine.consume", "engine.journal")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray               # (len,) int32
    max_new_tokens: int = 32
    frames: np.ndarray | None = None  # (F, d) audio family only
    priority: int = 0                # higher = admitted/kept first
    deadline_s: float | None = None  # seconds after submit; None = no deadline
    # filled by the engine:
    output: list = dataclasses.field(default_factory=list)
    done: bool = False
    status: str = "queued"           # queued|running|done|error|cancelled|
    #                                  deadline_missed
    error: str | None = None         # quarantine reason when status=="error"
    preemptions: int = 0             # times evicted-and-requeued (bounded)
    folded: int = 0                  # output tokens already folded into
    #                                  prompt by earlier preemptions
    submitted_at: float = 0.0
    admitted_at: float | None = None  # first admission (kept on preemption)
    first_token_at: float | None = None
    finished_at: float | None = None
    token_times: list = dataclasses.field(default_factory=list)


class RunResult(list):
    """``Engine.run``'s return value: the requests that reached a terminal
    state during the call (a plain list, for compatibility), plus the drain
    state — ``truncated`` is True when ``max_steps`` ran out with work still
    queued or in flight (the budget hit is explicit, never silent),
    ``stalled`` when the queue is non-empty but nothing could be admitted
    and no row is live (permanent starvation signature: call again after
    freeing resources)."""

    def __init__(self, reqs=(), *, truncated: bool = False,
                 in_flight: int = 0, queued: int = 0, stalled: bool = False):
        super().__init__(reqs)
        self.truncated = truncated
        self.in_flight = in_flight
        self.queued = queued
        self.stalled = stalled

    @property
    def drained(self) -> bool:
        """True when no work remains anywhere in the engine."""
        return not (self.truncated or self.stalled or
                    self.in_flight or self.queued)


@dataclasses.dataclass
class _Slot:
    """Host-side mirror of one row of the resident cache."""
    req: Request | None = None
    length: int = 0                  # TRUE tokens resident in this row
    pos: int = 0                     # prompt tokens consumed (chunk cursor)
    last_token: int = 0              # input token for the next decode step
    seq: int = 0                     # admission order (preemption picks the
    #                                  youngest = largest seq first)

    @property
    def prefilling(self) -> bool:
        return self.req is not None and self.pos < len(self.req.prompt)


# each executable's function has its own name, which jit gives the module
# (``jit_mixed_step``, ...), so a profiler trace says which step ran.  Every
# step returns (next token, logits, cache[, greedy], finite): ``finite`` (B,)
# says whether each row's logits are all finite, so the host reads one bool
# a row for ``check_finite`` instead of copying the (B, vocab) logits.
def _finite(logits):
    return jnp.all(jnp.isfinite(logits), axis=-1)


def _mixed_executable(cfg: ModelConfig):
    def mixed_step(p, c, tokens, lengths, q_lens):
        logits, new_c = api.mixed_step(cfg, p, c, tokens, lengths, q_lens)
        return jnp.argmax(logits, axis=-1), logits, new_c, _finite(logits)
    return jax.jit(mixed_step, donate_argnums=(1,))


def _mixed_executable_paged(cfg: ModelConfig):
    def mixed_step(p, c, tokens, lengths, q_lens, page_table):
        logits, new_c = api.mixed_step(cfg, p, c, tokens, lengths, q_lens,
                                       page_table=page_table)
        return jnp.argmax(logits, axis=-1), logits, new_c, _finite(logits)
    return jax.jit(mixed_step, donate_argnums=(1,))


# adaptive-speculation cap: a slot whose drafts keep fully rejecting sits
# out 1, 2, 4, ... up to this many ticks before drafting again
_DRAFT_BACKOFF_MAX = 8


def _mixed_executable_spec(cfg: ModelConfig, paged: bool):
    """Verify-capable mixed tick: ``all_logits=True`` scores every chunk
    position, the per-position greedy tokens (B, C) come back for host-side
    draft acceptance, and the last-live-position logits keep the ``sample``
    hook's contract.  A speculating engine uses this variant for ALL its
    mixed ticks, so keys stay exactly ``("mixed", W)`` — the price is
    unembedding W positions instead of 1 on chunked-prefill ticks, which is
    what buys verify ticks their K-fold weight-stream amortization."""
    def mixed_step_spec(p, c, tokens, lengths, q_lens, page_table=None):
        kw = {"page_table": page_table} if paged else {}
        logits, new_c = api.mixed_step(cfg, p, c, tokens, lengths, q_lens,
                                       all_logits=True, **kw)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # (B, C)
        idx = jnp.clip(q_lens - 1, 0, tokens.shape[1] - 1)
        next_tok = jnp.take_along_axis(greedy, idx[:, None], axis=1)[:, 0]
        last_logits = jnp.take_along_axis(
            logits, idx[:, None, None], axis=1)[:, 0]
        return next_tok, last_logits, new_c, greedy, _finite(last_logits)
    return jax.jit(mixed_step_spec, donate_argnums=(1,))


def _decode_executable(cfg: ModelConfig):
    def decode_step(p, c, tokens, lengths):
        logits, new_c = api.decode_step(cfg, p, c, tokens, lengths)
        return jnp.argmax(logits, axis=-1), logits, new_c, _finite(logits)
    return jax.jit(decode_step, donate_argnums=(1,))


def _decode_executable_paged(cfg: ModelConfig):
    # write_mask keeps non-advancing rows (retired slots riding along, rows
    # between ticks) from writing through a stale/parked page table entry —
    # the paged replacement for "stale rows hide behind true-length masking"
    def decode_step(p, c, tokens, lengths, page_table, write_mask):
        logits, new_c = api.decode_step(cfg, p, c, tokens, lengths,
                                        page_table=page_table,
                                        write_mask=write_mask)
        return jnp.argmax(logits, axis=-1), logits, new_c, _finite(logits)
    return jax.jit(decode_step, donate_argnums=(1,))


def _insert_executable(cfg: ModelConfig):
    def insert_row(c, row, slot):
        return api.insert_request(cfg, c, row, slot)
    # donate the resident cache: the engine rebinds it on every call, so XLA
    # may update the slot in place instead of copying the whole cache
    return jax.jit(insert_row, donate_argnums=(0,))


def _admit_executable(cfg: ModelConfig, max_len: int):
    def admit_row(p, frames):
        return api.request_cache(cfg, p, {"frames": frames}, max_len)
    return jax.jit(admit_row)


class Engine:
    """Continuous-batching engine: one mixed-batch dispatch per tick."""

    def __init__(self, cfg: ModelConfig, params: Any, *, batch_size: int = 4,
                 max_len: int = 512, eos_id: int | None = None,
                 chunk_size: int = 64,
                 prefill_token_budget: int | None = None,
                 prefill_policy: str = "mixed",
                 spec_k: int = 0, drafter: Any = "plookup",
                 prefix_cache: bool = False,
                 max_preemptions: int = 0,
                 enforce_deadlines: bool = True,
                 check_finite: bool = True,
                 audit_every: int = 0,
                 chaos: Any = None,
                 compile_cache: CompileCache | None = None,
                 clock: Callable[[], float] = time.monotonic,
                 snapshot_dir: str | None = None,
                 snapshot_every: int = 0,
                 snapshot_keep: int = 2,
                 journal: bool = True):
        if prefill_policy not in ("mixed", "stall"):
            raise ValueError(f"unknown prefill_policy {prefill_policy!r}")
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if max_preemptions < 0:
            raise ValueError(
                f"max_preemptions must be >= 0, got {max_preemptions}")
        self.cfg = cfg
        self.params = params
        self.batch = batch_size
        self.max_len = max_len
        self.eos_id = eos_id
        # >= 2 so a mixed tick never takes mixed_step's C == 1 decode
        # delegation (that path assumes every row advances by one token)
        self.chunk_size = max(2, min(chunk_size, max_len))
        self.prefill_token_budget = prefill_token_budget
        self.prefill_policy = prefill_policy
        # speculative decoding: drafts ride the mixed dispatch as K+1-token
        # chunks, so K is capped by the chunk width (and one slot of cache
        # room for the mandatory real token).  Families without a rewindable
        # sequence dimension cleanly fall back to plain decode (spec_k -> 0;
        # the request is recorded so callers can see the gate fired).
        self.spec_requested = spec_k
        self.spec_supported = api.supports_speculation(cfg)
        self.spec_k = (min(spec_k, self.chunk_size - 1)
                       if spec_k and self.spec_supported else 0)
        # chunk widths are bucketed so executables stay bounded: a tick's
        # dispatch width W is the smallest bucket covering its largest chunk.
        # A speculating engine keeps FINER buckets: verify ticks are only
        # K+1 wide, and padding a 3-wide verify tick to the full chunk width
        # costs more than the dispatch it saves — same ("mixed", W) key
        # family either way, and compile_budget counts all_buckets().
        self.chunk_buckets = TokenBuckets(
            max_tokens=self.chunk_size,
            min_bucket=min(4 if self.spec_k else 16, self.chunk_size))
        if self.spec_k:
            from repro.serving.draft import make_drafter
            self.drafter = (make_drafter(drafter)
                            if isinstance(drafter, str) else drafter)
        else:
            self.drafter = None
        self.spec_ticks = 0        # dispatches carrying >= 1 verify row
        self.spec_rows = 0         # verify rows dispatched
        self.spec_drafted = 0      # draft tokens scored
        self.spec_accepted = 0     # draft tokens accepted
        self.spec_rewinds = 0      # partial/full rejections rolled back
        # adaptive speculation: per-slot exponential backoff after fully
        # rejected drafts (a miss still costs a W-wide verify tick)
        self._draft_wait = [0] * batch_size      # ticks left to sit out
        self._draft_penalty = [0] * batch_size   # current backoff length
        # a shared compile cache must come from an engine with the same
        # (cfg, max_len, batch, chunk_size, spec on/off): executables bake
        # these in — a speculating engine's mixed executables return the
        # per-position greedy tokens, a plain engine's do not.  (`is not
        # None`, not `or`: an EMPTY CompileCache is falsy via __len__, and
        # silently replacing a caller's fresh cache means every engine
        # recompiles privately and the shared cache never warms.)
        self.cache_compiles = (compile_cache if compile_cache is not None
                               else CompileCache())
        self._queue: "collections.deque[Request]" = collections.deque()
        # the resident slot cache (pure-KV slots are reset lazily — stale
        # rows hide behind true-length masking; stateful families are reset
        # at admission via insert_request)
        self.cache = api.init_cache(cfg, batch_size, max_len)
        self._slots = [_Slot() for _ in range(batch_size)]
        # paged-KV bookkeeping: host free list + page table (see module doc)
        self.paged = api.has_paged_kv(cfg)
        # batch-1 admission rows: their paged pool leaves are SKIPPED by
        # insert_request (axis -1), so build them from a minimal-pool cfg —
        # otherwise a stateful paged engine would hold a dead duplicate of
        # the whole serving pool for its lifetime
        self._row_cfg = (dataclasses.replace(cfg, kv_pool_blocks=1)
                         if self.paged else cfg)
        # pristine batch-1 row for stateful-family admission resets
        self._fresh_row = (api.init_cache(self._row_cfg, 1, max_len)
                           if api.needs_admission_insert(cfg) and
                           cfg.family != "audio" else None)
        if self.paged:
            from repro.models.attention import (paged_geometry,
                                                paged_pool_blocks)
            from repro.parallel import decode_attn
            from repro.parallel.hints import active_mesh
            self.block_size, self.n_pages = paged_geometry(cfg, max_len)
            self.pool_blocks = paged_pool_blocks(cfg, batch_size, max_len)
            self._null_block = self.pool_blocks      # last pool row
            # topology-aware allocation: when decode will run through the
            # sharded paged path (a mesh is active at construction), the
            # pool rows split into per-shard block homes and the allocator
            # leases round-robin across them — paged_homes is the ONE
            # function both this ctor and the dispatch gate derive from,
            # so host accounting and device routing cannot disagree
            self.n_homes = decode_attn.paged_homes(
                active_mesh(), batch_size, self.pool_blocks + 1,
                window=cfg.window)
            self.alloc = BlockAllocator(self.pool_blocks, self.n_homes)
            self._page_table = np.full((batch_size, self.n_pages),
                                       self._null_block, np.int32)
            self._slot_blocks: list[list[int]] = [[] for _ in
                                                  range(batch_size)]
            self._slot_reserve = [0] * batch_size    # worst-case not-yet-leased
            # per-home split of each slot's reservation (row sums equal
            # _slot_reserve): the deadlock-freedom invariant holds PER
            # home — sum over slots of _reserve_home[:, h] <= free blocks
            # in home h — so a row can always lease its next block from a
            # home it reserved in, whatever the other homes' pressure
            self._reserve_home = [[0] * self.n_homes
                                  for _ in range(batch_size)]
        # prefix sharing: radix cache over prompt tokens -> physical blocks.
        # Gated to paged transformer families: recurrent state (ssm/hybrid)
        # has no per-token block chain, and audio decoder K/V depends on the
        # request's encoder output through cross-attention, so token-prefix
        # equality does not imply K/V equality there.
        self.prefix_requested = prefix_cache
        self.prefix_sharing = bool(prefix_cache and self.paged and
                                   api.supports_prefix_cache(cfg))
        self.prefix = (RadixPrefixCache(self.block_size)
                       if self.prefix_sharing else None)
        self.prefix_hits = 0         # admissions that reused >= 1 block
        self.prefix_hit_tokens = 0   # prompt tokens covered without recompute
        self.cow_copies = 0          # copy-on-write block duplications
        self.prefix_evictions = 0    # cache leaves dropped under pool pressure
        self.peak_pool_blocks = 0    # high-water physical blocks in use
        self.admission_stalls = 0    # admissions held back by the block pool
        self.peak_resident_tokens = 0
        self.steps = 0
        self.dispatches = 0          # must equal steps: one dispatch per tick
        self.mixed_ticks = 0
        self.logits_fetches = 0      # ticks that copied logits to the host
        # positions the step computed (batch x bucket width, or batch in a
        # decode tick) and those that advanced a row: their ratio sizes the
        # padding of the one-data-shape dispatch
        self.positions_computed = 0
        self.positions_live = 0
        self._occupancy_sum = 0.0
        # -- resilience layer (lifecycle, preemption, fault isolation) -------
        # max_preemptions bounds how many times ONE request may be evicted
        # and requeued (0 disables preemption — the seed's stall-only
        # behavior); a request at the bound is immune, so progress is
        # guaranteed.  enforce_deadlines sweeps deadline_s requests (queued
        # or running) every tick; check_finite quarantines rows whose logits
        # go non-finite (the step's per-row flag, computed on the device and
        # fetched with the tokens: no logits copy); audit_every=N self-checks
        # allocator/page-table invariants every N ticks; chaos is a
        # serving.chaos.ChaosMonkey injecting deterministic faults at exactly
        # these seams.
        self.max_preemptions = max_preemptions
        self.enforce_deadlines = enforce_deadlines
        self.check_finite = check_finite
        self.audit_every = audit_every
        self.chaos = chaos
        self.preemptions = 0         # total preempt-and-requeue events
        self.deadline_misses = 0     # requests retired past their deadline
        self.row_faults = 0          # rows quarantined (NaN logits / hook)
        self.cancels = 0             # cancel() calls that found their target
        self.audits = 0              # audit() passes run (all green)
        self._admit_seq = 0          # monotonic admission counter (slot age)
        self._live_rids: set = set() # queued + running rids (duplicate gate)
        # -- durability layer (snapshots + write-ahead journal) --------------
        # clock is injectable so lifecycle tests exercise nonzero deadlines
        # deterministically and snapshots serialize deadlines as REMAINING
        # budget (a restored engine's clock has a different monotonic base)
        self.clock = clock
        self.snapshot_dir = snapshot_dir
        self.snapshot_every = snapshot_every
        self.snapshot_keep = snapshot_keep
        self.journal_enabled = journal
        self.snapshots_taken = 0
        # terminal events replayed from the journal at restore (requests
        # that finished after the last snapshot in the killed process; the
        # caller's objects are gone, so restore surfaces them here)
        self.restored_terminal: list[Request] = []
        self._journal: Any = None
        self._snap_epoch = -1
        if snapshot_dir is not None:
            # baseline snapshot: restore ALWAYS has a complete snapshot to
            # start from, and the epoch's journal captures everything after
            from repro.serving import snapshot as _snaplib
            _snaplib.attach(self, snapshot_dir)

    # -- client API ----------------------------------------------------------

    def submit(self, req: Request) -> None:
        if len(req.prompt) == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.rid}: max_new_tokens must be >= 1, got "
                f"{req.max_new_tokens} — a request always emits at least "
                "its first token")
        if len(req.prompt) > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt length {len(req.prompt)} exceeds "
                f"engine max_len {self.max_len} — raise max_len or truncate")
        if self.paged and self._worst_case_blocks(req) > self.pool_blocks:
            raise ValueError(
                f"request {req.rid}: worst case needs "
                f"{self._worst_case_blocks(req)} KV blocks but the pool has "
                f"{self.pool_blocks} — raise kv_pool_blocks")
        if req.rid in self._live_rids:
            raise ValueError(
                f"request {req.rid}: rid already queued or in flight — "
                "rids must be unique among live requests")
        req.status = "queued"
        self._live_rids.add(req.rid)
        req.submitted_at = self.clock()
        self._queue.append(req)
        if self._journal is not None:
            self._journal.append({
                "ev": "submit", "rid": req.rid,
                "prompt": np.asarray(req.prompt).tolist(),
                "max_new": req.max_new_tokens, "priority": req.priority,
                "deadline": req.deadline_s,
                "frames": (None if req.frames is None
                           else np.asarray(req.frames).tolist())})
            # durable immediately: a submit outside run() must survive a
            # kill before the next tick-batch fsync
            self._journal.commit()

    def cancel(self, rid: int) -> bool:
        """Retire request ``rid`` wherever it is in the lifecycle: dequeued
        if still waiting, or freed mid-flight (slot + blocks released, the
        partial output stays on the request).  Terminal ``status`` becomes
        ``"cancelled"``.  Returns False when no live request has that rid
        (already finished, or never submitted) — cancel is idempotent.
        The request is retired HERE, not echoed through a later ``run()``
        result: the caller already holds the object."""
        for r in self._queue:
            if r.rid == rid:
                self._queue.remove(r)
                self.cancels += 1
                self._terminal(r, "cancelled")
                if self._journal is not None:
                    self._journal.commit()
                return True
        for i, s in enumerate(self._slots):
            if s.req is not None and s.req.rid == rid:
                self.cancels += 1
                self._terminal(s.req, "cancelled")
                self._free_slot(i)
                if self._journal is not None:
                    self._journal.commit()
                return True
        return False

    def snapshot(self) -> str:
        """Write a point-in-time snapshot to ``snapshot_dir`` (atomic: temp
        dir + ``os.replace``) and rotate the write-ahead journal to a fresh
        epoch.  Returns the snapshot directory.  See ``serving/snapshot.py``
        for the durability contract."""
        if self.snapshot_dir is None:
            raise RuntimeError("engine has no snapshot_dir")
        from repro.serving import snapshot as _snaplib
        return _snaplib.save(self)

    @classmethod
    def restore(cls, snapshot_dir: str, params: Any,
                **overrides) -> "Engine":
        """Rebuild a process-equivalent engine from the latest complete
        snapshot under ``snapshot_dir``, replaying the journal of everything
        that happened after it.  Restored token streams are bitwise equal to
        the never-killed engine's (and so to ``reference_decode``).
        ``overrides`` replace constructor kwargs (e.g. a fresh ``chaos``
        monkey or a shared ``compile_cache``)."""
        from repro.serving import snapshot as _snaplib
        return _snaplib.restore_engine(snapshot_dir, params, **overrides)

    def durability_stats(self) -> dict[str, Any]:
        """Snapshot/journal counters for launch stats lines."""
        return {
            "snapshot_dir": self.snapshot_dir,
            "snapshot_every": self.snapshot_every,
            "snapshots_taken": self.snapshots_taken,
            "epoch": self._snap_epoch,
            "journal": self._journal is not None,
            "restored_terminal": len(self.restored_terminal),
        }

    @property
    def compile_budget(self) -> int:
        """Upper bound on compile-cache misses this engine can cause:
        n_chunk_buckets (mixed widths) + decode + insert.  Audio adds one
        ``("admit", F)`` encoder executable per DISTINCT frame count seen —
        traffic-dependent, so it is counted from the cache, keeping
        ``misses <= compile_budget`` an invariant for any workload.  Prefix
        sharing adds exactly one executable — the ``("cow", 0)`` block
        copy — regardless of traffic."""
        extra = sum(1 for name, _ in self.cache_compiles.keys()
                    if name == "admit")
        return (len(self.chunk_buckets.all_buckets()) + 2 + extra +
                (1 if self.prefix_sharing else 0))

    # -- executables (all memoized: misses bounded by compile_budget) --------

    def _build_mixed(self):
        if self.spec_k:
            return _mixed_executable_spec(self.cfg, self.paged)
        return (_mixed_executable_paged(self.cfg) if self.paged
                else _mixed_executable(self.cfg))

    def _build_decode(self):
        return (_decode_executable_paged(self.cfg) if self.paged
                else _decode_executable(self.cfg))

    def _build_insert(self):
        return _insert_executable(self.cfg)

    def _build_cow(self):
        # one shape for every copy-on-write: (cache, src, dst) with traced
        # scalar block ids, donated cache — memoized under ("cow", 0)
        cfg = self.cfg

        def copy_block(c, s, d):
            return api.copy_pool_block(cfg, c, s, d)
        return jax.jit(copy_block, donate_argnums=(0,))

    # -- paged-KV block accounting -------------------------------------------

    @property
    def _free_blocks(self) -> list[int]:
        """The allocator's free list (kept as the PR 5 attribute name: tests
        and tools introspect it for leak checks)."""
        return self.alloc.free

    def _worst_case_blocks(self, req: Request) -> int:
        """Blocks the request can ever hold: its prompt plus its REMAINING
        generation (a preempted request's accepted output is folded into the
        prompt, so only ``max_new_tokens - len(output)`` tokens are still
        owed — but at least one: re-prefill always emits a token), capped by
        the cache's addressable span (the ``_emit`` stop rules)."""
        owed = max(req.max_new_tokens - len(req.output), 1)
        toks = min(len(req.prompt) + owed, self.max_len)
        return -(-toks // self.block_size)

    def _prefix_plan(self, req: Request) -> _PrefixPlan | None:
        """Match the prompt against the radix cache and plan the admission.

        ``consumed`` is capped at ``len(prompt) - 1``: the final prompt token
        must always run through a chunk to produce the first-token logits.
        When the cache covers the WHOLE prompt, the last matched block is
        demoted from shared to CoW source so that token has a writable page.
        """
        if self.prefix is None or len(req.prompt) < 2:
            return None
        full, partial = self.prefix.match(req.prompt)
        consumed = len(full) * self.block_size
        cow = None
        if partial is not None:
            blk, n = partial
            n = min(n, len(req.prompt) - 1 - consumed)
            if n > 0:
                cow = blk
                consumed += n
        elif consumed >= len(req.prompt):
            cow = full.pop()
            consumed = len(req.prompt) - 1
        if not full and cow is None:
            return None
        return _PrefixPlan(shared=full, cow=cow, consumed=consumed)

    def _evict_for(self, n: int, plan: _PrefixPlan | None) -> int:
        """Free up to ``n`` blocks by dropping cold radix-cache leaves
        (LRU-first).  Only blocks the cache SOLELY holds actually free —
        shared residents (refcount >= 2) and the current plan's blocks are
        skipped, so cache pressure can never invalidate a live mapping or
        the admission plan just computed.  Returns the blocks freed."""
        protect = set()
        if plan is not None:
            protect.update(plan.shared)
            if plan.cow is not None:
                protect.add(plan.cow)
        freed = 0
        while freed < n:
            blk = self.prefix.evict_lru(
                keep=lambda b: b in protect or self.alloc.ref(b) > 1)
            if blk is None:
                break                       # nothing evictable left
            self.prefix_evictions += 1
            if not self.alloc.decref(blk):  # cache was sole holder: frees
                raise RuntimeError(
                    f"evicted cache block {blk} still live — keep() gate "
                    "is wrong")
            freed += 1
        return freed

    def _reserved_by_home(self) -> list[int]:
        """Outstanding reservations per block home, summed over slots."""
        totals = [0] * self.n_homes
        for vec in self._reserve_home:
            for h, v in enumerate(vec):
                totals[h] += v
        return totals

    def _plan_reserve(self, need: int) -> list[int] | None:
        """Distribute a worst-case reservation of ``need`` blocks across
        block homes by remaining headroom (free minus already reserved,
        per home), so leases spread round-robin over the mesh and the
        deadlock-freedom invariant holds home by home.  Returns the
        per-home vector, or None when the pool cannot cover it.  With one
        home this degenerates to the PR 5 total check."""
        free_h = self.alloc.free_by_home()
        res_h = self._reserved_by_home()
        head = [f - r for f, r in zip(free_h, res_h)]
        if sum(h for h in head if h > 0) < need:
            return None
        vec = [0] * self.n_homes
        for _ in range(need):
            h = max(range(self.n_homes), key=lambda j: (head[j], -j))
            vec[h] += 1
            head[h] -= 1
        return vec

    def _can_reserve(self, req: Request,
                     plan: _PrefixPlan | None = None) -> bool:
        """Admission gate: unreserved free blocks must cover the request's
        worst case — per block HOME, not just in total.  Every admitted row
        can then ALWAYS lease its next block from a home it reserved in
        (``sum(reserve) <= free`` holds per home), so decode never stalls
        and the pool never deadlocks — pressure shows up as admission
        stalls, never as a stuck batch.  A prefix-cache hit shrinks the need
        by its shared blocks (the CoW page leases normally, inside the
        reservation); on a shortfall, cold cache leaves are evicted one at
        a time until the per-home plan closes (or nothing is evictable)."""
        need = self._worst_case_blocks(req)
        if plan is not None:
            need -= len(plan.shared)
        vec = self._plan_reserve(need)
        while vec is None and self.prefix is not None:
            if self._evict_for(1, plan) == 0:
                break                       # nothing evictable left
            vec = self._plan_reserve(need)
        return vec is not None

    def _lease_for_slot(self, idx: int) -> int:
        """Lease one block against slot ``idx``'s reservation, consuming
        the home with the most remaining reserved blocks (ties to the
        lowest home) — the per-home invariant guarantees that home has a
        free block, so the lease cannot fail."""
        vec = self._reserve_home[idx]
        homes = [h for h in range(self.n_homes) if vec[h] > 0]
        if not homes:
            raise RuntimeError(
                f"slot {idx} leased past its reservation — worst-case "
                "accounting is wrong")
        h = max(homes, key=lambda j: (vec[j], -j))
        try:
            blk = self.alloc.lease(home=h)
        except RuntimeError as e:
            raise RuntimeError(
                f"{e} despite a reservation there — per-home accounting "
                "is wrong") from None
        vec[h] -= 1
        self._slot_reserve[idx] -= 1
        return blk

    def _lease_to(self, idx: int, new_len: int) -> None:
        """Grow slot ``idx`` to cover ``new_len`` tokens, leasing blocks as
        the length crosses page boundaries (on-demand allocation)."""
        need = -(-new_len // self.block_size)
        owned = self._slot_blocks[idx]
        while len(owned) < need:
            blk = self._lease_for_slot(idx)
            self._page_table[idx, len(owned)] = blk
            owned.append(blk)

    def pool_stats(self) -> dict[str, int]:
        """Free-list invariants, exposed for leak/double-free checks.

        ``leased`` counts LIVE physical blocks (refcount >= 1), so ``free +
        leased == total`` stays the partition invariant under sharing —
        a block mapped by three slots and the cache is still ONE block."""
        return {
            "total": self.pool_blocks,
            "free": self.alloc.n_free,
            "leased": self.alloc.n_live,
            "n_homes": self.n_homes,
            "reserved_outstanding": sum(self._slot_reserve),
            "shared_blocks": self.alloc.n_shared(),
            "cached_blocks": (len(self.prefix)
                              if self.prefix is not None else 0),
            "prefix_hits": self.prefix_hits,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "cow_copies": self.cow_copies,
            "prefix_evictions": self.prefix_evictions,
        }

    def prefix_stats(self) -> dict[str, int]:
        """Prefix-cache counters (subset of ``pool_stats`` plus the gate)."""
        return {
            "enabled": self.prefix_sharing,
            "requested": self.prefix_requested,
            "hits": self.prefix_hits,
            "hit_tokens": self.prefix_hit_tokens,
            "cow_copies": self.cow_copies,
            "evictions": self.prefix_evictions,
            "cached_blocks": (len(self.prefix)
                              if self.prefix is not None else 0),
            "shared_blocks": self.alloc.n_shared() if self.paged else 0,
        }

    def drop_prefix_cache(self) -> int:
        """Flush the radix cache, releasing every cache-held block reference
        (cold-workload reset; also how leak checks prove the cache holds
        exactly one reference per node).  Returns the nodes dropped."""
        if self.prefix is None:
            return 0
        blocks = self.prefix.clear()
        for blk in blocks:
            self.alloc.decref(blk)
        return len(blocks)

    # -- internals -----------------------------------------------------------

    def _terminal(self, req: Request, status: str,
                  completed: list[Request] | None = None) -> None:
        """Move ``req`` into terminal state ``status`` — the single exit
        point of the lifecycle state machine, so every path (done, error,
        cancelled, deadline_missed) stamps ``finished_at`` and releases the
        rid for reuse exactly once."""
        assert status in TERMINAL_STATES, status
        req.status = status
        req.done = True
        req.finished_at = self.clock()
        self._live_rids.discard(req.rid)
        if self._journal is not None:
            self._journal.append({"ev": "terminal", "rid": req.rid,
                                  "status": status, "error": req.error})
        if completed is not None:
            completed.append(req)

    def _finish(self, req: Request, completed: list[Request]) -> None:
        self._terminal(req, "done", completed)

    def _free_slot(self, idx: int) -> None:
        """Retire a row: release the host lease.  Device eviction is lazy —
        pure-KV rows hide behind true-length masking and stateful rows are
        reset by the next admission's ``insert_request`` — so retirement
        costs no device dispatch.  The dead row rides along in later ticks
        at q_len 0 / its parked length; its output is ignored.  Paged: the
        row's block references are DROPPED — a block returns to the free
        list only when no other slot (and not the radix cache) still maps
        it — and its page-table row is pointed at the null block, so a
        stale lease can never alias a block the next occupant is handed."""
        if self.paged:
            for blk in self._slot_blocks[idx]:
                try:
                    self.alloc.decref(blk)
                except RuntimeError as e:
                    raise RuntimeError(f"{e} (slot {idx})") from None
            self._slot_blocks[idx] = []
            self._slot_reserve[idx] = 0
            self._reserve_home[idx] = [0] * self.n_homes
            self._page_table[idx, :] = self._null_block
        if self.drafter is not None:
            self.drafter.reset(idx)
        self._slots[idx] = _Slot()

    def _rewind_slot(self, idx: int, new_len: int) -> None:
        """Rollback primitive: shrink slot ``idx``'s valid length to
        ``new_len`` (rejected speculative tokens).  Host-side only — stale
        K/V past ``new_len`` hides behind true-length masking and the next
        writes land over it.  Paged: tail blocks wholly past the new length
        are re-nulled in the page table and returned to the free list, and
        the blocks go BACK into the slot's worst-case reservation (it may
        legitimately lease them again), so ``sum(reserve) <= free`` and
        free+leased accounting stay invariant.  Under prefix sharing a
        rewound tail block is always PRIVATE (shared blocks cover at most
        ``len(prompt) - 1`` tokens and speculation only rewinds past the
        prompt), so the decref here really frees — but refcounts make even
        an artificial shared rewind safe."""
        slot = self._slots[idx]
        if new_len > self.max_len:
            raise ValueError(f"rewind to {new_len} exceeds max_len")
        slot.length = new_len
        if self.paged:
            from repro.models.attention import paged_blocks_for
            keep = paged_blocks_for(new_len, self.block_size)
            owned = self._slot_blocks[idx]
            while len(owned) > keep:
                blk = owned.pop()
                self._page_table[idx, len(owned)] = self._null_block
                try:
                    self.alloc.decref(blk)
                except RuntimeError as e:
                    raise RuntimeError(f"{e} (rewind slot {idx})") from None
                # the freed block physically returns to ITS home's free
                # list, so crediting the reservation to that same home
                # preserves the per-home invariant exactly
                self._slot_reserve[idx] += 1
                self._reserve_home[idx][self.alloc.home(blk)] += 1

    # -- resilience: quarantine, deadlines, preemption ----------------------

    def _fault_row(self, idx: int, msg: str,
                   completed: list[Request]) -> None:
        """Quarantine exactly one row: the request finishes with
        ``status="error"`` (partial output kept, ``error`` says why), its
        slot and blocks are released through the normal ``_free_slot``
        path, and every other row's tick proceeds untouched — a bad row
        never propagates out of the batch."""
        req = self._slots[idx].req
        req.error = msg
        self.row_faults += 1
        self._terminal(req, "error", completed)
        self._free_slot(idx)

    def _safe_sample(self, idx: int, sample: Callable,
                     logits_np: np.ndarray,
                     completed: list[Request]) -> int | None:
        """Run the user's ``sample`` hook for one row, quarantining the row
        (not the tick) if the hook throws.  Returns None when faulted."""
        try:
            return int(sample(logits_np[idx]))
        except Exception as e:  # noqa: BLE001 — hook code is untrusted
            self._fault_row(idx, f"sample hook raised: {e!r}", completed)
            return None

    def _sweep_deadlines(self, completed: list[Request]) -> None:
        """Retire every live request whose deadline has passed — queued
        (never admitted) or mid-flight (slot freed, partial output kept).
        ``deadline_s`` is measured from ``submitted_at``; ``>=`` makes
        ``deadline_s=0.0`` miss deterministically at the first sweep."""
        if not self.enforce_deadlines:
            return
        now = self.clock()

        def missed(r: Request) -> bool:
            return (r.deadline_s is not None and
                    now - r.submitted_at >= r.deadline_s)

        for i, s in enumerate(self._slots):
            if s.req is not None and missed(s.req):
                self.deadline_misses += 1
                self._terminal(s.req, "deadline_missed", completed)
                self._free_slot(i)
        if any(missed(r) for r in self._queue):
            keep: collections.deque = collections.deque()
            for r in self._queue:
                if missed(r):
                    self.deadline_misses += 1
                    self._terminal(r, "deadline_missed", completed)
                else:
                    keep.append(r)
            self._queue = keep

    def _pick_victim(self, max_priority: int | None = None, *,
                     strict: bool = False) -> int | None:
        """Choose the slot to preempt: lowest priority first, youngest
        (largest admission ``seq``) within a priority.  Requests at their
        preemption bound are immune (progress guarantee).  ``strict``
        requires the victim's priority be LOWER than ``max_priority``
        (priority preemption for a full batch); non-strict allows equal
        (shortfall preemption — the FIFO head outranks a peer that has
        already had its turn)."""
        best: int | None = None
        best_key: tuple[int, int] | None = None
        for i, s in enumerate(self._slots):
            r = s.req
            if r is None or r.preemptions >= self.max_preemptions:
                continue
            if max_priority is not None:
                if strict and r.priority >= max_priority:
                    continue
                if not strict and r.priority > max_priority:
                    continue
            key = (r.priority, -s.seq)
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best

    def _fold_slot(self, idx: int) -> None:
        """The lossless fold primitive shared by preemption and snapshot
        restore: donate the slot's fully written resident blocks to the
        radix cache (prompt AND accepted output — so re-admission is mostly
        a page-table copy via ``_prefix_plan``), then fold the accepted
        output into the prompt.  The folded run's token stream is bitwise
        the never-folded one, since emit-time lengths realign exactly."""
        slot = self._slots[idx]
        req = slot.req
        if self.prefix is not None and slot.length >= self.block_size:
            # prompt already holds output[:folded] from earlier folds —
            # resident tokens are prompt + the output emitted SINCE
            resident = np.concatenate([
                np.asarray(req.prompt, np.int64),
                np.asarray(req.output[req.folded:], np.int64)])[:slot.length]
            nfull = slot.length // self.block_size
            fresh = self.prefix.insert(resident[:nfull * self.block_size],
                                       self._slot_blocks[idx][:nfull])
            for blk in fresh:
                self.alloc.incref(blk)
        if len(req.output) > req.folded:
            # fold only the output NOT already folded by an earlier
            # preemption — re-folding would duplicate tokens in the prompt
            req.prompt = np.concatenate([
                np.asarray(req.prompt, np.int64),
                np.asarray(req.output[req.folded:], np.int64)])
            req.folded = len(req.output)

    def _preempt(self, idx: int, *, requeue_front: bool = False) -> None:
        """Evict a running request, keeping its work: ``_fold_slot`` donates
        its blocks and folds accepted output into the prompt (re-admission
        recomputes nothing semantically).  Requeued behind the current head
        by default — the head caused the preemption and must win the freed
        space — or at the front for a forced (chaos) preemption with no
        waiting head."""
        req = self._slots[idx].req
        self._fold_slot(idx)
        req.preemptions += 1
        req.status = "queued"
        self.preemptions += 1
        self._free_slot(idx)
        if requeue_front or not self._queue:
            self._queue.appendleft(req)
        else:
            # behind the head that evicted it AND behind every waiter that
            # outranks it — a preempted hog must not become the new head
            # and block the higher-priority queue it was evicted for
            pos = 1
            while (pos < len(self._queue) and
                   self._queue[pos].priority > req.priority):
                pos += 1
            self._queue.insert(pos, req)

    def _admit_head(self, idx: int) -> bool:
        """Try to admit the queue head into free slot ``idx``.  On a paged
        reservation shortfall (after ``_can_reserve`` already ran LRU
        prefix eviction), preempt victims one at a time — youngest/lowest
        priority, never outranking the head — re-planning after each, until
        the head fits or no victim remains (admission stall)."""
        head = self._queue[0]
        plan = self._prefix_plan(head)
        if self.paged:
            if self.chaos is not None and self.chaos.deny_reservation():
                self.admission_stalls += 1
                return False
            while not self._can_reserve(head, plan):
                v = self._pick_victim(head.priority, strict=False)
                if v is None:
                    self.admission_stalls += 1
                    return False
                self._preempt(v)
                plan = self._prefix_plan(head)
        self._admit(self._queue.popleft(), idx, plan)
        return True

    def audit(self) -> None:
        """One-shot invariant audit (the ``audit_every`` knob runs it each
        N ticks).  Raises AssertionError on the first violation: allocator
        refcount/partition (``BlockAllocator.check``), deadlock-freedom
        (``sum(reserve) <= free``), page-table rows exactly mirror the
        slots' owned live blocks with a null tail, dead slots own nothing,
        cache-held blocks are live, and every running rid is tracked."""
        self.audits += 1
        if self.paged:
            self.alloc.check()
            reserved = sum(self._slot_reserve)
            assert reserved <= self.alloc.n_free, (
                f"reservation invariant broken: {reserved} reserved > "
                f"{self.alloc.n_free} free")
            # per-home deadlock freedom + reservation-vector coherence
            assert self.alloc.n_homes == self.n_homes, (
                "allocator homes diverged from the engine topology")
            free_h = self.alloc.free_by_home()
            for h, r in enumerate(self._reserved_by_home()):
                assert r <= free_h[h], (
                    f"home {h}: {r} reserved > {free_h[h]} free — per-home "
                    "deadlock-freedom broken")
            for i, vec in enumerate(self._reserve_home):
                assert all(v >= 0 for v in vec) and \
                    sum(vec) == self._slot_reserve[i], (
                    f"slot {i} home-reservation vector {vec} != total "
                    f"{self._slot_reserve[i]}")
            for i, s in enumerate(self._slots):
                owned = self._slot_blocks[i]
                if s.req is None:
                    assert not owned and not self._slot_reserve[i], (
                        f"dead slot {i} owns blocks/reservation")
                row = self._page_table[i]
                assert list(row[:len(owned)]) == owned, (
                    f"slot {i} page table != owned blocks")
                assert all(b == self._null_block
                           for b in row[len(owned):]), (
                    f"slot {i} page table has stale tail entries")
                for blk in owned:
                    assert self.alloc.ref(blk) >= 1, (
                        f"slot {i} maps freed block {blk}")
                    # every leased block resolves to (shard, local block)
                    # consistently with its page-table entry: the id is a
                    # real pool block (never the null row) and its home's
                    # local translation stays inside the home's rows
                    assert 0 <= blk < self.pool_blocks, (
                        f"slot {i} maps out-of-pool block {blk}")
                    home = self.alloc.home(blk)
                    local = blk - home * self.alloc.rows_per_home
                    assert (0 <= home < self.n_homes and
                            0 <= local < self.alloc.rows_per_home), (
                        f"block {blk} resolves outside home partition")
            if self.prefix is not None:
                for blk in self.prefix.blocks():
                    assert self.alloc.ref(blk) >= 1, (
                        f"radix cache holds freed block {blk}")
        for i, s in enumerate(self._slots):
            if s.req is not None:
                assert s.length <= self.max_len, f"slot {i} overran max_len"
                assert s.req.rid in self._live_rids, (
                    f"running rid {s.req.rid} untracked")

    def resilience_stats(self) -> dict[str, Any]:
        """Lifecycle/fault counters (chaos injection stats ride along when
        a monkey is attached)."""
        out: dict[str, Any] = {
            "preemptions": self.preemptions,
            "max_preemptions": self.max_preemptions,
            "deadline_misses": self.deadline_misses,
            "row_faults": self.row_faults,
            "cancels": self.cancels,
            "audits": self.audits,
            "enforce_deadlines": self.enforce_deadlines,
            "check_finite": self.check_finite,
        }
        if self.chaos is not None:
            out["chaos"] = self.chaos.stats()
        return out

    def _cow_block(self, idx: int, src: int) -> None:
        """Copy-on-write: lease a private block for slot ``idx``'s next page
        and duplicate shared block ``src`` into it on device.  The matched
        head of the copy is live (bit-identical K/V); its stale tail sits
        past the slot's length until the normal chunk writer overwrites it.
        The lease consumes the slot's reservation like any other, so the
        "+1 CoW block" is already inside the admission accounting."""
        page = len(self._slot_blocks[idx])
        dst = self._lease_for_slot(idx)
        self._page_table[idx, page] = dst
        self._slot_blocks[idx].append(dst)
        fn = self.cache_compiles.get("cow", 0, self._build_cow)
        self.cache = fn(self.cache, np.int32(src), np.int32(dst))
        self.cow_copies += 1

    def _cache_prompt(self, idx: int) -> None:
        """Prefill just finished for slot ``idx``: donate the prompt's fully
        written blocks to the radix cache.  The cache holds ONE reference
        per node it newly created; dedup (a concurrent identical prompt)
        keeps the first author's block and the duplicate stays private."""
        prompt = self._slots[idx].req.prompt
        nfull = len(prompt) // self.block_size
        if nfull == 0:
            return
        fresh = self.prefix.insert(np.asarray(prompt)[:nfull *
                                                      self.block_size],
                                   self._slot_blocks[idx][:nfull])
        for blk in fresh:
            self.alloc.incref(blk)

    def _admit(self, req: Request, idx: int,
               plan: _PrefixPlan | None = None) -> None:
        """Lease slot ``idx`` to ``req``.  No prefill dispatch happens here:
        the prompt streams through subsequent mixed ticks.  Stateful
        families scatter a fresh ``request_cache`` row into the slot first
        (recurrent-state reset; audio also carries the request's cross-KV).
        A prefix-cache ``plan`` maps the shared blocks into the page table
        (incref each), optionally CoW-copies one partial block, and starts
        the chunk cursor at the first uncovered prompt token."""
        if self.paged:
            shared = list(plan.shared) if plan is not None else []
            need = self._worst_case_blocks(req) - len(shared)
            vec = self._plan_reserve(need)
            if vec is None:   # _can_reserve just planned this very need
                raise RuntimeError(
                    "admission without a coverable reservation — "
                    "_can_reserve gate bypassed")
            self._slot_reserve[idx] = need
            self._reserve_home[idx] = vec
            for page, blk in enumerate(shared):
                self.alloc.incref(blk)
                self._page_table[idx, page] = blk
            if shared:
                self._slot_blocks[idx] = shared
        if api.needs_admission_insert(self.cfg):
            if self.cfg.family == "audio":
                f = np.asarray(req.frames)
                frames = jnp.asarray(f[None] if f.ndim == 2 else f)
                admit = self.cache_compiles.get(
                    "admit", frames.shape[1],
                    lambda: _admit_executable(self._row_cfg, self.max_len))
                row = admit(self.params, frames)
            else:
                row = self._fresh_row
            insert = self.cache_compiles.get("insert", self.batch,
                                             self._build_insert)
            self.cache = insert(self.cache, row, np.int32(idx))
        self._admit_seq += 1
        req.status = "running"
        if req.admitted_at is None:
            req.admitted_at = self.clock()
        self._slots[idx] = _Slot(req=req, seq=self._admit_seq)
        if plan is not None:
            if plan.cow is not None:
                self._cow_block(idx, plan.cow)
            s = self._slots[idx]
            s.length = s.pos = plan.consumed
            self.prefix_hits += 1
            self.prefix_hit_tokens += plan.consumed
        self._draft_wait[idx] = self._draft_penalty[idx] = 0
        if self.drafter is not None:
            # seed the drafter with the full prompt (prompt-lookup proper):
            # drafts may copy prompt spans before the prompt finishes
            # streaming through the cache — acceptance keeps it lossless
            self.drafter.reset(idx)
            self.drafter.observe(idx, req.prompt)

    def _schedule_drafts(self, chunks: list[int], decoding: list[int],
                         sample) -> dict[int, list[int]]:
        """Pick this tick's verify rows: up to ``spec_k`` draft tokens per
        decode row from the drafter, each capped by (1) cache room past the
        row's mandatory real token, (2) the request's remaining token need
        minus one — which also keeps the tick's writes inside the paged
        worst-case reservation (``len(prompt) + max_new_tokens`` total) —
        and (3) the shared prefill token budget: chunks are scheduled
        first, verify tokens consume what remains.  A ``sample`` hook
        disables drafting for the tick (acceptance is defined against the
        model's greedy tokens)."""
        if not self.spec_k or sample is not None:
            return {}
        left = None
        if self.prefill_token_budget is not None:
            left = max(self.prefill_token_budget - sum(chunks), 0)
        drafts: dict[int, list[int]] = {}
        for i in decoding:
            if self._draft_wait[i] > 0:          # backing off after misses
                self._draft_wait[i] -= 1
                continue
            s = self._slots[i]
            k = min(self.spec_k,
                    self.max_len - s.length - 1,
                    s.req.max_new_tokens - len(s.req.output) - 1)
            if left is not None:
                k = min(k, left)
            if k <= 0:
                continue
            d = self.drafter.draft(i, k)
            if d:
                drafts[i] = d
                if left is not None:
                    left -= len(d)
        return drafts

    def _schedule_chunks(self) -> list[int]:
        """Pick this tick's per-slot prompt-chunk sizes (Sarathi-style).

        Returns q_lens for mid-prefill rows only (0 elsewhere).  The
        "mixed" policy advances every mid-prefill row, subject to the
        token budget (FIFO by slot, at least one row always advances);
        the "stall" policy advances only the oldest mid-prefill row —
        the seed's head-of-line-blocking admission, kept as the
        serving_bench baseline.
        """
        chunks = [0] * self.batch
        budget = self.prefill_token_budget
        picked = 0
        for i, s in enumerate(self._slots):
            if not s.prefilling:
                continue
            want = min(self.chunk_size, len(s.req.prompt) - s.pos)
            if picked and budget is not None:
                want = min(want, max(budget, 0))
            if picked and self.prefill_policy == "stall":
                want = 0
            if want <= 0:
                continue
            chunks[i] = want
            picked += 1
            if budget is not None:
                budget -= want
        return chunks

    def _emit(self, idx: int, token: int, completed: list[Request],
              first: bool) -> None:
        """Record one generated token; finish/free the slot when done."""
        slot = self._slots[idx]
        req = slot.req
        now = self.clock()
        if first and req.first_token_at is None:
            # a preempted request keeps its ORIGINAL first-token time: the
            # re-prefill's "first" token is really a later output token
            req.first_token_at = now
        req.output.append(token)
        req.token_times.append(now)
        if self._journal is not None:
            self._journal.append({"ev": "emit", "rid": req.rid,
                                  "tok": int(token)})
        slot.last_token = token
        if self.drafter is not None:
            self.drafter.observe(idx, (token,))
        if (len(req.output) >= req.max_new_tokens or
                slot.length >= self.max_len or  # no cache room to decode into
                (self.eos_id is not None and token == self.eos_id)):
            self._finish(req, completed)
            self._free_slot(idx)

    # -- the phases of one tick (``run``; each runs under its own span) ------

    def _refill(self, completed: list[Request]) -> list[int]:
        """Tick steps 0-1: sweep deadlines and apply chaos preemption, then
        refill free slots from the queue (a host-side lease — no prefill
        dispatch), preempting bounded victims on a reservation shortfall or
        for a higher-priority head.  Returns the live slot indices."""
        # lifecycle sweeps: expired deadlines retire first (queued or
        # mid-flight), then chaos may force-preempt a running row
        self._sweep_deadlines(completed)
        if self.chaos is not None and self.max_preemptions:
            eligible = [i for i, s in enumerate(self._slots)
                        if s.req is not None and
                        s.req.preemptions < self.max_preemptions]
            v = self.chaos.forced_preempt(eligible)
            if v is not None:
                self._preempt(v, requeue_front=True)
        # 1. continuous refill: admit queued requests into free slots.
        # Paged: strict-FIFO admission gated on the worst-case block
        # reservation — shortfalls preempt the youngest/lowest-priority
        # bounded victim (when allowed), else stall the head
        for i in range(self.batch):
            if self._slots[i].req is None and self._queue:
                if not self._admit_head(i):
                    break
        # priority preemption: a waiting head that OUTRANKS a running
        # request does not sit behind it just because the batch is full
        while (self._queue and self.max_preemptions and
               all(s.req is not None for s in self._slots)):
            v = self._pick_victim(self._queue[0].priority, strict=True)
            if v is None:
                break
            self._preempt(v)
            if not self._admit_head(v):
                break
        return [i for i, s in enumerate(self._slots) if s.req is not None]

    def _plan(self, live: list[int], sample) -> tuple:
        """Tick step 2: co-schedule prompt chunks with decode and verify
        rows and lease the blocks every advancing row writes.  Returns
        (chunks, decoding, drafts)."""
        chunks = self._schedule_chunks()
        stall = (self.prefill_policy == "stall" and any(chunks))
        decoding = [i for i in live
                    if not self._slots[i].prefilling and not stall]
        drafts = self._schedule_drafts(chunks, decoding, sample)
        if self.chaos is not None and drafts:
            # garbage drafts: same length (leases are sized by it), but
            # possibly nonsense tokens — verify must reject losslessly
            drafts = {i: self.chaos.garble_draft(d, self.cfg.vocab_size)
                      for i, d in drafts.items()}
        if self.paged:
            # on-demand leases for every row advancing this tick (the
            # admission reservation guarantees these succeed — verify rows
            # stay inside it via the drafts' remaining-need cap)
            for i, s in enumerate(self._slots):
                if chunks[i]:
                    self._lease_to(i, s.length + chunks[i])
                elif i in decoding:
                    self._lease_to(i, s.length + 1 + len(drafts.get(i, ())))
        return chunks, decoding, drafts

    def _mixed_args(self, chunks: list[int], decoding: list[int],
                    drafts: dict) -> tuple[int, tuple]:
        """The mixed tick's bucket width and device arguments: prompt
        chunks + decode + verify rows, one dispatch."""
        wide = max([max(chunks), 2] + [1 + len(d) for d in drafts.values()])
        w = self.chunk_buckets.bucket(wide)
        tokens = np.zeros((self.batch, w), np.int32)
        lengths = np.zeros(self.batch, np.int32)
        q_lens = np.zeros(self.batch, np.int32)
        for i, s in enumerate(self._slots):
            lengths[i] = s.length
            if chunks[i]:
                q_lens[i] = chunks[i]
                tokens[i, :chunks[i]] = s.req.prompt[s.pos:s.pos + chunks[i]]
            elif i in decoding:
                d = drafts.get(i, ())
                q_lens[i] = 1 + len(d)
                tokens[i, 0] = s.last_token
                if d:
                    tokens[i, 1:1 + len(d)] = d
        args = (jnp.asarray(tokens), jnp.asarray(lengths),
                jnp.asarray(q_lens))
        if self.paged:
            args += (jnp.asarray(self._page_table),)
        return w, args

    def _decode_args(self, decoding: list[int]) -> tuple[int, tuple]:
        """The pure-decode tick's width (1) and device arguments: the
        classic executable, bit-identical to the batch-1 oracle; dead rows
        ride along and their output is ignored."""
        tokens = np.fromiter((s.last_token for s in self._slots),
                             np.int32, self.batch).reshape(-1, 1)
        lengths = np.fromiter(
            (s.length + 1 if i in decoding else max(s.length, 1)
             for i, s in enumerate(self._slots)),
            np.int32, self.batch)
        args = (jnp.asarray(tokens), jnp.asarray(lengths))
        if self.paged:
            adv = np.zeros(self.batch, bool)
            adv[decoding] = True
            args += (jnp.asarray(self._page_table), jnp.asarray(adv))
        return 1, args

    def _count_tick(self, live: list[int], chunks: list[int],
                    decoding: list[int], drafts: dict, mixed: bool, w: int,
                    positions: int) -> None:
        """Counters of one dispatched tick."""
        self.steps += 1
        self.dispatches += 1
        if mixed:
            self.mixed_ticks += 1
        if drafts:
            self.spec_ticks += 1
            self.spec_rows += len(drafts)
            self.spec_drafted += sum(len(d) for d in drafts.values())
        self.positions_computed += self.batch * w
        self.positions_live += positions
        self._occupancy_sum += len(live) / self.batch
        if self.paged:
            self.peak_pool_blocks = max(
                self.peak_pool_blocks,
                self.pool_blocks - self.alloc.n_free)
        self.peak_resident_tokens = max(
            self.peak_resident_tokens,
            sum(self._slots[i].length + chunks[i] + (i in decoding) +
                len(drafts.get(i, ()))
                for i in live))

    def _check_rows(self, finite_np: np.ndarray, logits, live: list[int],
                    chunks: list[int], decoding: list[int],
                    sample) -> tuple[np.ndarray | None, set[int]]:
        """The tick's row checks: returns the logits on the host (for the
        ``sample`` hook, else None) and the advancing rows whose logits are
        not finite (``check_finite``).  Finiteness is read from the step's
        per-row flag, computed on the device; a row chaos poisons this tick
        is non-finite by construction.  So the (B, vocab) logits are copied
        only for the hook, which sees the poisoned rows as NaN, and a
        greedy tick copies nothing (``logits_fetches`` counts the copies)."""
        advancing = [i for i in live if chunks[i] or i in decoding]
        hit = (self.chaos.corrupt_rows(advancing)
               if self.chaos is not None else [])
        logits_np = None
        if sample is not None:
            with TraceAnnotation("engine.fetch_logits"):
                self.logits_fetches += 1
                logits_np = np.asarray(logits)
                if hit:
                    logits_np = logits_np.copy()  # device arrays read-only
                    logits_np[hit] = np.nan
        bad: set[int] = set()
        if self.check_finite:
            bad = {i for i in advancing if not finite_np[i]}.union(hit)
        return logits_np, bad

    def _consume(self, live: list[int], chunks: list[int],
                 decoding: list[int], drafts: dict, bad: set[int],
                 next_np: np.ndarray, greedy_np, logits_np,
                 completed: list[Request], sample) -> None:
        """Tick step 3: advance cursors, emit tokens, retire finished rows;
        faulted rows quarantine here, the rest are untouched."""
        for i in list(live):
            slot = self._slots[i]
            if slot.req is None:
                continue        # freed earlier this tick
            if i in bad:
                self._fault_row(i, "non-finite logits", completed)
                continue
            if chunks[i]:
                slot.pos += chunks[i]
                slot.length += chunks[i]
                if slot.pos == len(slot.req.prompt):
                    if self.prefix is not None:
                        # fully-written prompt blocks join the cache
                        self._cache_prompt(i)
                    # final chunk: this row's logits are its first token
                    if sample is None:
                        tok = int(next_np[i])
                    else:
                        tok = self._safe_sample(i, sample, logits_np,
                                                completed)
                        if tok is None or self._slots[i].req is None:
                            # hook threw (row quarantined) or cancelled
                            # this very row mid-sample
                            continue
                    self._emit(i, tok, completed, first=True)
            elif i in drafts:
                # verify row: accept the longest draft prefix agreeing
                # with the model's greedy tokens, emit the model's token
                # at each accepted position PLUS the first disagreement
                # (so a verify tick never emits less than plain decode),
                # then roll back the rejected tail
                d = drafts[i]
                g = greedy_np[i]
                a = 0
                while a < len(d) and d[a] == int(g[a]):
                    a += 1
                self.spec_accepted += a
                if a:               # productive row: speculate freely
                    self._draft_wait[i] = self._draft_penalty[i] = 0
                else:               # full miss: back off exponentially
                    self._draft_penalty[i] = min(
                        max(self._draft_penalty[i] * 2, 1),
                        _DRAFT_BACKOFF_MAX)
                    self._draft_wait[i] = self._draft_penalty[i]
                base = slot.length
                freed = False
                for j in range(a + 1):
                    # emit-time length matches the sequential schedule:
                    # token j corresponds to cache length base + 1 + j,
                    # so the max_len/eos/max_new stop rules fire at
                    # exactly the oracle's token
                    slot.length = base + 1 + j
                    self._emit(i, int(g[j]), completed, first=False)
                    if self._slots[i].req is None:
                        freed = True   # finished: _free_slot did cleanup
                        break
                if not freed and a < len(d):
                    self.spec_rewinds += 1
                    self._rewind_slot(i, base + 1 + a)
            elif i in decoding:
                slot.length += 1
                if sample is None:
                    tok = int(next_np[i])
                else:
                    tok = self._safe_sample(i, sample, logits_np,
                                            completed)
                    if tok is None or self._slots[i].req is None:
                        # hook threw (row quarantined) or cancelled
                        # this very row mid-sample
                        continue
                self._emit(i, tok, completed, first=False)

    def run(self, *, max_steps: int = 10_000,
            sample: Callable | None = None) -> "RunResult":
        """Drain the queue; returns a ``RunResult`` (a list of the requests
        that reached a terminal state this call — done, error, cancelled,
        deadline_missed — plus truncation/stall flags).

        Each tick: (0) sweep deadlines and apply chaos, (1) refill free
        slots from the queue (a host-side lease — no prefill dispatch),
        preempting bounded victims on a reservation shortfall or for a
        higher-priority head, (2) co-schedule prompt chunks with decode
        rows, (3) advance ALL slots with exactly one jitted call —
        ``mixed_step`` when any prompt chunk is in flight, the classic
        ``decode_step`` otherwise — then quarantine any faulted row and
        consume the rest.  ``sample`` maps a logits row (V,) to a token id;
        greedy argmax (computed on device) when None.
        """
        completed: list[Request] = []
        start_steps = self.steps       # max_steps bounds THIS call, not the
        stalled = False                # engine's lifetime
        idle = 0                       # consecutive no-row no-admission ticks
        while self.steps - start_steps < max_steps:
            with TraceAnnotation("engine.tick") as tick:
                # 0. chaos process death fires at the TOP of the tick, after
                # the previous tick's journal batch was fsync'd — so a kill
                # can lose at most un-dispatched work, never an emitted token
                # (getattr: older monkeys/test doubles predate the kill seam)
                if self.chaos is not None:
                    kill = getattr(self.chaos, "maybe_kill", None)
                    if kill is not None:
                        kill()
                with TraceAnnotation("engine.admit"):
                    live = self._refill(completed)
                if not live:
                    stalled = bool(self._queue)
                    if not stalled:
                        break      # queue drained and no row in flight
                    # work is queued but nothing runs.  Without chaos this is
                    # permanent (submit bounds worst case by the pool, and
                    # with no live rows cache eviction can always free the
                    # rest) — under injection a denial is transient, so
                    # retry, bounded by max_steps idle ticks
                    idle += 1
                    if self.chaos is None or idle >= max_steps:
                        break
                    continue
                idle = 0
                with TraceAnnotation("engine.plan"):
                    chunks, decoding, drafts = self._plan(live, sample)
                # 2. one dispatch: mixed_step when any prompt chunk or draft
                # rides along, the classic decode_step otherwise
                mixed = bool(any(chunks) or drafts)
                with TraceAnnotation("engine.prepare"):
                    w, args = (self._mixed_args(chunks, decoding, drafts)
                               if mixed else self._decode_args(decoding))
                positions = (sum(chunks) + len(decoding) +
                             sum(len(d) for d in drafts.values()))
                tick.set_metadata(kind="mixed" if mixed else "decode", w=w,
                                  live=len(live), live_positions=positions)
                with TraceAnnotation("engine.dispatch"):
                    if mixed:
                        fn = self.cache_compiles.get("mixed", w,
                                                     self._build_mixed)
                    else:
                        fn = self.cache_compiles.get("decode", self.batch,
                                                     self._build_decode)
                    if mixed and self.spec_k:
                        next_tok, logits, self.cache, greedy, finite = fn(
                            self.params, self.cache, *args)
                    else:
                        next_tok, logits, self.cache, finite = fn(
                            self.params, self.cache, *args)
                self._count_tick(live, chunks, decoding, drafts, mixed, w,
                                 positions)
                with TraceAnnotation("engine.sync"):
                    # one transfer: a token and a finiteness flag a row
                    # (and the verify rows' greedy tokens)
                    next_np, finite_np, greedy_np = jax.device_get(
                        (next_tok, finite, greedy if drafts else None))
                logits_np, bad = self._check_rows(finite_np, logits, live,
                                                  chunks, decoding, sample)
                # 3. consume: advance cursors, emit tokens, retire finished
                # rows; faulted rows quarantine here, the rest are untouched
                with TraceAnnotation("engine.consume"):
                    self._consume(live, chunks, decoding, drafts, bad,
                                  next_np, greedy_np, logits_np, completed,
                                  sample)
                audit = bool(self.audit_every and
                             self.steps % self.audit_every == 0)
                snap = bool(self.snapshot_dir is not None and
                            self.snapshot_every and
                            self.steps % self.snapshot_every == 0)
                if audit or snap or self._journal is not None:
                    with TraceAnnotation("engine.journal"):
                        if audit:
                            self.audit()
                        # 4. durability: one fsync per tick batch, then maybe
                        # rotate a fresh snapshot — the snapshot sees every
                        # event the journal committed, so a kill between them
                        # loses nothing
                        if self._journal is not None:
                            self._journal.commit()
                        if snap:
                            self.snapshot()
        if self._journal is not None:
            self._journal.commit()
        in_flight = sum(s.req is not None for s in self._slots)
        truncated = (self.steps - start_steps >= max_steps and
                     bool(in_flight or self._queue))
        if truncated:
            warnings.warn(
                f"Engine.run hit max_steps={max_steps} with {in_flight} "
                f"request(s) in flight and {len(self._queue)} queued — "
                "work is NOT drained; call run() again to continue",
                RuntimeWarning, stacklevel=2)
        return RunResult(completed, truncated=truncated,
                         in_flight=in_flight, queued=len(self._queue),
                         stalled=stalled)

    # -- metrics ---------------------------------------------------------------

    @property
    def slot_occupancy(self) -> float:
        """Mean fraction of slots live per tick (1.0 = saturated)."""
        return self._occupancy_sum / self.steps if self.steps else 0.0

    def spec_stats(self) -> dict[str, float]:
        """Speculation counters: how much the verify ticks amortized.

        ``accepted_per_dispatch`` is the headline — extra tokens a verify
        dispatch yielded beyond the one plain decode would have (so
        verify-row tokens/dispatch is ``1 + accepted_per_dispatch``)."""
        return {
            "spec_k": self.spec_k,
            "spec_requested": self.spec_requested,
            "spec_supported": self.spec_supported,
            "spec_ticks": self.spec_ticks,
            "verify_rows": self.spec_rows,
            "draft_tokens": self.spec_drafted,
            "accepted_tokens": self.spec_accepted,
            "rewinds": self.spec_rewinds,
            "acceptance_rate": (self.spec_accepted /
                                max(self.spec_drafted, 1)),
            "accepted_per_dispatch": (self.spec_accepted /
                                      max(self.spec_ticks, 1)),
        }

    @staticmethod
    def summarize(reqs: list[Request]) -> dict[str, float]:
        if not reqs:
            return {}
        # TTFT is timed from submit, so it includes the queue wait
        ttft = [r.first_token_at - r.submitted_at for r in reqs
                if r.first_token_at]
        # queue wait: submit to the request's first admission into a row
        wait = [r.admitted_at - r.submitted_at for r in reqs
                if r.admitted_at is not None]
        # decode throughput: measured from the first token so queue-wait
        # does not pollute the device tokens/s number
        tps = [(len(r.output) - 1) /
               max(r.finished_at - r.first_token_at, 1e-9)
               for r in reqs
               if r.finished_at and r.first_token_at and len(r.output) > 1]
        itl = [dt for r in reqs
               for dt in np.diff(r.token_times).tolist()]
        out = {
            "n": len(reqs),
            "total_tokens": float(sum(len(r.output) for r in reqs)),
            # lifecycle outcome counts (ISSUE 8): empty buckets OMIT their
            # mean_* keys below rather than emitting nan — nan poisons JSON
            # diffs of BENCH_serving.json
            "completed": sum(r.status == "done" for r in reqs),
            "errors": sum(r.status == "error" for r in reqs),
            "cancelled": sum(r.status == "cancelled" for r in reqs),
            "deadline_missed": sum(r.status == "deadline_missed"
                                   for r in reqs),
            "preempted": sum(r.preemptions > 0 for r in reqs),
            "preemptions": sum(r.preemptions for r in reqs),
        }
        if ttft:
            out["mean_ttft_s"] = float(np.mean(ttft))
            out["ttft_p50_s"] = float(np.percentile(ttft, 50))
            out["ttft_p99_s"] = float(np.percentile(ttft, 99))
        if wait:
            out["queue_wait_p50_s"] = float(np.percentile(wait, 50))
            out["queue_wait_p99_s"] = float(np.percentile(wait, 99))
        if tps:
            out["mean_tokens_per_s"] = float(np.mean(tps))
        if itl:
            out["itl_p50_s"] = float(np.percentile(itl, 50))
            out["itl_p99_s"] = float(np.percentile(itl, 99))
        return out


def reference_decode(cfg: ModelConfig, params: Any, prompt: np.ndarray,
                     max_new_tokens: int, *, max_len: int = 512,
                     eos_id: int | None = None,
                     frames: np.ndarray | None = None,
                     compile_cache: CompileCache | None = None) -> list[int]:
    """Per-request batch-1 greedy decode — the EXACT numerics oracle.

    Teacher-forces the prompt through ``api.decode_step`` one token at a
    time (true positions, true lengths, no pad tokens in the context), so
    the resulting cache/state is the ground truth for EVERY family —
    including the post-prompt recurrent state of ssm/hybrid — then decodes
    greedily.  The chunked engine must match this token-for-token.
    """
    if len(prompt) > max_len:
        raise ValueError(f"prompt length {len(prompt)} exceeds {max_len}")
    cc = compile_cache if compile_cache is not None else CompileCache()
    if cfg.family == "audio":
        f = np.asarray(frames)
        fr = jnp.asarray(f[None] if f.ndim == 2 else f)
        admit = cc.get("ref_admit", fr.shape[1],
                       lambda: _admit_executable(cfg, max_len))
        cache = admit(params, fr)
    else:
        cache = api.init_cache(cfg, 1, max_len)
    dec = cc.get("ref_decode", 1, lambda: jax.jit(
        lambda p, c, t, l: api.decode_step(cfg, p, c, t, l)))
    logits = None
    n_cached = 0
    for t in np.asarray(prompt).tolist():
        logits, cache = dec(params, cache,
                            jnp.asarray([[t]], jnp.int32),
                            jnp.asarray([n_cached + 1], jnp.int32))
        n_cached += 1
    out = [int(np.argmax(np.asarray(logits[0])))]
    while (len(out) < max_new_tokens and n_cached < max_len and
           (eos_id is None or out[-1] != eos_id)):
        n_cached += 1
        logits, cache = dec(params, cache,
                            jnp.asarray([[out[-1]]], jnp.int32),
                            jnp.asarray([n_cached], jnp.int32))
        out.append(int(np.argmax(np.asarray(logits[0]))))
    return out
