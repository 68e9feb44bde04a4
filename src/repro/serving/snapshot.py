"""Durable serving: atomic engine snapshots + write-ahead request journal.

EdgeLLM's deployment target is an edge device where power loss and process
kills are ROUTINE, not rare — PR 8 made the engine resilient to in-process
faults, and this module closes the process boundary.  The durability
contract has three parts:

* **Point-in-time snapshots.**  ``save(engine)`` captures the device KV
  pool leaves (paged pool + int8 scales, or the slot cache — bit-exact
  through the training checkpoint's bf16/fp8 view codec) together with the
  FULL host control plane: slot leases, page tables, ``BlockAllocator``
  refcounts, the ``RadixPrefixCache`` token→block chains, every live
  ``Request``'s lifecycle fields (status, accepted output, ``folded``
  high-water mark, preemption count, deadline as REMAINING budget),
  drafter history, engine counters, and the bounded compile-key list for
  warm re-jit.  Writes are atomic (``core.atomic.atomic_dir``: temp dir +
  ``os.replace``) — a snapshot interrupted mid-write is NEVER observed by
  restore; the previous complete one wins.

* **Write-ahead journal.**  An append-only JSONL of submit/emit/terminal
  events, fsync'd once per tick batch (and immediately on out-of-tick
  submits/cancels).  Each snapshot epoch N owns ``journal_N.jsonl``: the
  file records exactly what happened AFTER snapshot N, and the engine
  rotates to a fresh journal only after the next snapshot commits, so the
  (snapshot, journal) pair is always a consistent recovery point.  Chaos
  kills fire at the TOP of a tick — after the previous tick's fsync — so
  an emitted token is never lost and never duplicated.

* **Restore + replay.**  ``restore_engine(dir, params)`` (the body of
  ``Engine.restore``) loads the latest complete snapshot, warms the saved
  compile keys (one throwaway dispatch each, so the first real tick is not
  a cold jit), loads the device state bit-exactly, rebuilds the host
  control plane, then replays the epoch's journal: submits re-enter the
  queue, emits extend the owning request's accepted output, terminals
  retire (surfaced via ``engine.restored_terminal`` — the dead process's
  caller objects are gone).  Any live request whose output grew past the
  snapshot is re-folded into its prompt via the PR 8 ``_fold_slot``
  preemption primitive and requeued at the FRONT in admission order — so
  replayed admission is mostly prefix-cache page-table copies, and the
  resumed token streams are BITWISE equal to the never-killed engine's
  (hence to ``reference_decode``).  Journals are never pruned: the
  concatenation of every epoch's emits is each request's full durable
  token stream, exactly once, in order (``journaled_streams``) — the
  parity source the kill/restore chaos soak checks against the oracle.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import re
import shutil
from typing import Any

import jax.numpy as jnp
import numpy as np

from repro.core.atomic import atomic_dir
from repro.models import api
from repro.models.config import ModelConfig
from repro.serving.engine import Engine, Request, _Slot
from repro.train import checkpoint

SNAPSHOT_VERSION = 1
_SNAP_RE = re.compile(r"snap_(\d+)$")
_JOURNAL_RE = re.compile(r"journal_(\d+)\.jsonl$")

# engine counters that round-trip verbatim through the host manifest
_COUNTERS = (
    "steps", "dispatches", "mixed_ticks", "_occupancy_sum",
    "positions_computed", "positions_live",
    "peak_pool_blocks", "peak_resident_tokens", "admission_stalls",
    "prefix_hits", "prefix_hit_tokens", "cow_copies", "prefix_evictions",
    "preemptions", "deadline_misses", "row_faults", "cancels", "audits",
    "spec_ticks", "spec_rows", "spec_drafted", "spec_accepted",
    "spec_rewinds", "_admit_seq", "snapshots_taken",
)


# -- paths ------------------------------------------------------------------

def snap_path(root: str, epoch: int) -> str:
    return os.path.join(root, f"snap_{epoch:06d}")


def journal_path(root: str, epoch: int) -> str:
    return os.path.join(root, f"journal_{epoch:06d}.jsonl")


def snapshots(root: str) -> list[tuple[int, str]]:
    """Every COMPLETE snapshot under ``root``, epoch-ascending.  A dir is
    complete only when both its host manifest and its device manifest
    exist — ``.tmp`` turds and half-written dirs are invisible here, which
    is the torn-snapshot guarantee."""
    out = []
    if not os.path.isdir(root):
        return out
    for d in os.listdir(root):
        m = _SNAP_RE.match(d)
        if not m:
            continue
        p = os.path.join(root, d)
        if (os.path.isfile(os.path.join(p, "host.json")) and
                os.path.isfile(os.path.join(p, "device", "manifest.json"))):
            out.append((int(m.group(1)), p))
    return sorted(out)


def latest_snapshot(root: str) -> tuple[int, str]:
    snaps = snapshots(root)
    if not snaps:
        raise FileNotFoundError(f"no complete snapshot under {root!r}")
    return snaps[-1]


# -- write-ahead journal ----------------------------------------------------

class Journal:
    """Append-only JSONL event log.  ``append`` is line-buffered (a dying
    in-process engine still leaves whole lines); ``commit`` is the real
    durability point — flush + ``os.fsync``, called once per tick batch."""

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "a", buffering=1)
        self.appended = 0

    def append(self, ev: dict) -> None:
        self._f.write(json.dumps(ev) + "\n")
        self.appended += 1

    def commit(self) -> None:
        if self._f.closed:
            return
        self._f.flush()
        os.fsync(self._f.fileno())

    def close(self) -> None:
        if not self._f.closed:
            self.commit()
            self._f.close()


def read_journal(path: str) -> list[dict]:
    """Parse a journal; a torn trailing line (kill mid-write) ends the
    replay — everything before it was a complete, fsync-able record."""
    events: list[dict] = []
    if not os.path.exists(path):
        return events
    with open(path) as f:
        for line in f:
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                break
    return events


def journaled_streams(root: str) -> tuple[dict[int, list[int]],
                                          dict[int, str]]:
    """The durable per-request record across every epoch, in order.

    Returns ``(streams, status)``: ``streams[rid]`` is the full emitted
    token stream (each token journaled exactly once — snapshots restore
    output state but emits are only ever journaled when first generated),
    ``status[rid]`` the last journaled lifecycle word ("submitted" until a
    terminal event lands).  This is what the kill/restore soak diffs
    against the ``reference_decode`` oracle."""
    streams: dict[int, list[int]] = collections.defaultdict(list)
    status: dict[int, str] = {}
    epochs = sorted(
        (int(m.group(1)), os.path.join(root, d))
        for d in os.listdir(root)
        if (m := _JOURNAL_RE.match(d)) is not None)
    for _, path in epochs:
        for ev in read_journal(path):
            if ev["ev"] == "emit":
                streams[ev["rid"]].append(int(ev["tok"]))
            elif ev["ev"] == "submit":
                status.setdefault(ev["rid"], "submitted")
            elif ev["ev"] == "terminal":
                status[ev["rid"]] = ev["status"]
    return dict(streams), status


# -- config / request codecs ------------------------------------------------

def cfg_to_dict(cfg: ModelConfig) -> dict:
    d = dataclasses.asdict(cfg)
    d["dtype"] = jnp.dtype(cfg.dtype).name
    return d


def cfg_from_dict(d: dict) -> ModelConfig:
    d = dict(d)
    d["dtype"] = jnp.dtype(d["dtype"]).type
    if isinstance(d.get("mrope_sections"), list):
        d["mrope_sections"] = tuple(d["mrope_sections"])
    return ModelConfig(**d)


def _dump_req(req: Request, now: float) -> dict:
    """Serialize one LIVE request.  Times go out as ages/offsets from the
    save-time clock: a restored process has a different monotonic base, so
    deadlines are stored as REMAINING budget and re-anchored at load —
    downtime does not count against a request."""
    age = now - req.submitted_at
    return {
        "rid": req.rid,
        "prompt": np.asarray(req.prompt).tolist(),
        "max_new": req.max_new_tokens,
        "frames": (None if req.frames is None
                   else np.asarray(req.frames).tolist()),
        "priority": req.priority,
        "deadline_remaining": (None if req.deadline_s is None
                               else req.deadline_s - age),
        "output": [int(t) for t in req.output],
        "status": req.status,
        "error": req.error,
        "preemptions": req.preemptions,
        "folded": req.folded,
        "age": age,
        "admitted": (None if req.admitted_at is None
                     else req.admitted_at - req.submitted_at),
        "ttft": (None if req.first_token_at is None
                 else req.first_token_at - req.submitted_at),
        "token_offsets": [t - req.submitted_at for t in req.token_times],
    }


def _load_req(d: dict, now: float) -> Request:
    req = Request(
        rid=d["rid"],
        prompt=np.asarray(d["prompt"], np.int64),
        max_new_tokens=d["max_new"],
        frames=(None if d["frames"] is None
                else np.asarray(d["frames"], np.float32)),
        priority=d["priority"])
    req.output = [int(t) for t in d["output"]]
    req.status = d["status"]
    req.error = d["error"]
    req.preemptions = d["preemptions"]
    req.folded = d["folded"]
    req.submitted_at = now - d["age"]
    # remaining budget: the miss fires ``deadline_remaining`` seconds after
    # restore, regardless of how long the process was dead
    req.deadline_s = (None if d["deadline_remaining"] is None
                      else d["deadline_remaining"] + d["age"])
    # .get: a snapshot written before requests kept their admission time
    req.admitted_at = (None if d.get("admitted") is None
                       else req.submitted_at + d["admitted"])
    req.first_token_at = (None if d["ttft"] is None
                          else req.submitted_at + d["ttft"])
    req.token_times = [req.submitted_at + o for o in d["token_offsets"]]
    return req


# -- save -------------------------------------------------------------------

def _ctor_kwargs(eng: Engine) -> dict:
    return {
        "batch_size": eng.batch, "max_len": eng.max_len,
        "eos_id": eng.eos_id, "chunk_size": eng.chunk_size,
        "prefill_token_budget": eng.prefill_token_budget,
        "prefill_policy": eng.prefill_policy,
        "spec_k": eng.spec_requested,
        "prefix_cache": eng.prefix_requested,
        "max_preemptions": eng.max_preemptions,
        "enforce_deadlines": eng.enforce_deadlines,
        "check_finite": eng.check_finite,
        "audit_every": eng.audit_every,
        "snapshot_every": eng.snapshot_every,
        "snapshot_keep": eng.snapshot_keep,
        "journal": eng.journal_enabled,
    }


def _dump_host(eng: Engine, epoch: int) -> dict:
    now = eng.clock()
    live: dict[int, Request] = {}
    for s in eng._slots:
        if s.req is not None:
            live[s.req.rid] = s.req
    for r in eng._queue:
        live[r.rid] = r
    host: dict[str, Any] = {
        "version": SNAPSHOT_VERSION,
        "epoch": epoch,
        "cfg": cfg_to_dict(eng.cfg),
        "kwargs": _ctor_kwargs(eng),
        "counters": {k: getattr(eng, k) for k in _COUNTERS},
        "draft_wait": list(eng._draft_wait),
        "draft_penalty": list(eng._draft_penalty),
        "slots": [{
            "rid": None if s.req is None else s.req.rid,
            "length": s.length, "pos": s.pos,
            "last_token": s.last_token, "seq": s.seq,
        } for s in eng._slots],
        "requests": [_dump_req(r, now) for r in live.values()],
        "queue": [r.rid for r in eng._queue],
        "compile_keys": [[name, bucket]
                         for name, bucket in eng.cache_compiles.keys()],
        "prefix": None if eng.prefix is None else eng.prefix.dump(),
        "drafter": None if eng.drafter is None else eng.drafter.dump(),
    }
    if eng.paged:
        host["paged"] = {
            "page_table": eng._page_table.tolist(),
            "slot_blocks": [list(b) for b in eng._slot_blocks],
            "slot_reserve": list(eng._slot_reserve),
            "n_homes": eng.n_homes,
            "reserve_home": [list(v) for v in eng._reserve_home],
            "free": list(eng.alloc.free),
            "refs": list(eng.alloc.refs),
        }
    return host


def _write_snapshot(eng: Engine, root: str, epoch: int) -> str:
    final = snap_path(root, epoch)
    with atomic_dir(final) as tmp:
        with open(os.path.join(tmp, "host.json"), "w") as f:
            json.dump(_dump_host(eng, epoch), f)
        checkpoint.write_state(
            os.path.join(tmp, "device"),
            {"cache": api.export_cache(eng.cfg, eng.cache)},
            extra={"epoch": epoch}, step=epoch)
    return final


def _prune(root: str, keep: int) -> None:
    """Drop all but the newest ``keep`` complete snapshots.  Journals are
    never pruned: concatenated epochs are the full durable stream."""
    if not keep:
        return
    for _, path in snapshots(root)[:-keep]:
        shutil.rmtree(path, ignore_errors=True)


def save(eng: Engine) -> str:
    """Write snapshot epoch N+1 and rotate the journal to it.

    Order is the crash-consistency argument: commit the OLD journal, write
    the new snapshot atomically, and only then close the old journal and
    open the new epoch's — a kill anywhere in between leaves a complete
    (snapshot, journal) recovery pair on disk."""
    root = eng.snapshot_dir
    epoch = eng._snap_epoch + 1
    if eng._journal is not None:
        eng._journal.commit()
    final = _write_snapshot(eng, root, epoch)
    if eng._journal is not None:
        eng._journal.close()
    eng._snap_epoch = epoch
    if eng.journal_enabled:
        eng._journal = Journal(journal_path(root, epoch))
    eng.snapshots_taken += 1
    _prune(root, eng.snapshot_keep)
    return final


def attach(eng: Engine, root: str) -> None:
    """Start durability on a FRESH engine: take the baseline snapshot (so
    restore always has a complete snapshot to stand on) and open its
    journal.  Called from ``Engine.__init__`` when ``snapshot_dir`` is
    set; a stale store from a previous run just yields a higher epoch."""
    os.makedirs(root, exist_ok=True)
    eng.snapshot_dir = root
    snaps = snapshots(root)
    eng._snap_epoch = snaps[-1][0] if snaps else -1
    save(eng)


# -- restore ----------------------------------------------------------------

def _warm_executables(eng: Engine, keys: list) -> None:
    """Re-jit the dead process's executables by EXECUTING one throwaway
    dispatch per saved compile key, threading the pristine zero cache
    through the donated calls.  Runs BEFORE the device state loads, so the
    garbage these dispatches write is overwritten bit-exactly.  Keys that
    need request data (audio ``admit``) re-jit on demand instead."""
    b = eng.batch
    pt = jnp.asarray(eng._page_table) if eng.paged else None
    for name, bucket in keys:
        if name == "mixed":
            fn = eng.cache_compiles.get("mixed", bucket, eng._build_mixed)
            tokens = jnp.zeros((b, bucket), jnp.int32)
            q_lens = np.zeros(b, np.int32)
            q_lens[0] = min(2, bucket)
            args = (tokens, jnp.zeros((b,), jnp.int32), jnp.asarray(q_lens))
            if eng.paged:
                args += (pt,)
            out = fn(eng.params, eng.cache, *args)
            eng.cache = out[2]
        elif name == "decode":
            fn = eng.cache_compiles.get("decode", bucket, eng._build_decode)
            args = (jnp.zeros((b, 1), jnp.int32), jnp.ones((b,), jnp.int32))
            if eng.paged:
                args += (pt, jnp.zeros((b,), bool))
            out = fn(eng.params, eng.cache, *args)
            eng.cache = out[2]
        elif name == "insert":
            fn = eng.cache_compiles.get("insert", bucket, eng._build_insert)
            row = api.init_cache(eng._row_cfg, 1, eng.max_len)
            eng.cache = fn(eng.cache, row, np.int32(0))
        elif name == "cow":
            fn = eng.cache_compiles.get("cow", bucket, eng._build_cow)
            eng.cache = fn(eng.cache, np.int32(0), np.int32(0))


def _load_host(eng: Engine, host: dict) -> None:
    now = eng.clock()
    reqs = {d["rid"]: _load_req(d, now) for d in host["requests"]}
    eng._queue = collections.deque(reqs[rid] for rid in host["queue"])
    for i, sd in enumerate(host["slots"]):
        s = _Slot(req=None if sd["rid"] is None else reqs[sd["rid"]],
                  length=sd["length"], pos=sd["pos"],
                  last_token=sd["last_token"], seq=sd["seq"])
        eng._slots[i] = s
    eng._live_rids = set(reqs)
    if eng.paged:
        pg = host["paged"]
        eng._page_table = np.asarray(pg["page_table"], np.int32)
        eng._slot_blocks = [list(bs) for bs in pg["slot_blocks"]]
        eng._slot_reserve = list(pg["slot_reserve"])
        # block homes must round-trip: a snapshot taken under a mesh only
        # restores into an engine built under the same home topology (the
        # page-table block spread is meaningless otherwise)
        homes = int(pg.get("n_homes", 1))
        if homes != eng.n_homes:
            raise RuntimeError(
                f"snapshot was taken with {homes} block homes but the "
                f"restoring engine derived {eng.n_homes} — restore under "
                "the same device mesh the snapshot was saved under")
        if "reserve_home" in pg:
            eng._reserve_home = [[int(x) for x in v]
                                 for v in pg["reserve_home"]]
        else:           # pre-home snapshot: only valid single-home
            eng._reserve_home = [[int(r)] for r in eng._slot_reserve]
        eng.alloc.free = [int(x) for x in pg["free"]]
        eng.alloc.refs = [int(x) for x in pg["refs"]]
    if eng.prefix is not None and host["prefix"] is not None:
        eng.prefix.load(host["prefix"])
    if eng.drafter is not None and host["drafter"] is not None:
        eng.drafter.ngram_max = host["drafter"]["ngram_max"]
        eng.drafter.ngram_min = host["drafter"]["ngram_min"]
        eng.drafter.load(host["drafter"])
    for k in _COUNTERS:
        # a counter newer than the snapshot keeps the fresh engine's zero
        setattr(eng, k, host["counters"].get(k, getattr(eng, k)))
    eng._draft_wait = list(host["draft_wait"])
    eng._draft_penalty = list(host["draft_penalty"])


def _find_live(eng: Engine, rid: int) -> Request:
    for s in eng._slots:
        if s.req is not None and s.req.rid == rid:
            return s.req
    for r in eng._queue:
        if r.rid == rid:
            return r
    raise RuntimeError(f"journal references unknown live rid {rid}")


def _replay(eng: Engine, events: list[dict]) -> set[int]:
    """Apply one epoch's journal to the freshly loaded snapshot state, in
    order.  Returns the rids whose accepted output grew past the snapshot
    (and are still live) — those must be re-folded, because the restored
    device KV only covers the snapshot's lengths."""
    emitted: set[int] = set()
    for ev in events:
        kind = ev["ev"]
        if kind == "submit":
            eng.submit(Request(
                rid=ev["rid"],
                prompt=np.asarray(ev["prompt"], np.int64),
                max_new_tokens=ev["max_new"],
                priority=ev["priority"],
                deadline_s=ev["deadline"],
                frames=(None if ev["frames"] is None
                        else np.asarray(ev["frames"], np.float32))))
        elif kind == "emit":
            req = _find_live(eng, ev["rid"])
            now = eng.clock()
            if req.first_token_at is None:
                req.first_token_at = now
            req.output.append(int(ev["tok"]))
            req.token_times.append(now)
            emitted.add(ev["rid"])
        elif kind == "terminal":
            rid = ev["rid"]
            req = None
            for r in list(eng._queue):
                if r.rid == rid:
                    eng._queue.remove(r)
                    req = r
                    break
            if req is None:
                for i, s in enumerate(eng._slots):
                    if s.req is not None and s.req.rid == rid:
                        req = s.req
                        eng._free_slot(i)
                        break
            if req is None:
                continue            # already terminal (duplicate event)
            req.error = ev.get("error")
            if ev["status"] == "deadline_missed":
                eng.deadline_misses += 1
            elif ev["status"] == "cancelled":
                eng.cancels += 1
            elif ev["status"] == "error":
                eng.row_faults += 1
            eng._terminal(req, ev["status"])
            eng.restored_terminal.append(req)
            emitted.discard(rid)
    return emitted


def _fold_replayed(eng: Engine, emitted: set[int]) -> None:
    """Re-fold every live request whose output grew past the snapshot.

    Slot residents fold through the preemption primitive (donating their
    snapshot-resident blocks to the radix cache, so re-admission is mostly
    a page-table copy) and requeue at the FRONT in admission order; a
    restore-fold does NOT count against ``max_preemptions`` — the request
    did nothing wrong.  Queued requests (admitted and preempted entirely
    after the snapshot) fold prompt-only."""
    resident = sorted(
        ((s.seq, i) for i, s in enumerate(eng._slots)
         if s.req is not None and s.req.rid in emitted),
        reverse=True)
    for _, i in resident:
        # front-requeue in reverse seq order leaves the queue seq-ascending
        req = eng._slots[i].req
        eng._fold_slot(i)
        req.status = "queued"
        eng._free_slot(i)
        eng._queue.appendleft(req)
    for r in eng._queue:
        if r.rid in emitted and len(r.output) > r.folded:
            r.prompt = np.concatenate([
                np.asarray(r.prompt, np.int64),
                np.asarray(r.output[r.folded:], np.int64)])
            r.folded = len(r.output)


def restore_engine(root: str, params: Any, **overrides) -> Engine:
    """Rebuild a process-equivalent engine from the latest complete
    snapshot + its journal.  See the module docstring for the contract;
    ``Engine.restore`` is the public face of this function."""
    epoch, snapdir = latest_snapshot(root)
    with open(os.path.join(snapdir, "host.json")) as f:
        host = json.load(f)
    cfg = cfg_from_dict(host["cfg"])
    kwargs = dict(host["kwargs"])
    kwargs.update(overrides)
    eng = Engine(cfg, params, **kwargs)     # snapshot_dir wired after replay
    _warm_executables(eng, host["compile_keys"])
    state, _ = checkpoint.read_state(os.path.join(snapdir, "device"),
                                     {"cache": eng.cache})
    eng.cache = state["cache"]
    _load_host(eng, host)
    emitted = _replay(eng, read_journal(journal_path(root, epoch)))
    _fold_replayed(eng, emitted)
    eng.audit()
    # resume durability on the SAME epoch: post-restore events append to
    # its journal, so concatenated epochs stay the full exactly-once stream
    eng.snapshot_dir = root
    eng._snap_epoch = epoch
    if eng.journal_enabled:
        eng._journal = Journal(journal_path(root, epoch))
    return eng
