"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/bench.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  A cell is an entry of ``BENCHMARK.json``'s
``workloads``; it names a configuration (``configs/<name>.json``) and a
traffic mix (``traffic/<name>.json``, generated by ``traffic/<kind>.py``),
and its engine sizing and the limit of its check sit in
``cells/<cell>.json``.  The configuration names its model family, whose
module ``families/<family>.py`` gives the weights' roles, the check of the
program's sizes, the plain reference and the counts of work (see
``families/dense.py``).  Each metric is read by ``metrics/<name>.py``.

Set-up builds the served weights on the device from the seed, allocates
the cell's paged KV pool (sized by ``size_pool.py`` from the chip's
measured peak, to fill its memory but a margin), warms up every
executable the cell's traffic uses, and runs the traffic for ``ramp_s``
seconds.  The window then drives the engine's own
entry, ``Engine.submit`` and ``Engine.run(max_steps=1)``, one tick at a
time, for ``--seconds``.  With ``--trace 1`` its last seconds are traced and
the per-layer metrics are printed; otherwise the end-to-end ones.  After the
close the engine runs on under the same traffic until every request due in
the window has its first token (``DRAIN_S`` at most), so that each time to
first token is whole.  Then the engine is freed and a sample of the requests it finished is
checked against the plain reference (``check.py``).

The last line of standard output is the result as one JSON object.  Without
a TPU, or with fewer chips than the cell asks for, it prints no result and
exits with 3.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import warnings  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
FAMILIES = HERE / "families"
# what the harness takes from a family's module (families/dense.py)
FAMILY_NEEDS = ("ROLES", "GENERATORS", "groups", "program_sizes", "logits",
                "tick_flops", "tick_bytes", "tick_floor_s", "weight_bytes",
                "kv_bytes_per_token")
TRACE_S = 3.0          # traced seconds at the end of a --trace 1 window
DRAIN_S = 60.0         # longest wait past the close for a first token


def load_cell(name: str) -> dict:
    """The cell's entry with its configuration, traffic mix, sizing and
    metrics, read from ``BENCHMARK.json`` and the files it names."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "cell": cell,
        "config": config,
        "family": load_family(config),
        "traffic": json.loads(
            (HERE / "traffic" / f"{cell['traffic']}.json").read_text()),
        "sizing": json.loads((HERE / "cells" / f"{name}.json").read_text()),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_family(config: dict, directory: Path = FAMILIES):
    """The module of the configuration's model family,
    ``<directory>/<family>.py``; stops with a message where the
    configuration names none, or one with no such file, or the file lacks
    a name of ``FAMILY_NEEDS``."""
    name = config.get("family")
    known = sorted(f.stem for f in directory.glob("*.py"))
    if name is None:
        raise SystemExit(f"configuration {config['name']!r} names no model "
                         f"family: give it \"family\", one of {known}")
    path = directory / f"{name}.py"
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]*", name) or \
            not path.is_file():
        raise SystemExit(f"configuration {config['name']!r} names model "
                         f"family {name!r}, which has no file "
                         f"{directory.name}/{name}.py; known: {known}")
    spec = importlib.util.spec_from_file_location(f"family_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [n for n in FAMILY_NEEDS if not hasattr(mod, n)]
    if missing:
        raise SystemExit(f"model family {name!r} ({path.name}) lacks "
                         f"{missing}")
    return mod


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def require_chips(n: int):
    """The first device, when JAX sees at least ``n`` TPU chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"needs {n} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return devs[0]


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def program_config(config: dict, family, pool_blocks: int = 0):
    """The program's ModelConfig for this configuration, checked against
    the sizes the configuration states, over the keys of its family's
    ``program_sizes``."""
    from repro.configs import get_config

    cfg = get_config(config["arch"], kv_layout="paged",
                     kv_pool_blocks=pool_blocks, **config["overrides"])
    m = config["model"]
    wrong = {k: (v, m.get(k)) for k, v in family.program_sizes(cfg).items()
             if v != m.get(k)}
    if wrong:
        raise SystemExit(f"program config differs from {config['name']}: "
                         f"{wrong}")
    return cfg


def build_weights(cfg, config: dict, family, key):
    """The served, packed parameter tree, made on the device in one call."""
    import jax

    from repro.core.compiler import quantize_model
    from repro.models import api

    import weights

    served = config["served"]
    shapes = jax.eval_shape(lambda k: api.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    build = jax.jit(lambda k: weights.build_served(
        k, shapes, family, config["model"], served,
        lambda t: quantize_model(t, served["strategy"])))
    params = build(key)
    jax.block_until_ready(params)
    return params


# ---------------------------------------------------------------------------
# driving the engine
# ---------------------------------------------------------------------------

FAILED = ("error", "cancelled", "deadline_missed")


class Driver:
    """Submits the mix's requests and ticks the engine, recording every
    tick and every request on the host clock."""

    def __init__(self, engine, mix, clock=time.monotonic):
        from repro.serving.engine import Request

        self.Request = Request
        self.engine = engine
        self.mix = mix
        self.clock = clock
        self.reqs: dict[int, dict] = {}
        self.objs: dict[int, object] = {}
        self.pending: set[int] = set()
        self.ticks: list[tuple] = []
        self.lateness: list[float] = []

    def _rows(self) -> dict:
        """Resident tokens of each request in a slot (the engine's host
        mirror of its cache rows)."""
        return {s.req.rid: s.length for s in self.engine._slots
                if s.req is not None}

    def _submit(self, now: float) -> None:
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("bench.submit"):
            for rid, due, prompt, max_new in self.mix.take(now):
                req = self.Request(rid=rid, prompt=prompt,
                                   max_new_tokens=max_new)
                self.engine.submit(req)
                self.objs[rid] = req
                self.pending.add(rid)
                self.reqs[rid] = {"rid": rid, "due": due,
                                  "sent": req.submitted_at,
                                  "prompt_len": len(prompt),
                                  "max_new": max_new, "admitted": None}
                self.lateness.append(req.submitted_at - due)

    def drive(self, until: float, on_tick=None, first_of=()) -> None:
        """Tick until ``until``, or until every request of ``first_of``
        has its first token."""
        from jax.profiler import TraceAnnotation

        eng = self.engine
        # one tick per call leaves work in flight by design
        warnings.filterwarnings("ignore", message="Engine.run hit max_steps")
        while True:
            now = self.clock()
            if on_tick is not None:
                on_tick(now)
            if now >= until or (first_of and all(
                    self.objs[rid].first_token_at is not None
                    or self.objs[rid].status in FAILED
                    for rid in first_of)):
                return
            self._submit(now)
            before = self._rows()
            steps = eng.steps
            mixed = eng.mixed_ticks
            t0 = self.clock()
            with TraceAnnotation("bench.tick"):
                done = eng.run(max_steps=1)
            t1 = self.clock()
            if eng.steps == steps:          # nothing to run: wait for work
                nxt = min(self.mix.next_due(), until)
                with TraceAnnotation("bench.wait"):
                    while self.clock() < nxt:
                        time.sleep(min(0.0005, max(nxt - self.clock(), 0)))
                continue
            after = self._rows()
            rows = []
            for rid in set(before) | set(after) | {r.rid for r in done}:
                n0 = before.get(rid, 0)
                if rid in after:
                    n1 = after[rid]
                else:
                    r = self.objs[rid]
                    n1 = len(r.prompt) + len(r.output) - 1
                rows.append((n0, n1 - n0))
            self.ticks.append((t0, t1, eng.mixed_ticks > mixed, rows))
            for rid in list(self.pending):
                if self.objs[rid].status != "queued":
                    self.reqs[rid]["admitted"] = t0
                    self.pending.discard(rid)
            for r in done:
                self.mix.done(r.rid, t1)

    def finish(self) -> list[dict]:
        for rid, rec in self.reqs.items():
            r = self.objs[rid]
            rec.update(first=r.first_token_at, times=list(r.token_times),
                       status=r.status, out=list(r.output),
                       prompt=r.prompt)
        return list(self.reqs.values())


def warm_up(engine, vocab: int) -> None:
    """One request per chunk-width bucket, each decoding once: every
    executable the traffic can reach is compiled and run before timing."""
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(0)
    for i, w in enumerate(engine.chunk_buckets.all_buckets()):
        engine.submit(Request(rid=-1 - i, max_new_tokens=2,
                              prompt=rng.integers(0, vocab, w,
                                                  dtype=np.int32)))
        engine.run()


class Run:
    """What the metric readers read: the window's requests and ticks, the
    trace's reduction, the engine's counters, the sizes, and the model
    family whose counts apply (``family``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def in_window(self, t) -> bool:
        return t is not None and self.ws <= t <= self.we

    @property
    def window_requests(self) -> list[dict]:
        """Requests due (open loop) or sent (closed loop) in the window."""
        return [r for r in self.requests if self.ws <= r["due"] < self.we]

    @property
    def window_ticks(self) -> list[tuple]:
        """(start, end, mixed, rows) of each tick wholly in the window."""
        return [t for t in self.ticks if self.ws <= t[0] and t[1] <= self.we]

    def ttfts(self) -> list[float]:
        """Time to first token of each window request from its due time,
        its first token waited for past the close; one with none by the
        end of that wait counts with its time so far."""
        return [(r["first"] if r["first"] is not None else self.te)
                - r["due"] for r in self.window_requests]

    def ttfts_to_close(self) -> list[float]:
        """The same, with each time cut at the close."""
        return [min(r["first"] if r["first"] is not None else self.we,
                    self.we) - r["due"] for r in self.window_requests]

    def itl_gaps(self) -> list[float]:
        """Gaps between consecutive output tokens ending in the window."""
        return [b - a for r in self.requests
                for a, b in zip(r["times"], r["times"][1:])
                if self.in_window(b)]

    def window_tokens(self) -> int:
        return sum(self.in_window(t) for r in self.requests
                   for t in r["times"])


_COMPILES = {"backend": 0, "hits": 0, "misses": 0}


def _count_compiles() -> None:
    """Count backend compiles and persistent-cache hits and misses."""
    import jax

    def on_event(event, *a, **k):
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILES["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _COMPILES["misses"] += 1

    def on_duration(event, secs, **k):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILES["backend"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def set_up(spec: dict, seed: int, dev) -> dict:
    """Weights, pool and warmed-up engine of one cell."""
    import jax

    from repro.serving.engine import Engine

    import weights

    config, sizing, family = spec["config"], spec["sizing"], spec["family"]
    rows, max_len = sizing["rows"], sizing["max_len"]
    split = {}
    t = time.monotonic()
    cfg = program_config(config, family)
    key = weights.base_key(seed)
    params = build_weights(cfg, config, family, key)
    split["weights_s"] = time.monotonic() - t
    t = time.monotonic()
    blocks = sizing["pool_blocks"]
    cfg = program_config(config, family, pool_blocks=blocks)
    engine = Engine(cfg, params, batch_size=rows, max_len=max_len,
                    chunk_size=sizing["chunk"])
    jax.block_until_ready(engine.cache)
    split["pool_s"] = time.monotonic() - t
    t = time.monotonic()
    warm_up(engine, cfg.vocab_size)
    split["warmup_s"] = time.monotonic() - t
    split["compiles_at_warmup"] = dict(_COMPILES)
    return {"cfg": cfg, "key": key, "params": params, "engine": engine,
            "split": split, "blocks": blocks}


def measure(ctx: dict, mix_spec: dict, seed: int, seconds: float,
            trace: bool) -> tuple:
    """Ramp, the window, then the wait for the window's first tokens;
    returns (Driver, ws, we, te, in-window counts, trace summary)."""
    import jax

    import traffic

    engine = ctx["engine"]
    mix = traffic.load(mix_spec, seed, mix_spec["ramp_s"], seconds,
                       ctx["cfg"].vocab_size)
    drv = Driver(engine, mix)
    t_ramp = time.monotonic()
    mix.start(t_ramp)
    # the window opens on the mix's schedule, not when the ramp's last tick
    # ends, so that every seed's window holds the arrivals its mix drew
    ws = t_ramp + mix_spec["ramp_s"]
    we = ws + seconds
    drv.drive(ws)
    ctx["split"]["ramp_s"] = time.monotonic() - t_ramp
    misses0, backend0 = engine.cache_compiles.misses, _COMPILES["backend"]
    steps0, occ0 = engine.steps, engine.slot_occupancy
    traced = {"on": False}
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    def on_tick(now):
        if trace and not traced["on"] and now >= we - TRACE_S:
            jax.profiler.start_trace(str(TRACE_DIR))
            traced["on"] = True

    drv.drive(we, on_tick)
    if traced["on"]:
        jax.profiler.stop_trace()
    steps1, occ1 = engine.steps, engine.slot_occupancy
    counts = {"compiles": _COMPILES["backend"] - backend0,
              "new_executables": engine.cache_compiles.misses - misses0,
              "occupancy": ((occ1 * steps1 - occ0 * steps0) /
                            (steps1 - steps0) if steps1 > steps0 else None)}
    drv._submit(we)             # arrivals due in the window's last tick
    due = [rid for rid, r in drv.reqs.items() if ws <= r["due"] < we]
    drv.drive(we + DRAIN_S, first_of=due)
    te = time.monotonic()
    summary = None
    if traced["on"]:
        import trace as trace_mod
        summary = trace_mod.reduce(*trace_mod.load(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return drv, ws, we, te, counts, summary


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, dev,
             peaks: dict, log=print, control: bool = False) -> dict:
    """One run of a cell; returns the result object (without printing).
    With ``control`` the float8 control's tokens take the served tokens'
    place in the check, so ``correct`` judges the control (the program's
    own gap is kept as ``program_gap``)."""
    import jax
    import numpy as np

    import check

    config, sizing = spec["config"], spec["sizing"]
    ctx = set_up(spec, seed, dev)
    drv, ws, we, te, counts, summary = measure(ctx, spec["traffic"], seed,
                                               seconds, trace)
    setup_s = ws - T_START
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", 0)
    requests = drv.finish()
    bs = ctx["cfg"].kv_block_size
    lat = sorted(drv.lateness)
    log(json.dumps({"setup": {
        **{k: round(v, 3) if isinstance(v, float) else v
           for k, v in ctx["split"].items()},
        "setup_s": round(setup_s, 3),
        "pool_blocks": ctx["blocks"],
        "pool_tokens": ctx["blocks"] * bs, "compiles": dict(_COMPILES),
        "in_window": counts, "peak_bytes": peak,
        "generator_lag_p99_s": (lat[int(0.99 * (len(lat) - 1))]
                                if lat else None)}}), file=sys.stderr)
    run = Run(ws=ws, we=we, te=te, seconds=seconds, requests=requests,
              ticks=drv.ticks, occupancy=counts["occupancy"], trace=summary,
              setup_s=setup_s, model=config["model"],
              served=config["served"], family=spec["family"], peaks=peaks)
    ttft = run.ttfts()
    if ttft:
        log(json.dumps({"ttft": {
            "p95_s": float(np.percentile(ttft, 95)),
            "p95_to_close_s": float(np.percentile(run.ttfts_to_close(), 95)),
            "drain_s": te - we,
            "requests": [[r["prompt_len"], round(r["due"] - ws, 3),
                          round(t, 3)]
                         for r, t in zip(run.window_requests, ttft)]}}),
            file=sys.stderr)
    metrics = {}
    for m in spec["per_layer"] if trace else spec["end_to_end"]:
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the program's state is freed before the reference runs
    t_check = time.monotonic()
    key = ctx["key"]
    del ctx, drv
    gc.collect()
    picked = check.sample([r for r in requests if r["status"] == "done"],
                          seed, sizing["check_tokens"],
                          sizing["check_requests"])
    program_gap, control_gap, compared = check.gaps(
        spec["family"], key, config["model"], config["served"], picked,
        control)
    gap = control_gap if control else program_gap
    limit = sizing["gap_limit"]
    window = run.window_requests
    result = {
        "correct": bool(picked) and gap <= limit,
        "attempted": len(window),
        "failed": sum(r["status"] in FAILED for r in window),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count(), "memory_peak_bytes": peak},
    }
    if summary is not None:
        result["device"].update(busy_s=summary["busy_s"],
                                window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    log(json.dumps({"check": {"requests": len(picked),
                              "tokens_compared": compared,
                              "seconds": time.monotonic() - t_check}}),
        file=sys.stderr)
    if control:
        result["program_gap"] = program_gap
    result["checks"] = {"max_logit_gap": {"value": gap, "limit": limit}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    _count_compiles()
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    dev = require_chips(spec["cell"]["chips"])
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace), dev,
                      peaks_for(dev.device_kind))
    checks = result["checks"]
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
