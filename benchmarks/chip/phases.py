"""Reduction of a profiler trace by the program's own instrumentation: the
serving engine's ``engine.*`` host spans, the named scopes of its step
(``attention``, ``ffn``, ``lm_head``, ``embed``) and its named executables
(``jit_mixed_step``, ``jit_decode_step``, ...).

``trace.py`` reduces the same ``.xplane.pb`` by the benchmark's ``bench.*``
spans alone; the traced window ``[lo, hi]`` given here is the one it takes
(first ``bench.*`` start to last end), so nothing it reports moves.  A trace
of a program without this instrumentation yields no engine span, no scope
and no module, and every reader of what it yields returns None.

Each device op's scope is read from the op path that XLA keeps in the
instruction's metadata (``jit(mixed_step)/while/body/closed_call/attention/
dot_general``).  The TPU's ``XLA Ops`` events carry no such stat: the path
comes from the HLO of each program, which the trace keeps in its
``/host:metadata`` plane, looked up by the op's name in the program whose
run on the ``XLA Modules`` line covers it.  A ``while`` op holds the ops of
its body on the same line, so scope times count leaf ops only.
"""

from __future__ import annotations

import glob
import os

import trace

ENGINE_PREFIX = "engine."
SCOPES = ("attention", "ffn", "lm_head", "embed")
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


def scope_of(path: str) -> str | None:
    """The innermost named scope on an op path, or None."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return None


# -- the protobuf wire format, as far as the trace's HLO needs it -----------
# Field numbers: XSpace.planes 1; XPlane.name 2, .event_metadata 4 (a map:
# key 1, value 2), .stat_metadata 5; XEventMetadata.name 2, .stats 5;
# XStat.metadata_id 1, .bytes_value 6; XStatMetadata.id 1, .name 2;
# HloProto.hlo_module 1; HloModuleProto.computations 3;
# HloComputationProto.instructions 2; HloInstructionProto.name 1,
# .metadata 7; OpMetadata.op_name 2 (tsl xplane.proto, xla hlo.proto).

def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one protobuf message: an int
    for a varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _field(buf, number: int, default=None):
    return next((v for n, v in _fields(buf) if n == number), default)


def _hlo_paths(hlo_proto) -> dict[str, str]:
    """Instruction name -> op path of one program's ``HloProto``."""
    paths = {}
    for n, comp in _fields(_field(hlo_proto, 1, b"")):
        if n != 3:
            continue
        for m, inst in _fields(comp):
            if m != 2:
                continue
            name, meta = None, None
            for k, v in _fields(inst):
                if k == 1:
                    name = bytes(v).decode()
                elif k == 7:
                    meta = v
            if name is not None and meta is not None:
                paths[name] = bytes(_field(meta, 2, b"")).decode()
    return paths


def op_paths(xspace: bytes) -> dict[str, dict[str, str]]:
    """Program name (``jit_mixed_step(<id>)``, as the ``XLA Modules`` line
    names its runs) -> instruction name -> op path, from the HLO that the
    serialized trace keeps in its metadata plane."""
    out = {}
    for n, plane in _fields(xspace):
        if n != 1 or bytes(_field(plane, 2, b"")).decode() != METADATA_PLANE:
            continue
        stat_names = {}
        for k, v in _fields(plane):
            if k == 5:
                md = _field(v, 2)
                stat_names[_field(md, 1, 0)] = bytes(
                    _field(md, 2, b"")).decode()
        for k, v in _fields(plane):
            if k != 4:
                continue
            md = _field(v, 2)
            name = bytes(_field(md, 2, b"")).decode()
            for j, stat in _fields(md):
                if j == 5 and stat_names.get(_field(stat, 1, 0)) == HLO_STAT:
                    out[name] = _hlo_paths(_field(stat, 6, b""))
    return out


def _scopes(ops: list, runs: list, paths: dict) -> list:
    """Each op of one device as ``(name, start, end, scope)``: the scope of
    its instruction in the program whose run covers it."""
    runs = sorted(runs, key=lambda r: r[1])
    out, j = [], 0
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        while j < len(runs) and runs[j][2] < s:
            j += 1
        program = runs[j][0] if j < len(runs) and runs[j][1] <= s else None
        path = paths.get(program, {}).get(name.lstrip("%"))
        out.append((name, s, e, scope_of(path) if path else None))
    return out


def load(logdir: str) -> dict:
    """The newest trace under ``logdir``, read once: ``bench`` spans and
    device ``ops`` as ``trace.load`` gives them, ``engine`` spans as
    ``(name, start_ns, end_ns, args)``, each device's ops as ``(name,
    start_ns, end_ns, scope)`` in ``scoped``, and each device's step runs
    as ``(program, start_ns, end_ns)`` in ``modules``."""
    from jax.profiler import ProfileData

    out = {"bench": [], "engine": [], "ops": {}, "scoped": {},
           "modules": {}}
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return out
    with open(files[-1], "rb") as f:
        xspace = f.read()
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        device = plane.name.startswith(trace.DEVICE_PLANE)
        for line in plane.lines:
            if device and line.name == trace.OPS_LINE:
                out["ops"].setdefault(plane.name, []).extend(
                    (trace.op_name(e.name), e.start_ns,
                     e.start_ns + e.duration_ns) for e in line.events)
            elif device and line.name == MODULES_LINE:
                out["modules"].setdefault(plane.name, []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not device:
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name.startswith(trace.SPAN_PREFIX):
                        out["bench"].append((e.name, e.start_ns, end))
                    elif e.name.startswith(ENGINE_PREFIX):
                        out["engine"].append(
                            (e.name, e.start_ns, end, dict(e.stats)))
    paths = op_paths(xspace) if out["ops"] else {}
    out["scoped"] = {dev: _scopes(ops, out["modules"].get(dev, []), paths)
                     for dev, ops in out["ops"].items()}
    return out


def leaves(ops: list) -> list:
    """The ops of one device line that hold no other op of the line (a
    ``while`` holds its body's ops; events on one line nest, never
    cross)."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= o[2]]


def scope_seconds(scoped: dict, lo: float, hi: float) -> dict | None:
    """Seconds of leaf device ops under each scope (``None``: under none)
    inside ``[lo, hi]``, mean over devices; None where no op carries a
    scope (a program whose step names none)."""
    if not any(op[3] for dev_ops in scoped.values() for op in dev_ops):
        return None
    total: dict = {}
    for dev_ops in scoped.values():
        for _, s, e, scope in leaves(dev_ops):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                total[scope] = total.get(scope, 0.0) + d * 1e-9
    return {k: v / len(scoped) for k, v in total.items()}


def step_ms(modules: dict, lo: float, hi: float) -> dict:
    """Mean device milliseconds per run of each step executable (its
    program's name without the ``jit_`` prefix and the program id), over
    runs wholly inside ``[lo, hi]`` on every device."""
    runs: dict = {}
    for dev_runs in modules.values():
        for program, s, e in dev_runs:
            name = program.split("(", 1)[0]
            if lo <= s and e <= hi and name.startswith("jit_"):
                runs.setdefault(name[len("jit_"):], []).append((e - s) * 1e-6)
    return {k: sum(v) / len(v) for k, v in runs.items()}


def engine_ticks(spans: list, lo: float, hi: float) -> list[dict]:
    """Each ``engine.tick`` span wholly inside ``[lo, hi]`` that dispatched
    a step, with its arguments, its seconds and its children's seconds by
    name."""
    ticks = []
    for name, s, e, args in spans:
        if name != "engine.tick" or "kind" not in args or not (
                lo <= s and e <= hi):
            continue
        kids: dict = {}
        for n, cs, ce, _ in spans:
            if n != "engine.tick" and s <= cs and ce <= e:
                kids[n] = kids.get(n, 0.0) + (ce - cs) * 1e-9
        ticks.append({**args, "s": (e - s) * 1e-9, "phases": kids})
    return ticks


def idle_gaps(ops: dict, spans: list, lo: float, hi: float,
              top: int = 10) -> list:
    """The longest device idle gaps inside ``[lo, hi]``, each labelled with
    the innermost host span (``bench.*`` or ``engine.*``) covering its
    middle, as ``trace.reduce`` labels them by ``bench.*`` spans."""
    gaps = []
    for dev_ops in ops.values():
        merged = trace.union([(s, e) for _, s, e in dev_ops], lo, hi)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append([trace._span_at(spans, (s + e) / 2),
                             (e - s) * 1e-9])
    return sorted(gaps, key=lambda g: -g[1])[:top]


def reduce(data: dict, top: int = 10) -> dict | None:
    """The per-layer reduction of ``load``'s result over ``trace.py``'s
    window; None where the trace holds no ``bench.*`` span or device op."""
    bench = data["bench"]
    if not bench or not any(data["ops"].values()):
        return None
    lo = min(s for _, s, _ in bench)
    hi = max(e for _, _, e in bench)
    busy = sum(sum(e - s for s, e in trace.union(
        [(s, e) for _, s, e in dev_ops], lo, hi)) * 1e-9
        for dev_ops in data["ops"].values()) / len(data["ops"])
    spans = bench + [(n, s, e) for n, s, e, _ in data["engine"]]
    return {
        "busy_s": busy,
        "scope_s": scope_seconds(data["scoped"], lo, hi),
        "step_ms": step_ms(data["modules"], lo, hi),
        "ticks": engine_ticks(data["engine"], lo, hi),
        "idle_gaps": idle_gaps(data["ops"], spans, lo, hi, top),
    }


def share(run, scope: str | None) -> float | None:
    """A scope's leaf device time over the device's busy time, in %, from
    the ``phases`` reduction a traced run carries; None without one."""
    p = getattr(run, "phases", None)
    if p is None or not p["scope_s"] or not p["busy_s"]:
        return None
    return p["scope_s"].get(scope, 0.0) / p["busy_s"] * 100.0
