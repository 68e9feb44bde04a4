#!/usr/bin/env python3
"""Bring-up smoke of the served path on TPU, at qwen-7b's published widths.

    python3 chip_smoke.py          # one chip: phases 1-3
    python3 chip_smoke.py --mesh   # four chips: the sharded paged engine
                                   # against the single-device engine

1. Device: JAX must report a TPU.  There is no CPU fallback.
2. qwen-7b (``configs/qwen_7b.py``: 32 layers at published widths, random
   weights from ``--seed``), strategy2 packing (``core.compiler``), paged
   KV, on the XLA twins: a few requests through ``Engine``.  Every request
   drains and ``Engine.audit()`` holds after every tick.
3. The same on the Pallas kernels (``use_kernels=True``).  The compiled
   mixed step holds the Mosaic kernels for attention and FFN, no op gives
   way to its twin, and each request's last-prompt-position logits agree
   with phase 2 within ``KERNEL_LOGIT_TOL``.

``--mesh`` runs only the sharded paged engine on a ``(1, 4)`` mesh and the
single-device engine it is compared with: every request drains, the audit
holds on every block home, each chip holds its own quarter of the pool
rows, and the logits agree within the mesh parity tests' bound.

Set-up numbers (compile seconds, peak device bytes, dispatch and
compile-key counts) are printed as they come; no speed is printed.  The
last line of stdout is one JSON object, ``{"ok": true, "device": {...}}``,
printed only when every phase passed.  Everything runs in this process,
which holds the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.compiler import init_quantized, quantized_bytes  # noqa: E402
from repro.kernels.ops import KernelFallbackWarning  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import api  # noqa: E402
from repro.parallel.hints import use_mesh  # noqa: E402
from repro.serving.engine import Engine, Request  # noqa: E402

ARCH, STRATEGY = "qwen-7b", "strategy2"
N_REQUESTS = 4          # one engine row each
PROMPT_LEN = (8, 16)    # inclusive; 16 is the smallest chunk bucket
MAX_NEW_TOKENS = 8
MAX_LEN = 256
# Kernels against twins, max |difference| over max |logit|, per request.
# The residual stream is bf16: every layer rounds its outputs to a step of
# 2^-8 (0.4%) of their size.  Kernels and twins add their f32 partial sums
# in different orders (per 128-group on the MXU against one einsum; the
# flash walk against the blocked twin), so roundings flip at different
# elements, and 32 layers compound the flips.  5% of the logit scale is
# about a dozen such steps in one direction; a wrong scale row, page or mask
# moves the logits by their whole scale.
KERNEL_LOGIT_TOL = 5e-2
# The mesh parity tests' bound on max |logit difference|
# (tests/test_mesh_serving.py, TestMeshParity): float32 activations and
# full-precision matmuls, so the mesh may differ only by the order of its
# f32 reductions.
MESH_LOGIT_TOL = 2e-4
# Four chips cost four times as much for each second; the mesh changes
# where pool rows live and how attention partials merge, which every layer
# does alike, so the mesh phase cuts depth to this.
MESH_LAYERS = 4
ATTENTION_KERNEL = "mixed_flash_attention"
FFN_KERNEL_PREFIX = "ffn_fused_"

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]


def _on_duration(event: str, secs: float, **_) -> None:
    if event == _BACKEND_COMPILE:
        _compile_s[0] += secs


class PhaseFailed(RuntimeError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseFailed(msg)


def report(phase: str, **fields) -> None:
    print(f"{phase} " + json.dumps(fields, default=str), flush=True)


def model_config(*, use_kernels: bool, **overrides):
    return get_config(ARCH, kv_layout="paged", use_kernels=use_kernels,
                      **overrides)


def build_params(cfg, seed: int):
    return init_quantized(lambda k: api.init_params(cfg, k),
                          jax.random.PRNGKey(seed), STRATEGY)


def workload(cfg, seed: int) -> list[Request]:
    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_LEN
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(lo, hi + 1))
                                        ).astype(np.int32),
                    max_new_tokens=MAX_NEW_TOKENS)
            for i in range(N_REQUESTS)]


def serve(cfg, params, reqs: list[Request], *, max_len: int = MAX_LEN):
    """Run ``reqs`` through one ``Engine`` with one row per request.

    Returns the engine, the token streams and every logits row the engine
    sampled a token from, in sampling order.  Every prompt fits one chunk
    and every request has a row, so the first tick prefills them all (the
    engine's one mixed tick is checked below) and each later tick decodes
    them all; a tick's rows emit in slot order, which is submit order.  So
    row ``t * len(reqs) + i`` is request ``i``'s ``t``-th token, and the
    first ``len(reqs)`` rows are the last prompt positions."""
    rows: list[np.ndarray] = []

    def sample(row):
        rows.append(np.asarray(row, np.float32))
        return int(np.argmax(row))

    engine = Engine(cfg, params, batch_size=len(reqs), max_len=max_len,
                    audit_every=1)
    for r in reqs:
        engine.submit(r)
    done = engine.run(sample=sample)
    engine.audit()
    check(done.drained and len(done) == len(reqs),
          f"engine did not drain: {len(done)}/{len(reqs)} finished, "
          f"truncated={done.truncated} stalled={done.stalled}")
    check(all(r.status == "done" and len(r.output) == r.max_new_tokens
              for r in reqs),
          f"request outcomes {[(r.status, len(r.output)) for r in reqs]}")
    check(engine.mixed_ticks == 1,
          f"{engine.mixed_ticks} mixed ticks: the prompts did not prefill in "
          "one tick, so logits rows cannot be matched to requests")
    check(all(np.isfinite(x).all() for x in rows), "non-finite logits")
    return engine, [list(r.output) for r in reqs], rows


def engine_counts(engine) -> dict:
    return {"ticks": engine.steps, "dispatches": engine.dispatches,
            "mixed_ticks": engine.mixed_ticks,
            "compile_keys": sorted(engine.cache_compiles.keys()),
            "compile_misses": engine.cache_compiles.misses,
            "compile_budget": engine.compile_budget,
            "audits": engine.audits}


def mosaic_kernels(hlo_text: str) -> set[str]:
    """Names of the Mosaic (Pallas) kernels in a compiled program's text."""
    return {m.group(1) for m in re.finditer(
        r"%([A-Za-z_]\w*?)(?:\.\d+)* = [^\n]*"
        r'custom_call_target="tpu_custom_call"', hlo_text)}


def compiled_mixed_step(engine) -> str:
    """The text of the engine's compiled mixed-tick executable."""
    (w,) = [w for name, w in engine.cache_compiles.keys() if name == "mixed"]
    fn = engine.cache_compiles.get("mixed", w, engine._build_mixed)
    b = engine.batch
    args = (jnp.zeros((b, w), jnp.int32), jnp.zeros(b, jnp.int32),
            jnp.zeros(b, jnp.int32), jnp.zeros((b, engine.n_pages), jnp.int32))
    return fn.lower(engine.params, engine.cache, *args).compile().as_text()


def logit_errors(got: list[np.ndarray], want: list[np.ndarray]):
    """Per row: (max |got - want|, that over max |want|, argmax equal)."""
    return [(float(np.max(np.abs(g - w))),
             float(np.max(np.abs(g - w)) / np.max(np.abs(w))),
             int(np.argmax(g)) == int(np.argmax(w)))
            for g, w in zip(got, want)]


def run_phase(name: str, dev, fn):
    before = _compile_s[0]
    out = fn()
    report(name, compile_s=round(_compile_s[0] - before, 3),
           peak_bytes_in_use=(dev.memory_stats() or {}).get(
               "peak_bytes_in_use"))
    return out


def single_chip(dev, seed: int) -> None:
    cfg_twin = model_config(use_kernels=False)
    params = run_phase("weights", dev, lambda: build_params(cfg_twin, seed))
    report("weights", arch=ARCH, strategy=STRATEGY,
           packed_bytes=quantized_bytes(params))

    def twins():
        engine, tokens, logits = serve(cfg_twin, params,
                                       workload(cfg_twin, seed))
        report("phase2", **engine_counts(engine))
        return tokens, logits

    tokens2, logits2 = run_phase("phase2", dev, twins)

    def kernels():
        cfg = model_config(use_kernels=True)
        with warnings.catch_warnings():
            # a kernel that gives way to its twin fails this phase
            warnings.simplefilter("error", KernelFallbackWarning)
            engine, tokens, logits = serve(cfg, params, workload(cfg, seed))
            report("phase3", **engine_counts(engine))
            found = mosaic_kernels(compiled_mixed_step(engine))
        report("phase3", mosaic_kernels=sorted(found))
        check(ATTENTION_KERNEL in found,
              f"no {ATTENTION_KERNEL} kernel in the mixed step: {found}")
        check(any(k.startswith(FFN_KERNEL_PREFIX) for k in found),
              f"no fused FFN kernel in the mixed step: {found}")
        return tokens, logits

    tokens3, logits3 = run_phase("phase3", dev, kernels)
    # the last prompt positions: later rows follow tokens, which may differ
    n = len(tokens2)
    errs = logit_errors(logits3[:n], logits2[:n])
    report("phase3", logit_max_abs=[e[0] for e in errs],
           logit_max_rel=[e[1] for e in errs],
           first_token_agrees=[e[2] for e in errs],
           tokens_agree=[a == b for a, b in zip(tokens3, tokens2)],
           tolerance=KERNEL_LOGIT_TOL)
    check(all(e[1] <= KERNEL_LOGIT_TOL for e in errs),
          f"kernel logits differ from the twins' by more than "
          f"{KERNEL_LOGIT_TOL} of their scale: {[e[1] for e in errs]}")


def pool_bytes_by_device(cache) -> dict[str, int]:
    out: dict[str, int] = {}
    for leaf in jax.tree.leaves(cache):
        for shard in leaf.addressable_shards:
            key = str(shard.device.id)
            out[key] = out.get(key, 0) + shard.data.nbytes
    return out


def mesh(dev, seed: int) -> None:
    n = len(jax.devices())
    check(n == 4, f"--mesh needs 4 chips, JAX reports {n}")
    m = make_mesh((1, n), ("data", "model"))
    # pool rows (null block included) divide by the chips: one home each
    rows = 64
    cfg = model_config(use_kernels=False, n_layers=MESH_LAYERS,
                       dtype=jnp.float32, kv_pool_blocks=rows - 1)
    with jax.default_matmul_precision("highest"):
        params = run_phase("weights", dev, lambda: build_params(cfg, seed))

        def one_device():
            engine, tokens, logits = serve(cfg, params, workload(cfg, seed))
            report("mesh-single", n_homes=engine.n_homes,
                   **engine_counts(engine))
            return tokens, logits

        tokens1, logits1 = run_phase("mesh-single", dev, one_device)

        def sharded():
            replicated = jax.device_put(params, NamedSharding(m, P()))
            with use_mesh(m):
                engine, tokens, logits = serve(cfg, replicated,
                                               workload(cfg, seed))
            per_dev = pool_bytes_by_device(engine.cache)
            total = sum(leaf.nbytes for leaf in jax.tree.leaves(engine.cache))
            report("mesh-sharded", n_homes=engine.n_homes,
                   pool_bytes_total=total, pool_bytes_by_device=per_dev,
                   **engine_counts(engine))
            check(engine.n_homes == n,
                  f"{engine.n_homes} block homes on {n} chips")
            check(len(per_dev) == n and
                  all(b * n == total for b in per_dev.values()),
                  f"the pool is not split in quarters: {per_dev} of {total}")
            return tokens, logits

        tokens4, logits4 = run_phase("mesh-sharded", dev, sharded)
    # as in the parity tests, the token streams are equal, so every sampled
    # row — the prefill tick's and the sharded decode ticks' — is compared
    errs = logit_errors(logits4, logits1)
    report("mesh", tokens_agree=[a == b for a, b in zip(tokens4, tokens1)],
           logit_rows=len(errs), logit_max_abs=max(e[0] for e in errs),
           argmax_agrees=all(e[2] for e in errs), tolerance=MESH_LOGIT_TOL)
    check(tokens4 == tokens1, "sharded token streams differ from one device's")
    check(all(e[0] <= MESH_LOGIT_TOL and e[2] for e in errs),
          f"sharded logits differ from one device's: {errs}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", action="store_true",
                    help="four chips: only the sharded paged engine and the "
                         "single-device engine it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args()

    # the weights are made on the host CPU (core.compiler.init_quantized):
    # keep that platform reachable where the environment names only the chip
    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"phase1 FAILED: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    report("phase1", platform=dev.platform, kind=dev.device_kind,
           count=len(jax.devices()),
           jax_platforms=os.environ.get("JAX_PLATFORMS"))
    report("setup", compile_cache=enable_compile_cache())
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    try:
        (mesh if args.mesh else single_chip)(dev, args.seed)
    except PhaseFailed as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
