"""Size a cell's paged KV pool to fill one chip's memory, on the chip.

    python3 benchmarks/chip/size_pool.py --workload <cell> [--margin-gb 0.5]

Compiles the engine's own paged mixed step (at the widest chunk) and decode
step for this chip at trial pools, and reads the compiler's memory
analysis: arguments, outputs not aliased to them, and temporaries, the sum
the chip refuses a program for.  (The allocator's ``peak_bytes_in_use``
does not see a program's temporaries, and the steps hold a temporary copy
of the whole pool beside the donated one, so a block costs twice its
bytes.)  A secant search finds the most blocks whose larger step leaves
``--margin-gb`` GiB of the chip's limit free, at most one full context per
row.  One JSON line on standard output; the result goes into
``cells/<cell>.json`` as ``pool_blocks``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import bench


def step_bytes(cfg, config, family, sizing, blocks: int, dev) -> dict:
    """The compiler's device bytes of each step at ``blocks``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.core.compiler import quantize_model
    from repro.models import api
    from repro.models.attention import paged_geometry
    from repro.serving import engine as eng

    import weights

    one = SingleDeviceSharding(dev)
    rows, max_len, chunk = sizing["rows"], sizing["max_len"], sizing["chunk"]
    cfg = dataclasses.replace(cfg, kv_pool_blocks=blocks)
    served = config["served"]
    shapes = jax.eval_shape(lambda k: api.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.eval_shape(lambda k: weights.build_served(
        k, shapes, family, config["model"], served,
        lambda t: quantize_model(t, served["strategy"])),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: api.init_cache(cfg, rows, max_len))

    def on(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)

    params, cache = jax.tree.map(on, params), jax.tree.map(on, cache)
    _, pages = paged_geometry(cfg, max_len)

    def arg(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    out = {}
    for name, fn, args in (
            ("mixed", eng._mixed_executable_paged(cfg),
             (arg((rows, chunk)), arg((rows,)), arg((rows,)),
              arg((rows, pages)))),
            ("decode", eng._decode_executable_paged(cfg),
             (arg((rows, 1)), arg((rows,)), arg((rows, pages)),
              arg((rows,), jnp.bool_)))):
        m = fn.lower(params, cache, *args).compile().memory_analysis()
        out[name] = {"arguments": m.argument_size_in_bytes,
                     "outputs_unaliased": (m.output_size_in_bytes -
                                           m.alias_size_in_bytes),
                     "temporaries": m.temp_size_in_bytes}
        out[name]["total"] = sum(out[name].values())
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--margin-gb", type=float, default=0.5,
                    help="GiB of the chip's limit left free")
    args = ap.parse_args()
    spec = bench.load_cell(args.workload)
    sys.path.insert(0, str(bench.ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(bench.CACHE_DIR)
    import jax

    from repro.models.attention import paged_geometry

    jax.config.update("jax_compilation_cache_dir", str(bench.CACHE_DIR))
    dev = bench.require_chips(spec["cell"]["chips"])
    config, sizing, family = spec["config"], spec["sizing"], spec["family"]
    cfg = bench.program_config(config, family)
    _, pages = paged_geometry(cfg, sizing["max_len"])
    full = sizing["rows"] * pages
    limit = dev.memory_stats()["bytes_limit"]
    budget = limit - args.margin_gb * 2 ** 30
    seen = {}

    def worst(p):
        if p not in seen:
            seen[p] = step_bytes(cfg, config, family, sizing, p, dev)
        return max(s["total"] for s in seen[p].values())

    p0, p1 = sizing["rows"], 2 * sizing["rows"]
    for _ in range(6):
        b0, b1 = worst(p0), worst(p1)
        p = min(full, int(p1 + (budget - b1) * (p1 - p0) / (b1 - b0)))
        if p in seen or (p == full and worst(p) <= budget):
            break
        p0, p1 = p1, p
    fits = max(k for k in seen if worst(k) <= budget)
    print(json.dumps({"workload": args.workload,
                      "device_kind": dev.device_kind, "bytes_limit": limit,
                      "budget": budget, "full_blocks": full,
                      "pool_blocks": fits, "at": seen}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
