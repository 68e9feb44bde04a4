"""Find the knee of an open-loop cell: the highest offered rate whose
backlog does not grow over the window.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 1,2,3 \
        --seconds 30 --seed 5

Set-up runs once; each rate gets a fresh engine (sharing the compiled
executables) and the cell's mix at that rate: ramp, then the window.  Per
rate, one JSON line: the backlog's growth (requests/s, a least-squares
slope of the requests due but not yet admitted, sampled at every tick),
the backlog at the close, and the end-to-end metrics.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

import bench


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--ramp", type=float, default=None,
                    help="seconds of traffic before each window")
    args = ap.parse_args()
    spec = bench.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(bench.CACHE_DIR)
    sys.path.insert(0, str(bench.ROOT / "src"))
    import jax

    from repro.serving.engine import Engine

    bench._count_compiles()
    jax.config.update("jax_compilation_cache_dir", str(bench.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = bench.require_chips(spec["cell"]["chips"])
    peaks = bench.peaks_for(dev.device_kind)
    ctx = bench.set_up(spec, args.seed, dev)
    sizing = spec["sizing"]
    for rate in (float(r) for r in args.rates.split(",")):
        cache = ctx["engine"].cache_compiles
        ctx["engine"] = None
        gc.collect()
        ctx["engine"] = Engine(ctx["cfg"], ctx["params"],
                               batch_size=sizing["rows"],
                               max_len=sizing["max_len"],
                               chunk_size=sizing["chunk"],
                               compile_cache=cache)
        mix = dict(spec["traffic"], rate_per_s=rate)
        if args.ramp is not None:
            mix["ramp_s"] = args.ramp
        drv, ws, we, te, counts, _ = bench.measure(ctx, mix, args.seed,
                                                   args.seconds, False)
        reqs, ticks = drv.finish(), drv.ticks
        drv = None                  # the next engine needs this one's pool
        t = np.array([b for _, b, _, _ in ticks if ws <= b <= we])
        backlog = np.array([sum(r["due"] <= x and (r["admitted"] is None or
                                                   r["admitted"] > x)
                                for r in reqs) for x in t])
        slope = float(np.polyfit(t - ws, backlog, 1)[0]) if len(t) > 2 \
            else None
        run = bench.Run(ws=ws, we=we, te=te, seconds=args.seconds,
                        requests=reqs, ticks=ticks, occupancy=counts["occupancy"],
                        trace=None, setup_s=0.0,
                        model=spec["config"]["model"],
                        served=spec["config"]["served"],
                        family=spec["family"], peaks=peaks)
        metrics = {m: bench.reader(m)(run) for m in
                   ("ttft_p95_s", "itl_p50_ms", "itl_p95_ms", "output_tok_s",
                    "queue_wait_p95_s", "slot_occupancy", "tick_ms.decode",
                    "tick_ms.mixed", "step_roofline_share")}
        print(json.dumps({"rate": rate, "backlog_slope_per_s": slope,
                          "backlog_at_close": int(backlog[-1]) if len(t)
                          else None, "due": len(run.window_requests),
                          **metrics}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
