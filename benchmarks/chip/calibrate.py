"""Read the two readings a cell's check limit is set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 11,12,13 \
        --seconds 10 --control 3

For each seed, one run of the cell as ``bench.py`` makes it (set-up, ramp,
a window at the cell's load, the check), in one process so that set-up
compiles once: the program's widest gap (the lower reading).  The first
``--control`` seeds run in the harness's control mode, in which the float8
control's tokens take the served tokens' place: their ``correct`` is the
control's verdict, which must come out false, and their ``gap`` the
control's widest gap on the same sample (the upper reading).  One JSON line
per seed on standard output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import bench


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args()
    spec = bench.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(bench.CACHE_DIR)
    sys.path.insert(0, str(bench.ROOT / "src"))
    import jax

    bench._count_compiles()
    jax.config.update("jax_compilation_cache_dir", str(bench.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = bench.require_chips(spec["cell"]["chips"])
    peaks = bench.peaks_for(dev.device_kind)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        r = bench.run_cell(spec, seed, args.seconds, False, dev, peaks,
                           control=i < args.control)
        print(json.dumps({"seed": seed, "control": i < args.control,
                          "correct": r["correct"],
                          "gap": r["checks"]["max_logit_gap"]["value"],
                          "program_gap": r.get("program_gap"),
                          "metrics": {k: v["value"]
                                      for k, v in r["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
