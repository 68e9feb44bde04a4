"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (one line per measurement).
Run: PYTHONPATH=src python -m benchmarks.run [--only table1,...]
"""

from __future__ import annotations

import argparse
import sys

MODULES = [
    "table1_mixed_precision",
    "fig5_packing",
    "table2_sparse_strategies",
    "table3_hbm_vs_ddr",
    "fig11_scaling",
    "table5_efficiency",
    "kernel_bench",
    "serving_bench",
    "decode_bench",
    "ffn_bench",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module substrings")
    ap.add_argument("--smoke", action="store_true",
                    help="perf smoke -> BENCH_decode.json + BENCH_serving.json"
                         " + BENCH_ffn.json, then exit (the CI trend records)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.smoke:
        from benchmarks.decode_bench import run_smoke
        from benchmarks.ffn_bench import run_smoke as ffn_smoke
        from benchmarks.serving_bench import run_smoke as serving_smoke
        run_smoke()
        serving_smoke()
        ffn_smoke()
        return

    selected = MODULES
    if args.only:
        keys = args.only.split(",")
        selected = [m for m in MODULES if any(k in m for k in keys)]

    print("name,us_per_call,derived")
    failures = 0
    for name in selected:
        try:
            mod = __import__(f"benchmarks.{name}", fromlist=["rows"])
            for row_name, us, derived in mod.rows():
                print(f"{row_name},{us:.2f},{derived}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{name},ERROR,{type(e).__name__}: {e}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
