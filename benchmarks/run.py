"""Benchmark orchestrator: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Select with
``python -m benchmarks.run [--only fig3,fig4,...]``.
"""
from __future__ import annotations

import argparse
import sys
import traceback

BENCHES = {
    "table1": "benchmarks.bench_table1_graphs",
    "fig3": "benchmarks.bench_fig3_split_approaches",
    "fig4": "benchmarks.bench_fig4_baselines",
    "fig6": "benchmarks.bench_fig6_scaling",
    "kernels": "benchmarks.bench_kernels",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(BENCHES))
    args = ap.parse_args()
    names = list(BENCHES) if not args.only else args.only.split(",")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    failed = []
    for name in names:
        mod_name = BENCHES[name]
        try:
            mod = __import__(mod_name, fromlist=["main"])
            mod.main()
        except Exception as e:
            failed.append(name)
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
