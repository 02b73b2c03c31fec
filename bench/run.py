#!/usr/bin/env python3
"""Benchmark of GSP-Louvain community detection on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this one process: makes the cell's
inputs from ``--seed``, warms up every shape its traffic uses, measures
for ``--seconds`` and checks every answer.  The last line of standard
output is the result object; the last lines of standard error are the
numbers compared, each with its limit.  With ``--trace 0`` the metrics are
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace of the window.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, or where the system under test (``src/``) is
missing.  JAX's persistent compilation cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jax_cache``; the
Pallas block-size autotuner's cache in ``bench/.autotune/``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"no system under test at {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    from harness.device import NoChip, configure

    configure(BENCH, ROOT)
    from harness import runner

    try:
        result = runner.run(args.workload, args.seed, args.seconds,
                            bool(args.trace), root=ROOT, t_start=T_START)
    except NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
