#!/usr/bin/env python3
"""Readings of the control and of the planted faults, at a cell's own size.

    python3 bench/controls.py --workload <cell> --kinds control,stale \
        --seeds 11,12,13 --seconds 10

For each kind of ``harness.faults.KINDS`` and each seed, runs the cell as
``bench/run.py`` does, with that fault planted under the timed path, and
prints one JSON line: the kind, the seed, ``correct`` and every number
read, compared or not.  All runs share this one process, so each program compiles
once.  The benchmark's own runs never run this; its readings set the
limits in ``bench/configs/*.json``.
"""
import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--kinds", default="control,stale,altered")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    from harness.device import configure

    configure(BENCH, ROOT)
    from harness import faults, runner

    for kind in (k for k in args.kinds.split(",") if k):
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            with faults.planted(kind):
                res, tally = runner.run_with_tally(
                    args.workload, seed, args.seconds, False, root=ROOT,
                    t_start=t0)
            print(json.dumps({"kind": kind, "seed": seed,
                              "correct": res["correct"],
                              "attempted": res["attempted"],
                              "readings": tally.readings(),
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
