"""Time the two placements of ``ops.segreduce_sorted`` at given shapes.

    PYTHONPATH=src python scripts/ends_crossover.py [--reps 20]
        [--shape M,NUM_SEGMENTS,D,BATCH ...]

After the segmented scan, ``segreduce_sorted`` places each segment's fold
either by marking the rows that end a run and scattering them
(``ops._ends_mark``: m values, or m row indices and a gather of rows) or
by binary search over the sorted ids (``ops._ends_search``:
``ceil(log2(m + 1))`` dependent gathers of ``num_segments`` rows).  For
each shape this prints one JSON line: the
median device time of each placement alone (float32 values, ``BATCH`` > 1
runs it under ``jax.vmap`` as the service engine does), ``x`` =
``num_segments * ceil(log2(m + 1)) / m``, and ``ratio``, the cost of one
row placed by the mark over one row gathered by a search step.  The last
line gives ``ends_mark_ratio``, the value of ``ops.ENDS_MARK_RATIO``
that loses the least time over the measured shapes, each counted as the
share by which its chosen placement is slower than the faster one (a
geometric midpoint between two measured ``x``).  The default shapes
are those of the benchmark's cells: Graph500 scale 15 (882,692 directed
entries; run ids over every entry, and vertex ids over 32,769 slots) and
the Girvan-Newman graphs in the service's (256, 2048) and (256, 8192)
buckets, eight graphs to a vmapped tile; and a few with fewer segments,
where the search should win.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.segsum import scan_identity

SHAPES = ("882692,882692,2,1", "882692,882692,1,1", "882692,32769,1,1",
          "882692,32769,2,1", "8192,256,2,8", "8192,256,1,8",
          "2048,256,2,8", "2048,256,1,8", "882692,8192,1,1",
          "882692,1024,1,1", "8192,32,1,8")


def sorted_ids(rng, m, num_segments):
    """Nondecreasing ids: run ids of ragged runs where the segments are as
    many as the rows, else vertex ids drawn over every segment."""
    if num_segments >= m:
        starts = rng.random(m) < 0.6
        starts[0] = True
        return np.cumsum(starts).astype(np.int32) - 1
    return np.sort(rng.integers(0, num_segments, m)).astype(np.int32)


def median_seconds(fn, args, reps):
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def measure(m, num_segments, d, batch, reps, seed=0):
    rng = np.random.default_rng(seed)
    ids = np.stack([sorted_ids(rng, m, num_segments) for _ in range(batch)])
    scanned = rng.random((batch, m, d), np.float32)
    args = (jnp.asarray(ids), jnp.asarray(scanned))
    ident = scan_identity("sum", jnp.float32)
    row = {"m": m, "num_segments": num_segments, "d": d, "batch": batch}
    for name, place in (("mark", ops._ends_mark),
                        ("search", ops._ends_search)):
        fn = jax.jit(jax.vmap(
            lambda i, s, place=place: place(i, s, num_segments, ident)))
        row[f"{name}_ms"] = median_seconds(fn, args, reps) * 1e3
    row["x"] = num_segments * m.bit_length() / m
    row["ratio"] = row["x"] * row["mark_ms"] / row["search_ms"]
    return row


def crossover(rows):
    """The ``ops.ENDS_MARK_RATIO`` (the mark where ``x`` exceeds it) whose
    choices are slower than the faster placement by the least summed
    share, among geometric midpoints of the measured ``x``."""
    xs = sorted({r["x"] for r in rows})
    cands = [xs[0] / 2] + [(a * b) ** 0.5 for a, b in zip(xs, xs[1:])] \
        + [xs[-1] * 2]

    def loss(c):
        return sum((r["mark_ms"] if r["x"] > c else r["search_ms"])
                   / min(r["mark_ms"], r["search_ms"]) - 1 for r in rows)
    return min(cands, key=loss)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--shape", action="append",
                    help="M,NUM_SEGMENTS,D,BATCH (repeatable)")
    args = ap.parse_args(argv)
    device = jax.devices()[0]
    rows = []
    for shape in args.shape or SHAPES:
        m, nseg, d, batch = (int(x) for x in shape.split(","))
        rows.append(measure(m, nseg, d, batch, args.reps))
        rows[-1]["device"] = device.device_kind
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"ends_mark_ratio": crossover(rows),
                      "device": device.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
