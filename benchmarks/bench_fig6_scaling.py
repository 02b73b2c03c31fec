"""Paper Figure 6: strong scaling.

Two views (this container has ONE physical core, so wall-clock multi-device
runs measure functional overhead, not speedup — stated in the derived
column):

1. functional: the distributed community step executes on 1..8 host devices
   in subprocesses (proves the sharded path runs at every width);
2. model: roofline step-time bound for the paper's own workload from the
   dry-run records at 256 vs 512 chips (the honest scaling signal without
   hardware — see EXPERIMENTS.md §Roofline).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import row

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CODE = """
import time, jax, jax.numpy as jnp
from repro.graph import rmat_graph
from repro.graph.partition import partition_edges_by_src
from repro.core.distributed import build_community_step
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh()
S = mesh.size
g = rmat_graph(scale=12, edge_factor=8, seed=1)
parts = partition_edges_by_src(g, S)
plan = build_community_step(mesh, n_cap=g.n_cap, m_shard=parts["src"].shape[1])
fn = jax.jit(plan["fn"], in_shardings=plan["in_shardings"],
             out_shardings=plan["out_shardings"])
args = (jnp.asarray(parts["src"]), jnp.asarray(parts["dst"]),
        jnp.asarray(parts["w"]), jnp.asarray(parts["v_lo"]),
        jnp.asarray(parts["v_hi"]), jnp.float32(g.total_weight_2m()),
        g.n_nodes.astype(jnp.int32))
jax.block_until_ready(fn(*args))
t0 = time.perf_counter()
for _ in range(3):
    jax.block_until_ready(fn(*args))
print((time.perf_counter() - t0) / 3)
"""


def main():
    for n_dev in [1, 2, 4, 8]:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
        # host devices only: an accelerator belongs to the parent process
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = os.path.join(ROOT, "src")
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(_CODE)],
                             capture_output=True, text=True, env=env,
                             timeout=1200)
        if out.returncode != 0:
            row(f"fig6/functional/devices_{n_dev}", 0.0,
                f"ERROR:{out.stderr.strip()[-120:]}")
            continue
        t = float(out.stdout.strip().splitlines()[-1])
        row(f"fig6/functional/devices_{n_dev}", t,
            "one-core-host;functional-only")

    # roofline-model scaling from dry-run records (if present)
    dr = os.path.join(ROOT, "experiments", "dryrun")
    for shape in ["soc_orkut", "web_uk2002"]:
        recs = {}
        for mesh_name, chips in [("pod", 256), ("multipod", 512)]:
            p = os.path.join(dr, f"louvain__{shape}__{mesh_name}.json")
            if os.path.exists(p):
                r = json.load(open(p))
                if r.get("status") == "ok":
                    recs[chips] = r["step_time_bound"]
        if len(recs) == 2:
            speedup = recs[256] / recs[512]
            row(f"fig6/roofline/louvain_{shape}", recs[512],
                f"bound256={recs[256]:.2e};bound512={recs[512]:.2e};"
                f"scale_x{speedup:.2f}_per_2x_chips")


if __name__ == "__main__":
    main()
