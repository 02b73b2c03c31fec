"""Production mesh definitions.

``make_production_mesh`` is a *function* (module import never touches jax
device state): a single TPU v5e pod is modeled as a (16, 16) mesh with axes
(data, model); the multi-pod configuration adds a leading 'pod' axis over
2 pods = 512 chips.  Graph workloads treat the flattened mesh as one edge-
parallel axis; LM workloads use data/model in the usual 2D layout with
'pod' as an outer data axis (DESIGN.md §4).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    # Auto axes: shardings propagate through the shard_map'd passes as the
    # graph drivers expect (jax.make_mesh defaults to Explicit axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a 1-D (data,) mesh (tests/examples)."""
    n = len(jax.devices())
    return _auto_mesh((n,), ("data",))


def flat_axes(mesh) -> tuple:
    """All axis names of a mesh — the edge-parallel axis set for graph work."""
    return tuple(mesh.axis_names)
