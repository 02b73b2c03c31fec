"""GSP-Louvain core: the paper's contribution as composable JAX modules."""
from repro.core.louvain import LouvainConfig, louvain, louvain_impl
from repro.core.local_move import local_move
from repro.core.split import split_labels
from repro.core.aggregate import aggregate
from repro.core.detect import (
    disconnected_communities, disconnected_communities_impl,
)
from repro.core.modularity import modularity
from repro.core.lpa import lpa, lpa_run
from repro.core.portfolio import (
    ALGORITHMS, QualityContract, contract_for, tier_config,
)
from repro.core.dynamic import (
    CapacityError, GraphUpdate, apply_vertex_updates, update_communities,
)
# the unified entry point (NOTE: rebinds the package attribute `detect`
# from the submodule to the function — import the submodule explicitly
# via `from repro.core.detect import ...` as everywhere in-repo)
from repro.core.api import Detection, DetectOptions, detect

__all__ = [
    "ALGORITHMS",
    "CapacityError",
    "Detection",
    "DetectOptions",
    "GraphUpdate",
    "LouvainConfig",
    "QualityContract",
    "contract_for",
    "tier_config",
    "apply_vertex_updates",
    "detect",
    "louvain",
    "louvain_impl",
    "local_move",
    "split_labels",
    "aggregate",
    "disconnected_communities",
    "disconnected_communities_impl",
    "modularity",
    "lpa",
    "lpa_run",
    "update_communities",
]
