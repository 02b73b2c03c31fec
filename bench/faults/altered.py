"""Every detection's answer altered where it is produced: a tenth of the
vertices take another vertex's community, the reported modularity kept."""
import numpy as np

from harness.faults import alter, each_detection


def plant():
    return each_detection(
        lambda g, labels, q: (alter(labels, int(np.asarray(g.n_nodes))), q))
