"""Half of each engine batch left out: the second half of the batch gets
the answers of the first half (``BatchedLouvainEngine.detect_batch``)."""
from harness.faults import patched


def plant():
    from repro.service.engine import BatchedLouvainEngine

    real_batch = BatchedLouvainEngine.detect_batch

    def detect_batch(self, graphs, **kw):
        graphs = list(graphs)
        h = -(-len(graphs) // 2)
        res = real_batch(self, graphs[:h], **kw)
        return [res[i % h] for i in range(len(graphs))]

    return patched(BatchedLouvainEngine, "detect_batch", detect_batch)
