"""Compile-only checks for a described TPU v5e (no chip needed).

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot:
block shapes the compiler refuses and kernels that overrun scalar or
vector memory.  Nothing runs; the compiled executables are discarded.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.segsum import segscan_blocked

# (op, dtype, D) of every sorted segment reduction the core issues:
# vertex degrees and run weights (sum f32, D 1|2), the local-move pass-B
# want/best max (f32 D 2), argmin community ids and split labels (min
# int32), wake-up flags (max int32), detector pieces (sum int32), and the
# LPA score max (f32) and hashed tie-break min (uint32)
CORE_SCANS = [
    ("sum", jnp.float32, 1), ("sum", jnp.float32, 2),
    ("sum", jnp.int32, 1), ("max", jnp.float32, 1),
    ("max", jnp.float32, 2), ("max", jnp.int32, 1),
    ("min", jnp.int32, 1), ("min", jnp.uint32, 1),
]
# the largest bucket's edge capacity, and the edge capacity of a Graph500
# scale-20 graph (2^25 >= its ~31M directed COO entries)
SIZES = [16384, 1 << 25]
BLOCK_M = 8192


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("m", SIZES)
@pytest.mark.parametrize("op,dtype,d", CORE_SCANS)
def test_segscan_compiles_for_v5e(one_chip, op, dtype, d, m):
    x = jax.ShapeDtypeStruct((m, d), dtype, sharding=one_chip)
    starts = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip)
    fn = jax.jit(lambda x, s: segscan_blocked(
        x, s, op=op, block_m=BLOCK_M, interpret=False))
    compiled = fn.lower(x, starts).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kron_pass_a_reduction_compiles_for_v5e(one_chip, monkeypatch):
    """Local move's pass A on a Graph500 scale-15 graph: the run sums of
    882,692 entries over as many run ids, the folds placed by end mark."""
    from repro.kernels import ops, segsum

    monkeypatch.setattr(segsum, "_default_interpret",
                        lambda i: False if i is None else i)
    m = 882692
    fn = jax.jit(lambda v, i: ops.segreduce_sorted(v, i, m, impl="pallas"))
    compiled = fn.lower(
        jax.ShapeDtypeStruct((m, 2), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "ends_mark" in text and "searchsorted" not in text


def test_engine_detect_program_compiles_for_v5e(one_chip, monkeypatch):
    """The service engine's vmapped detect program for the sortscan
    bucket, tiled by 8 as on an accelerator, with the compiled kernels."""
    from repro.graph.container import Graph
    from repro.kernels import ops, segsum
    from repro.service.buckets import Bucket
    from repro.service.engine import BatchedLouvainEngine

    # the backend here is the CPU: steer the kernel dispatch to the TPU
    # branch the engine takes on the chip
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(segsum, "_default_interpret",
                        lambda i: False if i is None else i)
    bucket = Bucket(1024, 16384)
    engine = BatchedLouvainEngine(sub_batch=8)
    assert engine.seg_impl == "pallas" and engine.scan_for(bucket) == "sort"
    monkeypatch.setattr(engine, "seg_block_for", lambda b: BLOCK_M)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lead = (1, 8)
    g = Graph(src=sds(lead + (bucket.m_cap,), jnp.int32),
              dst=sds(lead + (bucket.m_cap,), jnp.int32),
              w=sds(lead + (bucket.m_cap,), jnp.float32),
              n_nodes=sds(lead, jnp.int32),
              n_cap=bucket.n_cap, m_cap=bucket.m_cap)
    compiled = engine.compiled_fn(bucket, 1).lower(g).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the whole program fits one chip's 16 GB with room to spare
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 1 << 30
