"""Segment-reduction backend: bitwise parity across impls.

The backend contract (kernels/ops.py) is that 'xla', 'pallas' (interpret)
and 'scatter' fold every segment strictly in index order, making all three
bit-identical — which is what keeps delta-modularity tie-breaks, and hence
whole Louvain partitions, identical across backends and equal to the dense
scan twin.  These tests pin that contract at the op level (hypothesis over
ragged run layouts), at the sweep level, and end to end on tier-1 graphs.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (
    DetectOptions, LouvainConfig, louvain, disconnected_communities,
)
from repro.core import _segments as seg
from repro.core.local_move import _half_sweep, _half_sweep_scatter
from repro.core.modularity import modularity
from repro.kernels import ops, ref
from repro.kernels.segsum import _default_interpret, segscan_blocked
from repro.graph import (
    grid_graph, ring_of_cliques, rmat_graph, sbm_graph,
)

RNG = np.random.default_rng(0)
IMPLS = ("xla", "pallas", "scatter")


def _assert_all_impls_equal(values, ids, nseg, op, block_m=64):
    ref_out = np.asarray(ops.segreduce_sorted(values, ids, nseg, op=op,
                                              impl="xla"))
    for impl in ("pallas", "scatter"):
        got = np.asarray(ops.segreduce_sorted(values, ids, nseg, op=op,
                                              impl=impl, block_m=block_m))
        np.testing.assert_array_equal(
            got, ref_out, err_msg=f"impl={impl} op={op} not bit-identical")


# ---------------------------------------------------------------------------
# op-level parity: hypothesis over ragged run layouts
# ---------------------------------------------------------------------------

@given(st.integers(1, 400), st.integers(1, 60), st.integers(0, 100),
       st.sampled_from(["sum", "max", "min"]),
       st.sampled_from([16, 64, 512]))
@settings(max_examples=25, deadline=None)
def test_segreduce_parity_ragged_runs(m, nseg, seed, op, block_m):
    """Random ragged layouts: many short runs, some long, empty segments
    interleaved — pallas (interpret) == xla == scatter, bit for bit."""
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(np.sort(rng.integers(0, nseg, m)).astype(np.int32))
    v = jnp.asarray(rng.normal(size=m).astype(np.float32))
    _assert_all_impls_equal(v, ids, nseg, op, block_m)


@given(st.integers(2, 200), st.integers(2, 30), st.integers(0, 50))
@settings(max_examples=15, deadline=None)
def test_segreduce_parity_multichannel_and_int(m, nseg, seed):
    """2-channel f32 (the fused sweep's pass-A layout) and int32 payloads."""
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(np.sort(rng.integers(0, nseg, m)).astype(np.int32))
    vf = jnp.asarray(rng.normal(size=(m, 2)).astype(np.float32))
    vi = jnp.asarray(rng.integers(-99, 99, m).astype(np.int32))
    for op in ("sum", "max", "min"):
        _assert_all_impls_equal(vf, ids, nseg, op)
        _assert_all_impls_equal(vi, ids, nseg, op)


def test_segreduce_empty_and_tail_segments():
    """All-empty heads/tails and a single giant run: fills must match the
    jax.ops.segment_* conventions on every impl."""
    ids = jnp.asarray(np.array([3, 3, 3, 3, 7], np.int32))
    v = jnp.asarray(np.array([1.0, 2.0, 3.0, 4.0, 5.0], np.float32))
    for op in ("sum", "max", "min"):
        _assert_all_impls_equal(v, ids, 10, op, block_m=2)
    out = np.asarray(ops.segreduce_sorted(v, ids, 10, op="max",
                                          impl="pallas", block_m=2))
    assert out[0] == -np.inf and out[9] == -np.inf  # empty-segment fill
    assert out[3] == 4.0 and out[7] == 5.0


# the two placements of the folds (kernels/ops.py): the end mark where
# the segments are about as many as the rows, the boundary search where
# they are few; m = 1500 is no whole number of kernel blocks
ENDS_LAYOUTS = {
    # pass A's dense run ids, num_segments = m
    "run_ids": ("mark", 1500, 1500),
    # sparse sorted ids with empty head, middle and tail segments
    "sparse_many": ("mark", 1500, 3000),
    "sparse_few": ("search", 1500, 12),
}
DTYPES = (np.float32, np.int32, np.uint32)


def _ends_layout(kind, rng):
    side, m, nseg = ENDS_LAYOUTS[kind]
    by_mark = nseg * m.bit_length() > ops.ENDS_MARK_RATIO * m
    assert by_mark == (side == "mark"), kind
    if kind == "run_ids":
        starts = rng.random(m) < 0.6
        starts[0] = True
        return np.cumsum(starts).astype(np.int32) - 1, nseg
    # segments 0-1 and the last two stay empty, and so does every third
    # segment between them
    used = np.array([s for s in range(2, nseg - 2) if s % 3])
    return np.sort(rng.choice(used, m)).astype(np.int32), nseg


def _ends_values(dtype, shape, rng):
    if dtype == np.float32:
        return rng.normal(size=shape).astype(dtype)
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max, shape, dtype=dtype,
                        endpoint=True)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda t: t.__name__)
@pytest.mark.parametrize("op", ["sum", "max", "min"])
@pytest.mark.parametrize("kind", list(ENDS_LAYOUTS))
def test_segreduce_parity_on_both_sides_of_the_ends_rule(kind, op, dtype):
    """Both placements, every op, D 1 and 2, float and (wrapping) integer
    payloads: pallas == xla == scatter, bit for bit."""
    rng = np.random.default_rng(sum(map(ord, kind + op)))
    ids, nseg = _ends_layout(kind, rng)
    for shape in ((ids.size,), (ids.size, 2)):
        v = jnp.asarray(_ends_values(dtype, shape, rng))
        _assert_all_impls_equal(v, jnp.asarray(ids), nseg, op, 1024)


@pytest.mark.parametrize("op", ["sum", "max", "min"])
@pytest.mark.parametrize("kind", list(ENDS_LAYOUTS))
def test_segreduce_parity_under_vmap(kind, op):
    """The engine vmaps the detection: a batch of three layouts of one
    shape reduces as each would alone."""
    rng = np.random.default_rng(5)
    ids = []
    for _ in range(3):
        i, nseg = _ends_layout(kind, rng)
        ids.append(i)
    ids = jnp.asarray(np.stack(ids))
    v = jnp.asarray(rng.normal(size=ids.shape + (2,)).astype(np.float32))
    outs = {impl: np.asarray(jax.vmap(
        lambda x, i, impl=impl: ops.segreduce_sorted(
            x, i, nseg, op=op, impl=impl, block_m=1024))(v, ids))
        for impl in IMPLS}
    ref_out = np.asarray(jax.vmap(
        lambda x, i: ref.segreduce_sorted_ref(x, i, nseg, op=op))(v, ids))
    for impl, out in outs.items():
        np.testing.assert_array_equal(out, ref_out, err_msg=impl)


@pytest.mark.parametrize("m,nseg,d,opened,absent", [
    # Graph500 scale 15: local move's pass A over the run ids, and pass B
    # over the vertex ids
    (882692, 882692, 2, "ends_mark", "searchsorted"),
    (882692, 32769, 2, "ends_mark", "searchsorted"),
    # Girvan-Newman graphs in the (256, 8192) and (256, 2048) buckets
    (8192, 256, 1, "ends_search", "ends_mark"),
    (2048, 256, 1, "ends_mark", "searchsorted"),
])
def test_segreduce_takes_the_placement_its_shape_calls_for(
        m, nseg, d, opened, absent):
    fn = jax.jit(lambda v, i: ops.segreduce_sorted(v, i, nseg,
                                                   impl="pallas"))
    text = fn.lower(jax.ShapeDtypeStruct((m, d), jnp.float32),
                    jax.ShapeDtypeStruct((m,), jnp.int32)
                    ).as_text(debug_info=True)
    assert opened in text and absent not in text


def test_segreduce_refine_masked_graph_runs():
    """The masked padded-COO layout refine produces: cross-community
    weights zeroed, ghost padding at the tail — run sums bit-identical."""
    g = sbm_graph(48, 4, p_in=0.6, p_out=0.1, seed=3)[0]
    C, _ = louvain(g, LouvainConfig(max_passes=1))
    w_in = jnp.where(C[g.src] == C[g.dst], g.w, 0.0)  # refine's mask
    cd = C[g.dst]
    s_src, s_cd, perm = seg.sort_runs(g.src, cd)
    starts = seg.run_starts(s_src, s_cd)
    rid = seg.run_ids(starts)
    _assert_all_impls_equal(w_in[perm], rid, g.m_cap, "sum")
    _assert_all_impls_equal(w_in[perm], rid, g.m_cap, "max")


def test_segscan_inorder_fold():
    """The kernel's running value IS the strict left fold per run."""
    rng = np.random.default_rng(7)
    m = 96
    x = rng.normal(size=(m, 1)).astype(np.float32)
    starts = np.zeros(m, np.int32)
    starts[[0, 5, 6, 40, 80]] = 1
    out = np.asarray(segscan_blocked(jnp.asarray(x), jnp.asarray(starts),
                                     op="sum", block_m=32))
    acc = np.float32(0)
    for i in range(m):
        acc = np.float32(x[i, 0]) if starts[i] else np.float32(acc + x[i, 0])
        assert out[i, 0] == acc, i


# ---------------------------------------------------------------------------
# sweep-level parity: fused vs pre-backend scatter half-sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seg_impl", ["xla", "pallas"])
def test_half_sweep_fused_bitwise_equals_scatter(seg_impl):
    g = rmat_graph(scale=8, edge_factor=6, seed=4)
    nv = g.nv
    rng = np.random.default_rng(5)
    C = jnp.asarray(rng.integers(0, nv - 1, nv).astype(np.int32))
    C = C.at[nv - 1].set(nv - 1)
    K = jax.ops.segment_sum(g.w, g.src, num_segments=nv)
    Sigma = jax.ops.segment_sum(K, C, num_segments=nv)
    two_m = jnp.sum(g.w)
    owned = jnp.ones(nv, bool)
    movable = jnp.asarray(rng.random(nv) < 0.5)
    target_ok = jnp.asarray(rng.random(nv) < 0.5)
    legacy = _half_sweep_scatter(g.src, g.dst, g.w, C, K, Sigma, two_m,
                                 owned, movable, None, target_ok=target_ok)
    fused = _half_sweep(g.src, g.dst, g.w, C, K, Sigma, two_m,
                        owned, movable, None, target_ok=target_ok,
                        seg_impl=seg_impl, block_m=128)
    for name, a, b in zip(("C", "Sigma", "moved", "gain", "want"),
                          legacy, fused):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{name} diverged")


# ---------------------------------------------------------------------------
# end-to-end parity on tier-1 graphs + the zero-disconnected invariant
# ---------------------------------------------------------------------------

def _tier1_graphs():
    return {
        "kmer_ring": ring_of_cliques(12, 5),
        "road_grid": grid_graph(10, 10),
        "soc_sbm": sbm_graph(n_nodes=96, n_blocks=5, p_in=0.4, p_out=0.02,
                             seed=2)[0],
        "web_rmat": rmat_graph(scale=8, edge_factor=6, seed=1),
    }


def test_louvain_partition_parity_across_impls():
    cfg = LouvainConfig()
    for name, g in _tier1_graphs().items():
        C_ref = np.asarray(louvain(g, options=DetectOptions(
            louvain=cfg, seg_impl="xla"))[0])
        for impl in ("scatter", "pallas"):
            C = np.asarray(louvain(g, options=DetectOptions(
                louvain=cfg, seg_impl=impl, block_m=256))[0])
            np.testing.assert_array_equal(
                C, C_ref, err_msg=f"{name}: seg_impl={impl} partition "
                "diverged from xla")
        det = disconnected_communities(g.src, g.dst, g.w,
                                       jnp.asarray(C_ref), g.n_nodes)
        assert int(det["n_disconnected"]) == 0, name


def test_modularity_parity_across_impls():
    g = rmat_graph(scale=8, edge_factor=6, seed=9)
    C, _ = louvain(g, LouvainConfig())
    qs = [float(modularity(g.src, g.dst, g.w, C, seg_impl=i,
                           block_m=128))
          for i in IMPLS]
    assert qs[0] == qs[1] == qs[2]


def test_zero_disconnected_invariant_all_impls():
    """The paper's central guarantee survives every backend choice."""
    g = rmat_graph(scale=9, edge_factor=8, seed=11)
    for impl in IMPLS:
        C, _ = louvain(g, options=DetectOptions(
            louvain=LouvainConfig(), seg_impl=impl, block_m=256))
        det = disconnected_communities(g.src, g.dst, g.w, C, g.n_nodes,
                                       seg_impl=impl, block_m=256)
        assert int(det["n_disconnected"]) == 0, impl


# ---------------------------------------------------------------------------
# dispatch policy + autotuner
# ---------------------------------------------------------------------------

def test_auto_resolution_backend_keyed():
    want = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert ops.resolve_impl("auto") == want
    assert ops.resolve_impl("pallas") == "pallas"


def test_interpret_defaults_from_backend():
    """The satellite fix: interpret=None resolves at call time, so Pallas
    never silently runs interpret-mode where a compiled kernel exists."""
    on_tpu = jax.default_backend() == "tpu"
    assert _default_interpret(None) == (not on_tpu)
    assert _default_interpret(True) is True
    assert _default_interpret(False) is False


def test_autotune_block_m_caches_on_disk(tmp_path, monkeypatch):
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    monkeypatch.setattr(autotune, "_mem_cache", {})
    blk = autotune.autotune_block_m(4096, 2, impl="pallas",
                                    candidates=(1024, 2048))
    assert blk in (1024, 2048)
    assert (tmp_path / "autotune.json").exists()
    # second call must hit the cache (no re-measure): same answer
    monkeypatch.setattr(autotune, "_mem_cache", {})
    assert autotune.autotune_block_m(4096, 2, impl="pallas",
                                     candidates=(1024, 2048)) == blk
    # xla shapes are block-free
    assert autotune.autotune_block_m(4096, 2, impl="xla") == 0


def test_autotune_failing_candidate_raises(tmp_path, monkeypatch):
    """A candidate that does not compile is a kernel fault: it surfaces
    instead of being skipped behind a fallback block size."""
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    monkeypatch.setattr(autotune, "_mem_cache", {})
    real = ops.segreduce_sorted

    def refuse_2048(*args, block_m=0, **kw):
        if block_m == 2048:
            raise RuntimeError("Mosaic refused block 2048")
        return real(*args, block_m=block_m, **kw)

    monkeypatch.setattr(ops, "segreduce_sorted", refuse_2048)
    with pytest.raises(RuntimeError, match="refused"):
        autotune.autotune_block_m(4096, 1, impl="pallas",
                                  candidates=(1024, 2048))
    assert not (tmp_path / "autotune.json").exists()


def test_segreduce_parity_across_block_boundaries():
    """Runs that straddle kernel blocks: the carry crosses grid steps in
    order, at the smallest block and at a block wider than the input."""
    rng = np.random.default_rng(3)
    m = 5 * 1024 + 17
    ids = jnp.asarray(np.sort(rng.integers(0, 40, m)).astype(np.int32))
    v = jnp.asarray(rng.normal(size=(m, 2)).astype(np.float32))
    for block_m in (1024, 16384):
        _assert_all_impls_equal(v, ids, 40, "sum", block_m)


def test_engine_compile_key_carries_backend():
    from repro.service.buckets import Bucket
    from repro.service.engine import BatchedLouvainEngine

    eng_a = BatchedLouvainEngine(options=DetectOptions(
        louvain=LouvainConfig(), seg_impl="xla"))
    eng_b = BatchedLouvainEngine(options=DetectOptions(
        louvain=LouvainConfig(), seg_impl="scatter"))
    bucket = Bucket(1024, 16384)  # sortscan bucket under the default ladder
    assert eng_a.scan_for(bucket) == "sort"
    ka = eng_a._detect_key(bucket, 1)
    kb = eng_b._detect_key(bucket, 1)
    assert ka != kb and "xla" in ka and "scatter" in kb
