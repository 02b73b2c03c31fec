"""Local-moving phase of GSP-Louvain (paper Algorithm 4), TPU formulation.

The OpenMP original scans each vertex's neighborhood into a per-thread
hashtable keyed by neighbor community.  Here the whole edge set is sorted by
``(src, C[dst])`` once per sweep; equal keys form runs and a segment-sum
yields every ``K_{i->c}`` simultaneously (one "hashtable" for the entire
graph).  Delta-modularity (paper Eq. 2) is evaluated per run, and a
segment-argmax per source vertex picks the best destination community.

Synchronization policy (the one real semantic divergence from the OpenMP
original, which updates asynchronously — DESIGN.md §2):

* ``sync='handshake'`` (default): each iteration runs two half-sweeps; in
  half-sweep p, vertices of id-parity p may move, and only **into
  communities of parity 1-p**.  Both endpoints of any would-be label cycle
  are therefore separated: targets are frozen (no chain collapse — a
  community cannot lose its identity while receiving members) and
  symmetric swaps are impossible inside a half-sweep.  Parities re-roll
  every pass via dense renumbering, so no merge is blocked permanently.
* ``sync='parity'``: movers alternate by parity, targets unrestricted
  (ablation: admits same-parity pairwise swaps).
* ``sync='all'``: plain synchronous Jacobi (ablation: oscillates).

Convergence uses the **realized** modularity delta per iteration, not the
sum of per-move estimates: simultaneous moves make estimates additive-only,
and oscillating swap pairs report forever-positive estimated gains.
Realized Q is two cheap reductions (internal edge weight, sum of Sigma^2).

Vertex pruning (paper line 6 / line 14 of Alg. 4) is kept as an activity
mask: inactive vertices propose no move; any vertex adjacent to a moved
vertex is reactivated.  On TPU masking costs nothing extra per lane but
faithfully reproduces the pruned algorithm's work-skipping.

Distribution: edges arrive vertex-aligned (all out-edges of a vertex on one
shard — graph/partition.py), so every per-vertex reduction here is exact
shard-locally.  Per-vertex state (C, Sigma, active) is replicated and merged
with one ``psum``/``pmax`` per half-sweep (collectives.py wrappers; identity
when ``axis=None``).

Scan strategies (``scan=``): the sweep above is expressed twice.

* ``'sort'`` (default) — the sort + run-reduction formulation described
  above: O(m log m) per sweep, capacity-oblivious, the right layout for
  the paper's 100M+-vertex graphs.
* ``'dense'`` — the small-graph service specialization: ``K_{i->c}`` is
  scattered straight into a dense ``[nv, nv]`` vertex-x-community matrix
  and the argmax runs as a row reduction.  For the bucketed request
  shapes of :mod:`repro.service` (``nv`` of a few hundred, ``nv^2``
  comparable to ``m_cap``) this removes the per-sweep sort entirely,
  which dominates wall time on small graphs and vmaps/batches without
  sort's poor accelerator utilization.  The two strategies are **bit
  equivalent**: scatter-add applies duplicate-index updates in edge
  order, which is exactly the order the stable ``(src, C[dst])`` sort
  feeds the run reduction, so every W_{i->c} (and hence every dq,
  argmax decision, and realized-Q trajectory) matches the sort path
  float for float (asserted in tests/test_service.py).  Single-device
  only (``axis`` must be None).

Sortscan backend (``seg_impl=``): the sort path's reductions route
through the segment-reduction backend (:mod:`repro.kernels.ops` — the
single dispatch point; 'auto' picks the XLA sorted path on CPU and the
Pallas kernels on TPU).  The default fused sweep does **one sort carrying
a single permutation payload and two fused reduction passes** — pass A:
one 2-channel in-order run reduction (true + anchored K_{i->c} together);
pass B: the per-vertex Eq.-2 argmax as multi-channel sorted segment
max/min keyed directly by the sorted source ids — replacing the
pre-backend formulation's four-plus scatter rounds (two run_field
scatters, two separate run reductions, and unsorted per-vertex
reductions).  ``seg_impl='scatter'`` keeps that pre-backend sweep
callable as the paired-benchmark baseline (bench_kernels/check_bench).
All seg_impls are bit-identical — the backend's in-order fold contract —
so partitions match across 'xla'/'pallas'/'scatter' AND the dense twin.

The fused sweep (and the sorted wake-up reduction under pruning) assumes
the container's sorted-edge invariant (``src`` nondecreasing —
graph/container.py; aggregation preserves it).  ``seg_impl='scatter'``
lifts the assumption for callers with raw unsorted COO.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import _segments as seg
from repro.distributed import collectives as col
from repro.kernels import ops
from repro.telemetry.spans import scope

NEG = jnp.float32(-jnp.inf)


class MoveState(NamedTuple):
    C: jax.Array          # int32[nv]  community of each vertex (replicated)
    Sigma: jax.Array      # f32[nv]    total edge weight per community
    active: jax.Array     # bool[nv]   pruning mask
    q_prev: jax.Array     # f32[]      realized modularity after last sweep
    dQ_iter: jax.Array    # f32[]      realized gain in the last full sweep
    dQ_prev: jax.Array    # f32[]      realized gain one sweep earlier
    it: jax.Array         # int32[]    completed iterations
    n_prod: jax.Array     # int32[]    iterations with realized gain > tau
    C_best: jax.Array     # int32[nv]  best-realized-Q membership so far
    Sigma_best: jax.Array
    q_best: jax.Array     # f32[]


def _hash_parity(ids, it):
    """Iteration-salted pseudo-random parity bit per id.

    A fixed id-parity handshake deadlocks: two communities whose ids share a
    parity can never merge directly.  Salting with the iteration index
    re-rolls the bipartition every sweep, so every pair is mover/target-
    compatible within ~2 sweeps in expectation, while each individual sweep
    keeps the frozen-target guarantee.
    """
    h = ids.astype(jnp.uint32) * jnp.uint32(0x9E3779B1) + (
        it.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
    )
    h = (h ^ (h >> 16)) * jnp.uint32(0x45D9F3B)
    return ((h >> 13) & 1).astype(jnp.int32)


def realized_modularity(src, dst, w, C, Sigma, two_m, owned, axis,
                        gidx=None, m_total=None):
    """Q of the current partition (directed-COO convention).

    Single-device (``axis=None``): one flat reduce over the masked edge
    weights — this runs once per local-move sweep on the service hot path,
    so it must stay a plain [m] reduction (a per-vertex scatter here costs
    ~40% end-to-end on the batched dense engine).

    Sharded with ``gidx`` (the production driver, core/distributed.py):
    each shard scatters its masked weights to their **global edge slots**
    (``gidx``, from the order-preserving vertex-aligned partition; padding
    routes to the dump slot ``m_total``) and the ``psum`` merge only adds
    disjoint-support zeros (``x + 0.0 == x`` for the non-negative values
    here) — the replicated ``[m_total]`` vector is bitwise the
    single-device masked-weight vector, and the same flat reduce over it
    matches the single-device scalar ulp-for-ulp.  A psum of per-shard
    *scalar* partials would merge in a different order than the
    single-device fold and break the exact parity contract.

    Sharded without ``gidx`` (the approximate multi-device harness): fall
    back to per-vertex grouping — K_in is exact shard-locally under the
    vertex-aligned partition, so the psum is still exact, but the final
    [nv] reduce is NOT the single-device fold order.
    """
    w_in = jnp.where(C[src] == C[dst], w, 0.0)
    if axis is None:
        internal = jnp.sum(w_in)
    elif gidx is not None:
        full = col.psum(
            jax.ops.segment_sum(w_in, gidx, num_segments=m_total + 1), axis)
        internal = jnp.sum(full[:m_total])
    else:
        nv = C.shape[0]
        K_in = col.psum(
            jax.ops.segment_sum(w_in, src, num_segments=nv), axis)
        internal = jnp.sum(K_in)
    # Sigma is replicated; sum of squares is collective-free
    sig2 = jnp.sum(Sigma * Sigma)
    return internal / two_m - sig2 / (two_m * two_m)


def _half_sweep(src, dst, w, C, K, Sigma, two_m, owned, movable, axis,
                target_ok=None, anchored=True, seg_impl="xla", block_m=0):
    """One synchronous half-sweep (fused sortscan). Returns
    (C_new, Sigma_new, moved, gain, want).

    ``target_ok``: bool[nv] — if given, moves are only allowed into
    communities flagged True (the handshake schedule).
    ``anchored``: join-attraction counts only frozen neighbors (see below);
    disabled for the 'all' ablation where nothing is frozen.

    Fused formulation (bit-identical to :func:`_half_sweep_scatter`, the
    pre-backend twin): one permutation sort, pass A = a single 2-channel
    in-order run reduction producing true and anchored K_{i->c} together,
    Eq.-2 scoring per run representative in **element space**, pass B =
    multi-channel sorted segment max/min keyed by the sorted source ids
    (``s_src`` is nondecreasing by construction, so no second key layout
    is ever materialized).  The two run_field scatter rounds disappear
    entirely: run sums come back per element via the ``Wc[rid]`` gather,
    and the run's (vertex, community) identity is just ``(s_src, s_cd)``
    read at run-start rows.
    """
    nv = C.shape[0]
    m_cap = src.shape[0]
    ghost = nv - 1

    # --- scanCommunities: sort by (src, C[dst]); gather payloads ---------
    with scope("sort"):
        cd = C[dst]
        s_src, s_cd, perm = seg.sort_runs(src, cd)
        s_dst = dst[perm]
        s_w = w[perm]
    with scope("gain"):
        # exclude self-loops from scan (paper Alg. 4)
        not_self = s_src != s_dst
        w_all = jnp.where(not_self, s_w, 0.0)
        # Anchored joins: attraction toward a *target* community only counts
        # neighbors frozen this half-sweep.  A synchronous join is thereby
        # always anchored to a member that provably stays, which suppresses the
        # join-while-anchor-leaves races that mass-produce internally
        # disconnected communities under Jacobi dynamics (DESIGN.md §2).
        w_frozen = (jnp.where(not_self & ~movable[s_dst], s_w, 0.0)
                    if anchored else w_all)
        starts = seg.run_starts(s_src, s_cd)
        rid = seg.run_ids(starts)
        # pass A: both weight channels in ONE in-order run reduction
        Wc = seg.runs_reduce(jnp.stack([w_all, w_frozen], axis=1), rid, m_cap,
                             impl=seg_impl, block_m=block_m)
        W_all_e = Wc[rid, 0]           # true K_{i->c}, per element of the run
        W_frz_e = Wc[rid, 1]           # anchored K_{i->c}

        # --- K_{i->d}: true weight to own community (excluding self) ---------
        # each vertex has at most ONE own run, so this is a select: one
        # scatter-set at own-run starts (exact — no duplicate indices)
        own_start = starts & (s_cd == C[s_src])
        K_own = jnp.zeros(nv, jnp.float32).at[
            jnp.where(own_start, s_src, ghost)].set(
            jnp.where(own_start, W_all_e, 0.0), mode="drop")
        K_own = K_own.at[ghost].set(0.0)

        # --- delta-modularity per run representative (paper Eq. 2) -----------
        # Score with the true attraction W_all; *gate* on having at least
        # one frozen anchor in the target (W_frz frozen-filtered > 0), so the
        # join stays connected even if every movable member departs
        # simultaneously.
        Ki = K[s_src]
        d_of_i = C[s_src]
        dq = (
            2.0 * (W_all_e - K_own[s_src]) / two_m
            - 2.0 * Ki * (Ki + Sigma[s_cd] - Sigma[d_of_i]) / (two_m * two_m)
        )
        valid = starts & (s_src < ghost) & (s_cd < ghost) & (s_cd != d_of_i)
        cand = valid & (W_frz_e > 0.0) & movable[s_src] & owned[s_src]
        if target_ok is not None:
            cand = cand & target_ok[s_cd]
        # 'want': the vertex has a positive move ignoring schedule gates —
        # used to keep schedule-blocked vertices awake under pruning (a
        # pruned vertex whose merge was blocked by an unlucky parity roll
        # must retry, or the move is lost forever once its neighborhood goes
        # quiet).  Zero-weight runs are excluded: cand requires W_frz > 0 <=
        # W_all, so a zero-weight target can never become admissible and
        # shouldn't hold a vertex awake — this also keeps the dense scan
        # (whose cells exist iff W_all > 0) bit-equivalent even when
        # zero-weight edges appear (refine's masked graphs, weight-delta
        # updates).
        base = valid & (W_all_e > 0.0)
        # pass B: want and best fused into one 2-channel sorted segment max
        dq2 = jnp.stack([jnp.where(base, dq, NEG), jnp.where(cand, dq, NEG)],
                        axis=1)
        mx = ops.segreduce_sorted(dq2, s_src, nv, op="max", impl=seg_impl,
                                  block_m=block_m)
        want = mx[:, 0] > 0.0
        best = mx[:, 1]

        # --- argmax per source vertex (min community id breaks ties) ---------
        dq_c = jnp.where(cand, dq, NEG)
        is_best = cand & (dq_c >= best[s_src] - 0.0)
        c_star = ops.segreduce_sorted(
            jnp.where(is_best, s_cd, seg.INT_MAX), s_src, nv, op="min",
            impl=seg_impl, block_m=block_m)
    with scope("move"):
        move = (best > 0.0) & (c_star < ghost)
        C_local = jnp.where(move, c_star.astype(jnp.int32), C)

        # --- merge shard-local decisions (each vertex owned by one shard) ----
        C_new = col.psum(jnp.where(owned, C_local, 0), axis)
        C_new = C_new.at[ghost].set(ghost)
        moved = col.psum(
            jnp.where(owned & move, 1, 0).astype(jnp.int32), axis) > 0

        # --- exact Sigma recompute (synchronous) --------------------------
        # unsorted keys (C_new): stays an in-order XLA scatter on every backend
        # — nv-sized, off the critical path, and in-order is what keeps Sigma
        # bit-identical across seg_impls and the dense twin.  K and C_new are
        # replicated here, so every shard recomputes the full Sigma identically
        # and collective-free; a psum of owned-masked partials would fold
        # cross-shard in a different order than the single-device scatter and
        # break the ulp-exact sharded parity contract.
        Sigma_new = jax.ops.segment_sum(K, C_new, num_segments=nv)
        gain = col.psum(jnp.sum(jnp.where(owned & move, best, 0.0)), axis)
        want = col.pmax((want & owned).astype(jnp.int32), axis) > 0
    return C_new, Sigma_new, moved, gain, want


def _half_sweep_scatter(src, dst, w, C, K, Sigma, two_m, owned, movable, axis,
                        target_ok=None, anchored=True):
    """The pre-backend scatter sweep (``seg_impl='scatter'``).

    Kept verbatim as (a) the paired baseline the bench gate measures the
    fused sweep against and (b) the fallback for raw unsorted COO inputs.
    Bit-identical outputs to :func:`_half_sweep`.
    """
    nv = C.shape[0]
    m_cap = src.shape[0]
    ghost = nv - 1

    # --- scanCommunities: sort by (src, C[dst]) and reduce runs ----------
    with scope("sort"):
        cd = C[dst]
        not_self = src != dst  # exclude self-loops from scan (paper Alg. 4)
        w_all = jnp.where(not_self, w, 0.0)
        w_frozen = (jnp.where(not_self & ~movable[dst], w, 0.0)
                    if anchored else w_all)
        s_src, s_cd, s_wf, s_wa = seg.sort_by_key2(src, cd, w_frozen, w_all)
    with scope("gain"):
        starts = seg.run_starts(s_src, s_cd)
        rid = seg.run_ids(starts)
        W_ic = seg.runs_reduce(s_wf, rid, m_cap, impl="scatter")
        W_ic_all = seg.runs_reduce(s_wa, rid, m_cap, impl="scatter")
        i_run, run_valid = seg.run_field(s_src, starts, rid, m_cap, ghost,
                                         impl="scatter")
        c_run, _ = seg.run_field(s_cd, starts, rid, m_cap, ghost,
                                 impl="scatter")

        # --- K_{i->d}: true weight to own community (excluding self) ---------
        own = (c_run == C[i_run]) & run_valid
        K_own = jax.ops.segment_sum(
            jnp.where(own, W_ic_all, 0.0), i_run, num_segments=nv
        )

        # --- delta-modularity per candidate run (paper Eq. 2) ----------------
        Ki = K[i_run]
        d_of_i = C[i_run]
        dq = (
            2.0 * (W_ic_all - K_own[i_run]) / two_m
            - 2.0 * Ki * (Ki + Sigma[c_run] - Sigma[d_of_i]) / (two_m * two_m)
        )
        cand = (
            run_valid
            & (i_run < ghost)
            & (c_run < ghost)
            & (c_run != d_of_i)
            & (W_ic > 0.0)
            & movable[i_run]
            & owned[i_run]
        )
        if target_ok is not None:
            cand = cand & target_ok[c_run]
        base = (run_valid & (i_run < ghost) & (c_run < ghost)
                & (c_run != d_of_i) & (W_ic_all > 0.0))
        dq_all = jnp.where(base, dq, NEG)
        want = jax.ops.segment_max(dq_all, i_run, num_segments=nv) > 0.0
        dq = jnp.where(cand, dq, NEG)

        # --- argmax per source vertex (min community id breaks ties) ---------
        best = jax.ops.segment_max(dq, i_run, num_segments=nv)
        is_best = cand & (dq >= best[i_run] - 0.0)
        c_star = jax.ops.segment_min(
            jnp.where(is_best, c_run, seg.INT_MAX), i_run, num_segments=nv
        )
    with scope("move"):
        move = (best > 0.0) & (c_star < ghost)
        C_local = jnp.where(move, c_star.astype(jnp.int32), C)

        # --- merge shard-local decisions (each vertex owned by one shard) ----
        C_new = col.psum(jnp.where(owned, C_local, 0), axis)
        C_new = C_new.at[ghost].set(ghost)
        moved = col.psum(
            jnp.where(owned & move, 1, 0).astype(jnp.int32), axis) > 0

        # --- exact Sigma recompute (synchronous) --------------------------
        # replicated (K, C_new) -> collective-free, bit-identical to the
        # single-device scatter (see _half_sweep)
        Sigma_new = jax.ops.segment_sum(K, C_new, num_segments=nv)
        gain = col.psum(jnp.sum(jnp.where(owned & move, best, 0.0)), axis)
        want = col.pmax((want & owned).astype(jnp.int32), axis) > 0
    return C_new, Sigma_new, moved, gain, want


def _half_sweep_dense(src, dst, w, C, K, Sigma, two_m, owned, movable, axis,
                      target_ok=None, anchored=True, valid_cell=None):
    """Dense twin of :func:`_half_sweep` for small ``nv`` (see module doc).

    Same contract and bit-identical results (for positive edge weights —
    the framework invariant); the sortscan is replaced by a complex-packed
    scatter-add into a ``[nv, nv]`` community matrix (real part: true
    K_{i->c}; imaginary part: anchored/frozen K_{i->c}).

    ``owned=None`` means "no ownership partition" (single-device service
    path) and skips the masking entirely — value-identical to an all-True
    owned.  ``valid_cell`` optionally carries the loop-invariant
    (i < ghost) & (c < ghost) mask so callers hoist it out of the sweep.
    """
    nv = C.shape[0]
    ghost = nv - 1
    ids = jnp.arange(nv, dtype=jnp.int32)
    c_ids = ids[None, :]
    if valid_cell is None:
        valid_cell = (ids[:, None] < ghost) & (c_ids < ghost)

    with scope("gain"):
        cd = C[dst]
        not_self = src != dst  # exclude self-loops from scan (paper Alg. 4)
        w_all = jnp.where(not_self, w, 0.0)
        w_frozen = (jnp.where(not_self & ~movable[dst], w, 0.0)
                    if anchored else w_all)
        # One scatter pays the per-index cost once for both scans.  Complex
        # add is componentwise IEEE f32 add, and duplicate-index updates
        # apply in edge order — the same order the stable sort feeds
        # segment_sum — so both components are bit-identical to the sort
        # path's run sums.
        packed = jax.lax.complex(w_all, w_frozen)
        Wc = jnp.zeros((nv, nv), jnp.complex64).at[src, cd].add(packed)
        W_all = jnp.real(Wc)       # true K_{i->c} per (vertex, community)
        W_frz = jnp.imag(Wc)       # anchored K_{i->c}

        # --- K_{i->d}: true weight to own community (excluding self) ---------
        K_own = W_all[ids, C]

        # --- delta-modularity per candidate cell (paper Eq. 2) ---------------
        Ki = K[:, None]
        dq = (
            2.0 * (W_all - K_own[:, None]) / two_m
            - 2.0 * Ki * (Ki + Sigma[None, :] - Sigma[C][:, None])
            / (two_m * two_m)
        )
        # A cell (i, c != C[i]) corresponds to a sortscan run iff some non-self
        # edge i->j lands in c; all real edge weights are positive, so run
        # existence is exactly W_all > 0 (and the anchored gate W_frz > 0
        # subsumes it for cand).
        geom = valid_cell & (c_ids != C[:, None])
        cand = geom & (W_frz > 0.0) & movable[:, None]
        if owned is not None:
            cand = cand & owned[:, None]
        if target_ok is not None:
            cand = cand & target_ok[None, :]
        want = jnp.max(jnp.where(geom & (W_all > 0.0), dq, NEG), axis=1) > 0.0

        # --- argmax per source vertex (min community id breaks ties) ---------
        dq_cand = jnp.where(cand, dq, NEG)
        best = jnp.max(dq_cand, axis=1)
        c_star = jnp.min(
            jnp.where(cand & (dq_cand >= best[:, None] - 0.0), c_ids,
                      seg.INT_MAX),
            axis=1,
        )
    with scope("move"):
        move = (best > 0.0) & (c_star < ghost)
        C_local = jnp.where(move, c_star.astype(jnp.int32), C)

        # --- merge + exact Sigma recompute: identical to the sort path -------
        if owned is None:
            C_new = C_local.at[ghost].set(ghost)
            moved = move
            Sigma_new = jax.ops.segment_sum(K, C_new, num_segments=nv)
            gain = jnp.sum(jnp.where(move, best, 0.0))
        else:
            C_new = col.psum(jnp.where(owned, C_local, 0), axis)
            C_new = C_new.at[ghost].set(ghost)
            moved = col.psum(
                jnp.where(owned & move, 1, 0).astype(jnp.int32), axis) > 0
            Sigma_new = jax.ops.segment_sum(K, C_new, num_segments=nv)
            gain = col.psum(jnp.sum(jnp.where(owned & move, best, 0.0)), axis)
            want = col.pmax((want & owned).astype(jnp.int32), axis) > 0
    return C_new, Sigma_new, moved, gain, want


@partial(jax.jit, static_argnames=("max_iters", "sync", "prune", "axis",
                                   "scan", "seg_impl", "block_m", "m_total"))
def local_move(
    src,
    dst,
    w,
    C0,
    K,
    Sigma0,
    two_m,
    *,
    tau,
    max_iters: int = 20,
    sync: str = "handshake",
    prune: bool = True,
    axis=None,
    owned=None,
    scan: str = "sort",
    skip=None,
    adj=None,
    seg_impl: str = "auto",
    block_m: int = 0,
    gidx=None,
    m_total=None,
):
    """Run the local-moving phase to convergence.

    Returns ``(C, Sigma, l_i)`` — final membership, community weights, and
    the number of iterations performed (paper's ``l_i``; drives the global
    convergence check ``l_i <= 1``).

    ``scan='dense'`` selects the small-graph dense community-matrix sweep
    (bit-identical results; single-device only — see module docstring).

    ``seg_impl`` selects the sortscan's segment-reduction backend
    ('auto' | 'xla' | 'pallas' | 'scatter'; module docstring); ``block_m``
    is the Pallas kernel block size (0 = default / autotuned by the
    service engine).  All choices return bit-identical results.

    ``skip`` (traced bool[] or None): when True the loop exits before the
    first sweep and returns the initial state.  Callers that re-enter the
    pass loop under ``vmap`` pass their per-element done flag here so a
    finished graph contributes zero trips to the batched while_loop instead
    of re-converging work that the pass driver then discards.

    ``adj`` (bool[nv, nv] or None, dense scan only): precomputed edge
    adjacency; lets the pass driver amortize one scatter across the
    local-move and split phases.

    ``gidx`` / ``m_total`` (sharded only): global edge slots of this
    shard's edges and the global edge capacity — lets the per-sweep
    modularity reduce exactly reproduce the single-device fold (see
    :func:`realized_modularity`).  ``m_total`` is static.
    """
    nv = C0.shape[0]
    ghost = nv - 1
    if scan == "dense" and axis is not None:
        raise ValueError("scan='dense' is single-device only (axis=None)")
    if owned is None and scan != "dense":
        owned = jnp.ones((nv,), bool)
    no_skip = jnp.bool_(False) if skip is None else skip
    ids = jnp.arange(nv, dtype=jnp.int32)
    seg_impl = ops.resolve_impl(seg_impl)
    sweep_kw = {}
    if scan == "dense":
        sweep = _half_sweep_dense
        if adj is None:
            # boolean adjacency for the pruning wake-up (replaces the
            # per-sweep segment_max scatter with a [nv, nv] reduction;
            # booleans, so any formulation is exact).  Padded edges land at
            # (ghost, ghost) where moved[ghost] is always False.
            adj = jnp.zeros((nv, nv), bool).at[src, dst].set(True)
        # loop-invariant cell validity, hoisted out of the sweeps
        sweep_kw["valid_cell"] = (ids[:, None] < ghost) & (ids[None, :] < ghost)
    elif seg_impl == "scatter":
        sweep = _half_sweep_scatter
    else:
        sweep = _half_sweep
        sweep_kw["seg_impl"] = seg_impl
        sweep_kw["block_m"] = block_m

    def body(state: MoveState) -> MoveState:
        (C, Sigma, active, q_prev, dq_it, _, it, n_prod,
         C_best, Sigma_best, q_best) = state
        moved_any = jnp.zeros((nv,), bool)
        pbit = _hash_parity(ids, it)        # re-rolled bipartition per sweep
        if sync == "handshake":
            phases = ((0, 1), (1, 0))       # (mover parity, target parity)
        elif sync == "parity":
            phases = ((0, None), (1, None))
        else:  # 'all': plain synchronous Jacobi (ablation)
            phases = ((None, None),)
        for ph, tp in phases:
            parity_ok = jnp.ones((nv,), bool) if ph is None else (pbit == ph)
            movable = active & parity_ok
            target_ok = None if tp is None else (pbit == tp)
            C, Sigma, moved, _, want = sweep(
                src, dst, w, C, K, Sigma, two_m, owned, movable, axis,
                target_ok=target_ok, anchored=(ph is not None), **sweep_kw,
            )
            moved_any = moved_any | moved
        q_now = realized_modularity(src, dst, w, C, Sigma, two_m, owned, axis,
                                    gidx, m_total)
        if prune:
            # neighbors of moved vertices wake up; everyone else sleeps
            if scan == "dense":
                nbr_moved = jnp.any(adj & moved_any[:, None], axis=0)
            elif seg_impl == "scatter":
                nbr_moved = jax.ops.segment_max(
                    moved_any[src].astype(jnp.int32), dst, num_segments=nv
                )
                nbr_moved = col.pmax(nbr_moved, axis) > 0
            else:
                # keyed by the sorted src instead of the unsorted dst: on
                # the symmetric directed COO, out-neighbors == in-neighbors
                # as sets, and booleans make any formulation exact
                nbr_moved = ops.segreduce_sorted(
                    moved_any[dst].astype(jnp.int32), src, nv, op="max",
                    impl=seg_impl, block_m=block_m)
                nbr_moved = col.pmax(nbr_moved, axis) > 0
            active = nbr_moved | want  # schedule-blocked desire stays awake
        else:
            active = jnp.ones((nv,), bool)
        better = q_now > q_best
        C_best = jnp.where(better, C, C_best)
        Sigma_best = jnp.where(better, Sigma, Sigma_best)
        q_best = jnp.maximum(q_now, q_best)
        gain = q_now - q_prev
        return MoveState(
            C, Sigma, active, q_now, gain, dq_it, it + 1,
            n_prod + (gain > tau).astype(jnp.int32),
            C_best, Sigma_best, q_best,
        )

    def cond(state: MoveState):
        # converge only after two consecutive no-gain sweeps: a single sweep
        # can stall purely because of an unlucky parity roll
        warmup = state.it < 2
        progress = (state.dQ_iter > tau) | (state.dQ_prev > tau)
        return (warmup | progress) & (state.it < max_iters) & ~no_skip

    C_init = C0.astype(jnp.int32).at[ghost].set(ghost)
    q0 = realized_modularity(src, dst, w, C_init, Sigma0, two_m, owned, axis,
                             gidx, m_total)
    init = MoveState(
        C=C_init,
        Sigma=Sigma0,
        active=jnp.ones((nv,), bool),
        q_prev=q0,
        dQ_iter=jnp.float32(jnp.inf),
        dQ_prev=jnp.float32(jnp.inf),
        it=jnp.int32(0),
        n_prod=jnp.int32(0),
        C_best=C_init,
        Sigma_best=Sigma0,
        q_best=q0,
    )
    out = jax.lax.while_loop(cond, body, init)
    # Return the best realized state: local_move is monotone in true Q.
    # li keeps the paper's semantics: li == 1 <=> no productive iteration
    # (global convergence signal for the pass driver).
    li = jnp.minimum(out.n_prod + 1, out.it)
    return out.C_best, out.Sigma_best, jnp.maximum(li, 1)
