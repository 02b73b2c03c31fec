"""Incremental community updates for fully-dynamic graphs (delta-screening).

Production graphs change; recomputing Louvain from scratch per batch of
updates wastes the previous solution.  Following the Delta-Screening idea
(Zarayeneh & Kalyanaraman 2021 — the paper's citation [47]), an update
batch only perturbs communities *near* the touched region:

  0. **vertex rewrite** (:func:`apply_vertex_updates`): removed vertices
     first lose every incident directed edge through the same signed-delta
     slot-freeing machinery as step 1, are tombstoned, and the tombstones
     are compacted away in the same host-side pass — surviving ids shift
     down by the number of removed ids below them (the *compaction
     contract*: order-preserving, so clients can mirror the remap from
     the removed ids alone).  Additions then claim the next free ids
     ``[n', n' + add)`` from the padding slots; growing past ``n_cap``
     raises :class:`CapacityError`, which the service maps to
     re-bucketing exactly like edge-capacity overflow,
  1. apply the signed edge weight-deltas to the padded COO in place
     (additions fill free slots, decreases rewrite existing entries,
     deletions free their slots for reuse) — endpoint ids live in the
     post-rewrite id space, so a batch may wire up its own new vertices,
  2. mark affected vertices: endpoints of changed edges, their same- and
     adjacent-community neighbors — for weight *decreases* the whole
     community of each endpoint, and for vertex ops the new vertices plus
     every member of a removed vertex's former community, because a
     removed cut vertex (like a removed intra-community edge) can
     disconnect or dissolve the community,
  3. warm-start the local-moving phase from the previous membership with
     ONLY affected vertices active (the pruning mask doubles as the
     screening set — the paper's own pruning machinery, reused),
  4. run the SP split + renumber as usual.  The split pass is what makes
     deletions — of edges and of vertices — safe: a community
     disconnected by a removed bridge or cut vertex is relabeled per
     connected component, so the paper's
     no-internally-disconnected-communities guarantee survives every
     update (asserted by the service smoke and the planted tests).

The warm-started pass converges in a handful of sweeps when the update
touches a small region, versus full passes from singletons.

:class:`GraphUpdate` is the combined vertex+edge batch type (plain
``(u, v, dw)`` tuples stay accepted everywhere and mean edges-only);
:func:`prepare_graph_update` is the ONE host-side fold for steps 0-2 that
the core (:func:`update_communities`) and the service store share.

Batching: :func:`warm_update_impl` is the jit/vmap-composable form of
steps 2-4 (the host-side rewrites of steps 0-1 stay per graph; ``nv`` is
capacity-static, so vertex churn never changes compile keys).  The
service engine vmaps it across same-bucket graphs so update-dominated
traffic gets the same batching win as detection traffic
(:meth:`repro.service.engine.BatchedLouvainEngine.update_batch`).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import _segments as seg
from repro.core.detect import disconnected_communities_impl
from repro.core.local_move import MoveState, _half_sweep, \
    _half_sweep_dense, _half_sweep_scatter, _hash_parity, \
    realized_modularity
from repro.core.modularity import modularity
from repro.core.split import split_labels
from repro.graph.container import Graph, from_coo, remap_vertices
from repro.kernels import ops
from repro.telemetry.spans import scope


class CapacityError(ValueError):
    """A rewrite does not fit the graph's static capacities (vertex
    additions past ``n_cap``, or a merged edge set past ``m_cap``).  The
    service maps this to re-bucketing; plain validation failures raise
    bare ``ValueError`` and must NOT be conflated with it."""


def merge_edge_deltas(g: Graph, new_src, new_dst, new_dw):
    """Merge directed signed weight-deltas into ``g``'s live edge set.

    Host-side numpy.  Per directed pair ``(u, v)`` the net delta of the
    batch is added to the existing entry's weight (parallel live entries,
    a legacy of the old append-only path, are coalesced first).  Pairs
    whose resulting weight is ``<= 0`` are **deleted** — so passing
    ``-w`` for an existing weight-``w`` edge removes it, and deleting an
    edge that does not exist is a no-op (idempotent).  New pairs with a
    positive net delta are insertions.

    Returns ``(src, dst, w)`` of the merged live entries, sorted by
    ``(src, dst)`` — unpadded, so callers choose the output capacity.
    """
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = np.asarray(g.w)
    live = src < g.n_cap
    u = np.concatenate([src[live], np.asarray(new_src, np.int32)])
    v = np.concatenate([dst[live], np.asarray(new_dst, np.int32)])
    vals = np.concatenate([w[live].astype(np.float32),
                           np.asarray(new_dw, np.float32)])
    # group by directed pair; float64 accumulation so an exact add-then-
    # delete round-trip cancels to 0.0
    key = u.astype(np.int64) * (g.n_cap + 1) + v.astype(np.int64)
    order = np.argsort(key, kind="stable")
    key, u, v, vals = key[order], u[order], v[order], vals[order]
    first = np.ones(key.shape, bool)
    first[1:] = key[1:] != key[:-1]
    run = np.cumsum(first) - 1
    w_net = np.bincount(run, weights=vals).astype(np.float32)
    keep = w_net > 0.0
    return u[first][keep], v[first][keep], w_net[keep]


def apply_edge_updates(g: Graph, new_src, new_dst, new_dw):
    """Apply directed signed weight-deltas in place (host-side numpy).

    Fully dynamic: positive deltas on new pairs append into free padded
    slots, deltas on existing pairs rewrite the entry's weight in place,
    and entries driven to ``<= 0`` are removed — their slots return to
    the padding pool, so capacity freed by deletions is reusable by later
    additions (compaction: the edge list is re-sorted every update, which
    pushes the ghost-keyed padding back to the tail).

    Returns a new Graph; raises :class:`CapacityError` (a ``ValueError``)
    if the merged live edge set exceeds ``m_cap`` (the service maps this
    to re-bucketing).
    """
    u, v, w = merge_edge_deltas(g, new_src, new_dst, new_dw)
    n_live = len(u)
    if n_live > g.m_cap:
        raise CapacityError(
            f"edge capacity exhausted ({n_live} live edges > m_cap "
            f"{g.m_cap})")
    ghost = g.n_cap
    pad = g.m_cap - n_live
    # numpy leaves on purpose: the update hot path prepares many graphs
    # host-side before one batched device call, and eager per-graph
    # host->device copies here measurably dominate prepare time; jit/vmap
    # convert the leaves exactly once at dispatch.
    return Graph(
        src=np.concatenate([u, np.full(pad, ghost, np.int32)]).astype(
            np.int32),
        dst=np.concatenate([v, np.full(pad, ghost, np.int32)]).astype(
            np.int32),
        w=np.concatenate([w, np.zeros(pad, np.float32)]),
        n_nodes=g.n_nodes, n_cap=g.n_cap, m_cap=g.m_cap,
    )


def directed_deltas(u, v, dw):
    """Expand undirected update pairs to the container convention: each
    ``u != v`` pair in both directions, self-loops once (full weight)."""
    u, v, dw = (np.asarray(x) for x in (u, v, dw))
    loops = u == v
    src = np.concatenate([u[~loops], v[~loops], u[loops]]).astype(np.int32)
    dst = np.concatenate([v[~loops], u[~loops], u[loops]]).astype(np.int32)
    ww = np.concatenate([dw[~loops], dw[~loops],
                         dw[loops]]).astype(np.float32)
    return src, dst, ww


def touched_mask(nv: int, u, v) -> np.ndarray:
    """bool[nv] host-side mask of update endpoints (vmappable screening
    input — index lists have data-dependent shapes, masks do not)."""
    t = np.zeros((nv,), bool)
    t[np.asarray(u, np.int64)] = True
    t[np.asarray(v, np.int64)] = True
    return t


@dataclasses.dataclass(frozen=True)
class GraphUpdate:
    """One combined vertex+edge update batch (the service's update unit).

    Step order within a batch:

    0. **vertex rewrite** — every id in ``remove`` is tombstoned: its
       incident directed edges are deleted (freed slots return to the
       padding pool) and the tombstones are compacted away host-side in
       the same pass.  The compaction contract is order-preserving: a
       surviving id shifts down by the number of removed ids below it, so
       callers can mirror the remap from the removed ids alone.  ``add``
       fresh vertices then claim the next free ids ``[n', n' + add)``.
    1. **edge deltas** — ``(u, v, dw)`` undirected signed weight-deltas,
       exactly as before, with endpoint ids in the POST-rewrite id space
       (so a batch may wire up the vertices it just added).

    Plain ``(u, v, dw)`` tuples coerce to an edges-only ``GraphUpdate``
    (:func:`as_update`), so every pre-existing call site keeps working.
    """

    u: Any = ()
    v: Any = ()
    dw: Any = ()
    add: int = 0
    remove: Any = ()

    @property
    def has_vertex_ops(self) -> bool:
        return bool(self.add) or np.asarray(self.remove).size > 0

    @property
    def has_edges(self) -> bool:
        return np.asarray(self.u).size > 0


def as_update(updates) -> GraphUpdate:
    """Coerce (and statically validate) an update batch.

    Accepts a :class:`GraphUpdate` or a legacy ``(u, v, dw)`` tuple;
    returns a normalized ``GraphUpdate`` with numpy arrays.  Raises
    ``ValueError`` for malformed batches: mismatched/non-1-D edge arrays,
    non-integer endpoint ids, a negative ``add``, or a ``remove`` list
    with duplicates or negative ids.  Upper id bounds depend on the
    evolving ``n_nodes`` and are checked at apply time
    (:func:`check_vertex_ids` / :func:`apply_vertex_updates`).
    """
    if isinstance(updates, GraphUpdate):
        u, v, dw = updates.u, updates.v, updates.dw
        add, remove = updates.add, updates.remove
    else:
        u, v, dw = updates
        add, remove = 0, ()
    u, v = np.asarray(u), np.asarray(v)
    dw = np.asarray(dw, np.float32)
    if not (u.shape == v.shape == dw.shape and u.ndim == 1):
        raise ValueError(
            f"update arrays must be equal-length 1-D, got shapes "
            f"{u.shape}, {v.shape}, {dw.shape}")
    for name, x in (("u", u), ("v", v)):
        if x.size and not np.issubdtype(x.dtype, np.integer):
            raise ValueError(
                f"edge endpoint ids ({name}) must be integers, got dtype "
                f"{x.dtype}")
    add = int(add)
    if add < 0:
        raise ValueError(f"add must be >= 0, got {add}")
    remove = np.asarray(remove)
    if remove.size and not np.issubdtype(remove.dtype, np.integer):
        raise ValueError(
            f"remove ids must be integers, got dtype {remove.dtype}")
    remove = remove.astype(np.int64).ravel()
    if remove.size:
        if int(remove.min()) < 0:
            raise ValueError("remove ids must be >= 0")
        if np.unique(remove).size != remove.size:
            raise ValueError("duplicate ids in remove")
    return GraphUpdate(u=u, v=v, dw=dw, add=add, remove=remove)


def check_vertex_ids(u, v, n_nodes: int):
    """The id-validity contract: every edge endpoint must name a live
    vertex, ``0 <= id < n_nodes``.  Ids in ``[n_nodes, n_cap)`` are
    padding slots and become legal only by claiming them through the
    vertex-addition path (:class:`GraphUpdate` ``add``) first."""
    for name, x in (("u", u), ("v", v)):
        x = np.asarray(x)
        if not x.size:
            continue
        lo, hi = int(x.min()), int(x.max())
        if lo < 0 or hi >= n_nodes:
            raise ValueError(
                f"edge endpoint ids ({name}) must be in [0, n_nodes="
                f"{n_nodes}); got range [{lo}, {hi}]")


def _survivor_perm(n: int, remove: np.ndarray, nv: int) -> np.ndarray:
    """Order-preserving compaction map: old id -> new id over ``[0, nv)``,
    ``-1`` for tombstoned (and dead/ghost) slots."""
    alive = np.zeros(nv, bool)
    alive[:n] = True
    alive[remove] = False
    perm = np.full(nv, -1, np.int64)
    perm[np.flatnonzero(alive)] = np.arange(n - remove.size)
    return perm


def apply_vertex_updates(g: Graph, C_prev, *, add: int = 0, remove=(),
                         touched=None):
    """Step-0 vertex rewrite (host-side numpy): tombstone + compact
    removals, then grow ``n_nodes`` by ``add`` within ``n_cap``.

    * ``remove``: live vertex ids.  Their incident directed edges are
      deleted (slots freed for reuse) and the ids compacted away under
      the order-preserving contract (see :class:`GraphUpdate`).
    * ``add``: number of fresh vertices; they claim ids ``[n', n'+add)``
      where ``n'`` is the post-removal count.  Raises
      :class:`CapacityError` when the result exceeds ``n_cap`` (the
      service re-buckets, exactly like edge overflow).
    * ``C_prev``: previous dense membership (or ``None`` to skip label
      bookkeeping).  Survivor labels are converted to min-member-id
      representatives in the new id space so fresh vertices can start as
      own-id singletons without colliding with an existing community;
      :func:`warm_update_impl`'s final renumber densifies them again.
    * ``touched``: optionally, an accumulated screening mask in the OLD
      id space; it is carried through the remap.

    Returns ``(g_new, C_new, touched_new, info)`` where the new touched
    mask seeds delta-screening with (a) the surviving endpoints of every
    deleted incident edge, (b) every member of a removed vertex's former
    community — a removed cut vertex can disconnect its community, so the
    whole community must be re-evaluated and re-split — and (c) the new
    vertices.  ``info`` carries ``n_deleted`` (gross directed edge
    removals), ``n_added``, ``n_removed``, and ``perm`` (the old->new id
    map, ``-1`` at tombstones).
    """
    n = int(g.n_nodes)
    nv = g.nv
    rem = np.asarray(remove, np.int64).ravel()
    add = int(add)
    if add < 0:
        raise ValueError(f"add must be >= 0, got {add}")
    if rem.size:
        if int(rem.min()) < 0 or int(rem.max()) >= n:
            raise ValueError(
                f"remove ids must be in [0, n_nodes={n}); got range "
                f"[{int(rem.min())}, {int(rem.max())}]")
        if np.unique(rem).size != rem.size:
            raise ValueError("duplicate ids in remove")
    n_keep = n - rem.size
    n_new = n_keep + add
    if n_new > g.n_cap:
        raise CapacityError(
            f"vertex capacity exhausted ({n_new} vertices > n_cap "
            f"{g.n_cap})")
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    t_old = (np.zeros(nv, bool) if touched is None
             else np.array(touched, dtype=bool, copy=True))
    C = None if C_prev is None else np.asarray(C_prev)
    n_deleted = 0
    if rem.size:
        dead = np.zeros(nv, bool)
        dead[rem] = True
        inc = (src < g.n_cap) & (dead[src] | dead[dst])
        n_deleted = int(inc.sum())
        # (a) endpoints of deleted incident edges (tombstoned ones are
        # dropped by the remap below)
        t_old[src[inc]] = True
        t_old[dst[inc]] = True
        # (b) the removed vertices' whole former communities
        if C is not None and n:
            lab_dead = np.zeros(nv, bool)
            lab_dead[C[rem]] = True
            t_old[:n] |= lab_dead[C[:n]]
    perm = _survivor_perm(n, rem, nv)
    if rem.size:
        g2 = remap_vertices(g, perm, n_new)
    else:
        # pure addition: the permutation is the identity and no edge is
        # touched — only n_nodes changes, so skip the O(m log m) COO
        # gather/re-sort on the latency-sensitive warm path
        g2 = dataclasses.replace(g, n_nodes=np.int32(n_new))
    old_ids = np.flatnonzero(perm >= 0)
    t_new = np.zeros(nv, bool)
    t_new[:n_keep] = t_old[old_ids]
    t_new[n_keep:n_new] = True                      # (c) the new vertices
    if C is None:
        C2 = None
    else:
        # survivors keep their partition, re-labeled by min-member-id in
        # the NEW id space; dead/pad slots go to the ghost label (renumber
        # collapses invalid slots there anyway)
        lab = C[old_ids]
        rep = np.full(nv, nv, np.int64)
        np.minimum.at(rep, lab, np.arange(n_keep))
        C2 = np.full(nv, nv - 1, np.int32)
        C2[:n_keep] = rep[lab]
        C2[n_keep:n_new] = np.arange(n_keep, n_new)  # own-id singletons
    info = dict(n_deleted=n_deleted, n_added=add, n_removed=int(rem.size),
                perm=perm)
    return g2, C2, t_new, info


def tombstone_vertices(g: Graph, C_prev, remove, *, touched=None):
    """Deferred-compaction removal: detach ids WITHOUT the remap.

    The compaction of :func:`apply_vertex_updates` re-sorts the whole
    COO per removal batch; under removal-heavy streams the service can
    instead *tombstone* — delete the removed ids' incident edges (slots
    return to the padding pool) and leave the ids in place as edgeless
    own-label singletons — and pay one compaction for a whole window of
    removals later (``ResultStore(compact_window=...)``).  Surviving
    internal ids do NOT shift; ``n_nodes`` is unchanged; each tombstone
    still counts as a (degenerate, connected) singleton community until
    the flush compacts it away.

    ``C_prev`` label hygiene mirrors :func:`apply_vertex_updates`:
    surviving communities are re-labeled by their min *surviving* member
    id, and each removed id becomes its own-id singleton — so a removed
    label-carrier cannot collide with the community it used to name.
    Tombstoned ids from earlier batches keep their own-id labels
    (they're singletons, so the min-member rule is a fixpoint for them).

    Returns ``(g_new, C_new, touched_new, info)`` with the same touched
    rules (a)/(b) as :func:`apply_vertex_updates` — deleted-edge
    endpoints and the removed ids' whole former communities — and
    ``info['perm'] = None`` (no remap happened; ``info['deferred']``
    carries the tombstoned ids).  Raises ``ValueError`` for out-of-range
    or duplicate ids (re-removing an already-tombstoned id is the
    *caller's* bookkeeping to reject — this function cannot tell a
    tombstone from a live isolated vertex).
    """
    n = int(g.n_nodes)
    nv = g.nv
    rem = np.asarray(remove, np.int64).ravel()
    if not rem.size:
        t = (np.zeros(nv, bool) if touched is None
             else np.array(touched, dtype=bool, copy=True))
        C = None if C_prev is None else np.asarray(C_prev, np.int32).copy()
        return g, C, t, dict(n_deleted=0, n_added=0, n_removed=0,
                             perm=None, deferred=rem)
    if int(rem.min()) < 0 or int(rem.max()) >= n:
        raise ValueError(
            f"remove ids must be in [0, n_nodes={n}); got range "
            f"[{int(rem.min())}, {int(rem.max())}]")
    if np.unique(rem).size != rem.size:
        raise ValueError("duplicate ids in remove")
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = np.asarray(g.w)
    dead = np.zeros(nv, bool)
    dead[rem] = True
    live = src < g.n_cap
    inc = live & (dead[src] | dead[dst])
    n_deleted = int(inc.sum())
    t = (np.zeros(nv, bool) if touched is None
         else np.array(touched, dtype=bool, copy=True))
    # (a) surviving endpoints of deleted incident edges
    t[src[inc]] = True
    t[dst[inc]] = True
    C = None if C_prev is None else np.asarray(C_prev)
    if C is not None and n:
        # (b) the removed ids' whole former communities
        lab_dead = np.zeros(nv, bool)
        lab_dead[C[rem]] = True
        t[:n] |= lab_dead[C[:n]]
    t[rem] = False       # a tombstone has no neighbors to re-evaluate
    keep = live & ~inc
    pad = src.size - int(keep.sum())
    ghost = np.int32(g.n_cap)
    g2 = Graph(
        src=np.concatenate([src[keep],
                            np.full(pad, ghost, np.int32)]).astype(np.int32),
        dst=np.concatenate([dst[keep],
                            np.full(pad, ghost, np.int32)]).astype(np.int32),
        w=np.concatenate([w[keep], np.zeros(pad, np.float32)]).astype(
            np.float32),
        n_nodes=g.n_nodes, n_cap=g.n_cap, m_cap=g.m_cap,
    )
    if C is None:
        C2 = None
    else:
        # min-*surviving*-member representative per surviving community;
        # removed ids become own-id singletons (see docstring)
        alive_ids = np.flatnonzero(~dead[:n])
        lab = C[alive_ids]
        rep = np.full(nv, nv, np.int64)
        np.minimum.at(rep, lab, alive_ids)
        C2 = np.full(nv, nv - 1, np.int32)
        C2[alive_ids] = rep[lab]
        C2[rem] = rem
    info = dict(n_deleted=n_deleted, n_added=0, n_removed=int(rem.size),
                perm=None, deferred=rem)
    return g2, C2, t, info


def rebuild_with_vertex_ops(g: Graph, *, add: int = 0, remove=()) -> Graph:
    """Capacity-free vertex rewrite for the re-bucketing fallback: the
    same remove-compact-then-add semantics as :func:`apply_vertex_updates`
    but the result takes natural capacities (the caller re-admits it into
    a bigger bucket)."""
    n = int(g.n_nodes)
    rem = np.asarray(remove, np.int64).ravel()
    if rem.size and (int(rem.min()) < 0 or int(rem.max()) >= n):
        raise ValueError(f"remove ids must be in [0, n_nodes={n})")
    perm = _survivor_perm(n, rem, g.nv)
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = np.asarray(g.w)
    keep = (src < g.n_cap) & (perm[src] >= 0) & (perm[dst] >= 0)
    n_new = n - rem.size + int(add)
    return from_coo(n_new, perm[src[keep]].astype(np.int32),
                    perm[dst[keep]].astype(np.int32), w[keep])


def gross_deleted(g_old: Graph, g_new: Graph) -> int:
    """Directed entries whose (src, dst) pair left the live set — the
    GROSS deletion count (a batch that also inserts must still report
    its removals; the net live-entry delta would hide them)."""
    K = g_old.n_cap + 1
    so, do = np.asarray(g_old.src), np.asarray(g_old.dst)
    sn, dn = np.asarray(g_new.src), np.asarray(g_new.dst)
    mo, mn = so < g_old.n_cap, sn < g_new.n_cap
    old = so[mo].astype(np.int64) * K + do[mo]
    new = sn[mn].astype(np.int64) * K + dn[mn]
    return int(np.setdiff1d(np.unique(old), new).size)


def prepare_graph_update(g: Graph, C_prev, updates, *, touched=None):
    """The ONE host-side fold for steps 0-2 of a single update batch.

    Vertex rewrite first (when the batch carries vertex ops), then the
    edge deltas — whose endpoint ids are bounds-checked against the
    post-rewrite ``n_nodes`` **before** the COO is touched
    (``ValueError``; ids in ``[n_nodes, n_cap)`` are only legal once
    claimed via ``add``) — then the accumulated screening mask.  Both
    :func:`update_communities` and the service store's
    ``prepare_update_seq`` run exactly this fold, so the immediate,
    engine-batched and async-frontend paths cannot diverge.

    Returns ``(g, C, touched, info)``; raises :class:`CapacityError` for
    vertex/edge capacity overflow and plain ``ValueError`` for malformed
    input (callers must not conflate the two — only capacity maps to
    re-bucketing).  Validation strictly precedes any capacity raise, so
    a batch that raises ``CapacityError`` is well-formed: the service's
    capacity-free re-bucketing rebuild can replay it without failing.
    """
    upd = as_update(updates)
    # validate the WHOLE batch before any capacity check can fire: a
    # malformed batch must raise ValueError with the caller's entry
    # untouched, never be half-classified as a capacity overflow (the
    # service invalidates + re-buckets on CapacityError, and the
    # capacity-free rebuild then replays these same ids against the same
    # logical post-rewrite vertex count)
    n_after = int(g.n_nodes)
    if upd.has_vertex_ops:
        rem = upd.remove
        if rem.size and int(rem.max()) >= n_after:
            raise ValueError(
                f"remove ids must be in [0, n_nodes={n_after}); got max "
                f"{int(rem.max())}")
        n_after = n_after - rem.size + upd.add
    if upd.has_edges:
        check_vertex_ids(upd.u, upd.v, n_after)
    if upd.has_vertex_ops:
        g, C, t, info = apply_vertex_updates(
            g, C_prev, add=upd.add, remove=upd.remove, touched=touched)
    else:
        C = None if C_prev is None else np.asarray(C_prev)
        t = (np.zeros(g.nv, bool) if touched is None
             else np.array(touched, dtype=bool, copy=True))
        info = dict(n_deleted=0, n_added=0, n_removed=0, perm=None)
    if upd.has_edges:
        g_old = g
        g = apply_edge_updates(g, *directed_deltas(upd.u, upd.v, upd.dw))
        info["n_deleted"] += gross_deleted(g_old, g)
        t |= touched_mask(g.nv, upd.u, upd.v)
    return g, C, t, info


def affected_mask(g: Graph, C, touched):
    """Screening set from a touched-endpoint mask (jit/vmap-composable).

    Marks (a) the touched endpoints, (b) their neighbors, and (c) every
    member of a community containing a touched endpoint.  (c) is what
    extends delta-screening to weight *decreases*: a decreased or removed
    intra-community edge re-evaluates both endpoints' communities in
    full, so members can re-bind after the split pass breaks the
    community apart (Zarayeneh & Kalyanaraman's deletion rule).  For pure
    increases (c) is the same community-adjacency superset the additions
    path always used.
    """
    nv = g.nv
    t = touched
    nbr = jax.ops.segment_max(
        t[g.src].astype(jnp.int32), g.dst, num_segments=nv) > 0
    comm_touched = jax.ops.segment_max(
        jnp.where(t, 1, 0), C, num_segments=nv) > 0
    member = comm_touched[C]
    return t | nbr | member


def affected_vertices(g: Graph, C, touched):
    """Index-list façade over :func:`affected_mask` (legacy API)."""
    t = jnp.zeros((g.nv,), bool).at[touched].set(True)
    return affected_mask(g, C, t)


def warm_local_move_impl(src, dst, w, C_prev, two_m, active0, *, tau=1e-3,
                         max_iters: int = 10, sync: str = "handshake",
                         scan: str = "sort", adj=None,
                         seg_impl: str = "auto", block_m: int = 0):
    """Local-moving warm-started from C_prev with a restricted active set.

    Mirrors local_move but (a) starts from the previous membership instead
    of singletons and (b) seeds the pruning mask with the screening set.
    ``scan`` selects the sweep implementation exactly as in local_move;
    ``seg_impl``/``block_m`` select the sortscan's segment-reduction
    backend (kernels/ops.py; all impls bit-identical); ``adj`` optionally
    shares a precomputed bool[nv, nv] adjacency (dense scan) so callers
    amortize the scatter across phases.
    Unjitted — vmap/jit-compose freely (the batched update path vmaps it).
    Returns (C, Sigma, iterations).
    """
    nv = C_prev.shape[0]
    ghost = nv - 1
    ids = jnp.arange(nv, dtype=jnp.int32)
    owned = None if scan == "dense" else jnp.ones((nv,), bool)
    seg_impl = ops.resolve_impl(seg_impl)
    K = jax.ops.segment_sum(w, src, num_segments=nv)
    C0 = C_prev.astype(jnp.int32).at[ghost].set(ghost)
    Sigma0 = jax.ops.segment_sum(K, C0, num_segments=nv)
    sweep_kw = {}
    if scan == "dense":
        sweep = _half_sweep_dense
        if adj is None:
            adj = jnp.zeros((nv, nv), bool).at[src, dst].set(True)
        sweep_kw["valid_cell"] = (ids[:, None] < ghost) & (ids[None, :] < ghost)
    elif seg_impl == "scatter":
        sweep = _half_sweep_scatter
        adj = None
    else:
        sweep = _half_sweep
        sweep_kw["seg_impl"] = seg_impl
        sweep_kw["block_m"] = block_m
        adj = None

    def body(state: MoveState) -> MoveState:
        (C, Sigma, active, q_prev, dq_it, _, it, n_prod,
         C_best, Sigma_best, q_best) = state
        moved_any = jnp.zeros((nv,), bool)
        pbit = _hash_parity(ids, it)
        for ph, tp in ((0, 1), (1, 0)):
            movable = active & (pbit == ph)
            target_ok = pbit == tp
            C, Sigma, moved, _, want = sweep(
                src, dst, w, C, K, Sigma, two_m, owned, movable, None,
                target_ok=target_ok, anchored=True, **sweep_kw,
            )
            moved_any = moved_any | moved
        q_now = realized_modularity(src, dst, w, C, Sigma, two_m, owned, None)
        if scan == "dense":
            nbr_moved = jnp.any(adj & moved_any[:, None], axis=0)
        elif seg_impl == "scatter":
            nbr_moved = jax.ops.segment_max(
                moved_any[src].astype(jnp.int32), dst, num_segments=nv) > 0
        else:
            # sorted-src wake-up: exact on the symmetric COO (booleans)
            nbr_moved = ops.segreduce_sorted(
                moved_any[dst].astype(jnp.int32), src, nv, op="max",
                impl=seg_impl, block_m=block_m) > 0
        active = nbr_moved | (want & active)
        better = q_now > q_best
        C_best = jnp.where(better, C, C_best)
        Sigma_best = jnp.where(better, Sigma, Sigma_best)
        q_best = jnp.maximum(q_now, q_best)
        gain = q_now - q_prev
        return MoveState(C, Sigma, active, q_now, gain, dq_it, it + 1,
                         n_prod + (gain > tau).astype(jnp.int32),
                         C_best, Sigma_best, q_best)

    def cond(state: MoveState):
        warmup = state.it < 2
        progress = (state.dQ_iter > tau) | (state.dQ_prev > tau)
        return (warmup | progress) & (state.it < max_iters)

    q0 = realized_modularity(src, dst, w, C0, Sigma0, two_m, owned, None)
    init = MoveState(C0, Sigma0, active0, q0, jnp.float32(jnp.inf),
                     jnp.float32(jnp.inf), jnp.int32(0), jnp.int32(0),
                     C0, Sigma0, q0)
    out = jax.lax.while_loop(cond, body, init)
    return out.C_best, out.Sigma_best, out.it


warm_local_move = partial(
    jax.jit, static_argnames=("max_iters", "sync", "scan", "seg_impl",
                              "block_m")
)(warm_local_move_impl)


def warm_update_impl(g: Graph, C_prev, touched, *, tau=1e-3,
                     max_iters: int = 10, scan: str = "sort",
                     seg_impl: str = "auto", block_m: int = 0):
    """One warm update on an already-rewritten graph (jit/vmap-composable).

    screening -> warm local move -> split -> renumber -> detector ->
    modularity, all on device.  This is the ONE compute path both the
    store's immediate update (:meth:`repro.service.store.ResultStore.
    apply_update`) and the engine's batched update path run, so their
    partitions agree exactly.  ``seg_impl``/``block_m`` pick the
    segment-reduction backend for every phase (bit-identical results).

    Returns a dict: ``C`` (dense int32[nv] membership), ``n_communities``,
    ``n_disconnected``, ``fraction``, ``q``, ``iterations``,
    ``n_affected``, ``split_moved`` (vertices the split pass relabelled).
    Each phase runs under its device scope (``repro.telemetry.spans.SCOPES``).
    """
    impl = "dense" if scan == "dense" else "coo"
    active0 = affected_mask(g, C_prev, touched)
    two_m = g.total_weight_2m()
    # one adjacency scatter shared by the warm sweep, the split fixpoint,
    # and the detector (dense scan) — mirrors louvain_impl's per-pass
    # sharing; booleans, so every formulation is exact
    adj = (jnp.zeros((g.nv, g.nv), bool).at[g.src, g.dst].set(True)
           if scan == "dense" else None)
    with scope("local_move"):
        C, _, it = warm_local_move_impl(
            g.src, g.dst, g.w, C_prev, two_m, active0,
            tau=tau, max_iters=max_iters, scan=scan, adj=adj,
            seg_impl=seg_impl, block_m=block_m,
        )
    with scope("split"):
        labels, _ = split_labels(g.src, g.dst, g.w, C, impl=impl, adj=adj,
                                 seg_impl=seg_impl, block_m=block_m)
    with scope("renumber"):
        C_new, n_comms = seg.renumber(labels, g.node_mask(), g.nv)
    with scope("detector"):
        det = disconnected_communities_impl(
            g.src, g.dst, g.w, C_new, g.n_nodes, impl=impl, adj=adj,
            seg_impl=seg_impl, block_m=block_m)
    with scope("modularity"):
        q = modularity(g.src, g.dst, g.w, C_new, seg_impl=seg_impl,
                       block_m=block_m)
    return dict(
        C=C_new,
        n_communities=n_comms,
        n_disconnected=det["n_disconnected"],
        fraction=det["fraction"],
        q=q,
        iterations=it,
        n_affected=jnp.sum(active0.astype(jnp.int32)),
        split_moved=jnp.sum((labels != C) & g.node_mask()).astype(jnp.int32),
    )


warm_update = partial(
    jax.jit, static_argnames=("max_iters", "scan", "seg_impl", "block_m")
)(warm_update_impl)


def update_communities(g_old: Graph, C_prev, updates, *, tau=1e-3,
                       max_iters: int = 10, scan: str = "sort",
                       seg_impl: str = "auto", block_m: int = 0):
    """Incrementally update a partition after one update batch.

    ``updates``: a :class:`GraphUpdate` (combined vertex+edge batch) or a
    legacy ``(u int32[], v int32[], dw f32[])`` tuple of undirected
    **signed** weight-deltas (each pair is applied in both directions;
    self-loops once, per the container convention).  Positive deltas add
    weight or insert edges; negative deltas decrease weight, and an entry
    driven to ``<= 0`` is deleted — its capacity slot becomes reusable.
    Vertex ops run first (step 0: removals compact ids, additions claim
    padding slots — see :class:`GraphUpdate`); edge endpoint ids are
    validated against the post-rewrite ``n_nodes``.  Returns
    (g_new, C_new dense, stats).  ``scan='dense'`` routes the warm
    local-move and the split through the small-graph dense kernels (the
    service's low-latency update path).
    """
    g, C_host, t, info = prepare_graph_update(g_old, C_prev, updates)
    out = warm_update(g, jnp.asarray(C_host), jnp.asarray(t),
                      tau=tau, max_iters=max_iters, scan=scan,
                      seg_impl=seg_impl, block_m=block_m)
    stats = dict(
        iterations=out["iterations"],
        n_communities=out["n_communities"],
        n_affected=out["n_affected"],
        split_moved=out["split_moved"],
        n_disconnected=out["n_disconnected"],
        q=out["q"],
        n_deleted=info["n_deleted"],
        n_added=info["n_added"],
        n_removed=info["n_removed"],
    )
    return g, out["C"], stats
