"""The kernel dispatch point: public wrappers around the Pallas kernels.

Every op takes ``impl`` in {'auto', 'pallas', 'xla'}:
  * 'pallas' — the kernel (interpret-mode on CPU, compiled on TPU);
  * 'xla'    — the pure-jnp reference path (always available, any size);
  * 'auto'   — pallas when the input fits the kernel's envelope and we are
               on a TPU backend, else xla.  On this CPU container 'auto'
               resolves to xla so the system never pays interpret-mode cost
               in production paths; tests pin impl='pallas'.

:func:`segreduce_sorted` is the backend of the whole GSP-Louvain sortscan
core (``core/_segments.runs_reduce`` and the fused local-move sweep route
every run reduction here).  It additionally accepts ``impl='scatter'`` —
the pre-backend unsorted-scatter formulation, kept callable as the paired
baseline for the bench gate (``benchmarks/bench_kernels.py``,
``scripts/check_bench.py``) and as an escape hatch for callers that cannot
guarantee the sorted-ids contract.

The bit-exactness contract (load-bearing — see kernels/segsum.py): every
impl of ``segreduce_sorted`` folds each segment strictly in index order,
so 'xla', 'pallas' (interpret or compiled-CPU semantics) and 'scatter'
agree **bit for bit**, which keeps delta-modularity tie-breaks — and hence
whole Louvain partitions — identical across backends and equal to the
dense-scan twin (core/local_move.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.segsum import (
    BLOCK_GRANULE, cumsum_blocked, scan_identity, segscan_blocked,
)
from repro.kernels.spmm import bucket_spmm as _bucket_spmm_kernel
from repro.kernels.onehot_segsum import onehot_segsum as _onehot_segsum_kernel
from repro.telemetry.spans import scope


# kernel block rows when the caller pins none (kernels/autotune.py tunes it)
DEFAULT_BLOCK_M = 8192

# Cost of the end-mark placement per row, in rows gathered by one step of
# the boundary search: segreduce_sorted places by mark where
# num_segments * ceil(log2(m + 1)) > ENDS_MARK_RATIO * m.  Measured on a
# TPU v5e (scripts/ends_crossover.py): the search won where that product
# was at most 0.44 m, the mark where it was 0.74 m or more; the constant is
# their geometric mean.
ENDS_MARK_RATIO = 0.57


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl: str) -> str:
    """Resolve 'auto' to the backend-keyed policy: the XLA sorted-scatter
    path on CPU/GPU (no interpret-mode cost in production), the Pallas
    kernels on TPU (compiled, ``interpret=False``)."""
    if impl == "auto":
        return "pallas" if _on_tpu() else "xla"
    return impl


def _pad_rows(x, multiple):
    m = x.shape[0]
    pad = (-m) % multiple
    if pad == 0:
        return x, m
    pad_block = jnp.zeros((pad,) + x.shape[1:], x.dtype)
    return jnp.concatenate([x, pad_block], axis=0), m


def cumsum(x, *, impl: str = "auto", block_m: int = 1024):
    """Inclusive prefix sum along axis 0; x [M] or [M, D]."""
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    if impl == "xla" or (impl == "auto" and not _on_tpu()):
        out = ref.cumsum_ref(x)
    else:
        xp, m = _pad_rows(x, block_m)
        out = cumsum_blocked(xp, block_m=block_m, interpret=not _on_tpu())[: x.shape[0]]
    return out[:, 0] if squeeze else out


def segsum_sorted(values, segment_ids, num_segments, *, impl: str = "auto",
                  block_m: int = 1024):
    """Segment sum over sorted ids via the blocked-cumsum kernel.

    sum over segment s = prefix[end_s] - prefix[start_s]: two gathers of the
    kernel's output at boundaries found with searchsorted (no scatter).
    """
    if impl == "xla" or (impl == "auto" and not _on_tpu()):
        return ref.segsum_sorted_ref(values, segment_ids, num_segments)
    squeeze = values.ndim == 1
    v = values[:, None] if squeeze else values
    prefix = cumsum(v, impl=impl, block_m=block_m)
    zero = jnp.zeros((1, prefix.shape[1]), prefix.dtype)
    prefix = jnp.concatenate([zero, prefix], axis=0)          # [M+1, D]
    bounds = jnp.searchsorted(
        segment_ids, jnp.arange(num_segments + 1, dtype=segment_ids.dtype)
    )
    out = prefix[bounds[1:]] - prefix[bounds[:-1]]
    return (out[:, 0] if squeeze else out).astype(values.dtype)


def segreduce_sorted(values, ids, num_segments, *, op: str = "sum",
                     impl: str = "auto", block_m: int = 0):
    """Segment reduce (sum/max/min) over **sorted** segment ids.

    values: [M] or [M, D]; ids: int32[M], nondecreasing, in
    [0, num_segments).  Empty segments get the same fill values the
    ``jax.ops.segment_*`` family uses (0 / dtype-min / dtype-max).

    impl: 'auto' | 'xla' | 'pallas' | 'scatter' (see module docstring).
    block_m: Pallas kernel block rows, rounded up to whole
    ``BLOCK_GRANULE``s and down to the padded input; 0 = ``DEFAULT_BLOCK_M``
    (the service engine passes the per-bucket autotuned value —
    kernels/autotune.py).
    All impls are bit-identical (in-order fold contract).  Every impl
    runs under the device scope ``segreduce``, whatever phase calls it.
    The Pallas path then places each segment's fold, from the static
    shapes, by run-end mark (scope ``ends_mark``) or by binary search for
    the segment's end (``ends_search``): see ``ENDS_MARK_RATIO``.
    """
    with scope("segreduce"):
        return _segreduce_sorted(values, ids, num_segments, op,
                                 resolve_impl(impl), block_m)


def _segreduce_sorted(values, ids, num_segments, op, impl, block_m):
    if impl == "scatter":
        return ref.segreduce_sorted_ref(values, ids, num_segments, op=op,
                                        assume_sorted=False)
    if impl == "xla":
        return ref.segreduce_sorted_ref(values, ids, num_segments, op=op)
    squeeze = values.ndim == 1
    v = values[:, None] if squeeze else values
    m = v.shape[0]
    # whole granules, and no more of them than the padded input holds
    granules = -(-(block_m if block_m > 0 else DEFAULT_BLOCK_M)
                 // BLOCK_GRANULE)
    block_m = min(granules, max(-(-m // BLOCK_GRANULE), 1)) * BLOCK_GRANULE
    starts = jnp.concatenate([jnp.ones((1,), bool), ids[1:] != ids[:-1]]
                             ).astype(jnp.int32)
    # pad to a block multiple; padding rows start fresh runs of identity
    # values, so they can neither absorb nor leak a carry
    pad = (-m) % block_m
    ident = scan_identity(op, v.dtype)
    if pad:
        v = jnp.concatenate([v, jnp.full((pad, v.shape[1]), ident, v.dtype)])
        starts = jnp.concatenate([starts, jnp.ones((pad,), jnp.int32)])
    scanned = segscan_blocked(v, starts, op=op, block_m=block_m)[:m]
    # the running value at a segment's last row IS the segment's in-order
    # fold: place it with whichever finding of the last rows costs less
    if num_segments * m.bit_length() > ENDS_MARK_RATIO * m:
        with scope("ends_mark"):
            out = _ends_mark(ids, scanned, num_segments, ident)
    else:
        with scope("ends_search"):
            out = _ends_search(ids, scanned, num_segments, ident)
    return out[:, 0] if squeeze else out


def _ends_mark(ids, scanned, num_segments, ident):
    """Place the scan at each row that ends a run into its segment's slot
    of an ``ident``-filled ``[num_segments, D]`` buffer: one scatter of m
    values (a single channel), or of m row indices and then a gather of
    the rows (several channels)."""
    m, d = scanned.shape
    last = jnp.concatenate([ids[1:] != ids[:-1], jnp.ones((1,), bool)])
    # every other row goes to a slot of its own past the end, so the
    # indices are unique and those writes are dropped
    dest = jnp.where(last, ids,
                     num_segments + jnp.arange(m, dtype=ids.dtype))

    def place(fill, x):
        return jnp.full((num_segments,), fill, x.dtype).at[dest].set(
            x, mode="drop", unique_indices=True, wrap_negative_indices=False)

    if d == 1:
        return place(ident, scanned[:, 0])[:, None]
    # rows of several values scatter slowly: on a TPU v5e, 882,692 rows of
    # two took 41.9 ms, their row indices 6.1 ms and the gather 5.4 ms
    ends = place(m, jnp.arange(m, dtype=jnp.int32))
    return jnp.where((ends < m)[:, None], scanned[jnp.minimum(ends, m - 1)],
                     ident)


def _ends_search(ids, scanned, num_segments, ident):
    """Find each segment's last row by binary search over the sorted ids
    and gather the scan there: ``ceil(log2(m + 1))`` dependent gathers of
    ``num_segments`` rows."""
    m = ids.shape[0]
    seg = jnp.arange(num_segments, dtype=ids.dtype)
    ends = jnp.searchsorted(ids, seg, side="right").astype(jnp.int32) - 1
    present = (ends >= 0) & (ids[jnp.clip(ends, 0, m - 1)] == seg)
    return jnp.where(present[:, None],
                     scanned[jnp.clip(ends, 0, m - 1)], ident)


def spmm(nbr, w, x, *, impl: str = "auto", block_n: int = 64):
    """Fixed-degree neighbor aggregation out[i] = sum_k w[i,k] x[nbr[i,k]].

    Falls back to XLA gather when X exceeds the VMEM-resident envelope.
    """
    nx, d = x.shape
    fits = nx * d * 4 <= 8 * 1024 * 1024
    if impl == "xla" or (impl == "auto" and (not _on_tpu() or not fits)):
        return ref.bucket_spmm_ref(nbr, w, x)
    nbr_p, n = _pad_rows(nbr, block_n)
    w_p, _ = _pad_rows(w, block_n)
    out = _bucket_spmm_kernel(
        nbr_p, w_p, x.astype(jnp.float32),
        block_n=block_n, interpret=not _on_tpu(),
    )
    return out[:n].astype(x.dtype)


def segsum(values, ids, num_segments, *, impl: str = "auto", block_n: int = 512):
    """Unsorted segment sum; values [N] or [N, D], ids int32[N]."""
    squeeze = values.ndim == 1
    v = values[:, None] if squeeze else values
    fits = num_segments * v.shape[1] * 4 <= 8 * 1024 * 1024
    if impl == "xla" or (impl == "auto" and (not _on_tpu() or not fits)):
        out = ref.onehot_segsum_ref(v, ids, num_segments)
    else:
        v_p, n = _pad_rows(v, block_n)
        # pad ids to an out-of-range segment? No: clamp into range with zero
        # values (padding rows are zeros, any segment absorbs them safely).
        ids_p, _ = _pad_rows(ids, block_n)
        out = _onehot_segsum_kernel(
            v_p.astype(jnp.float32), ids_p,
            num_segments=num_segments, block_n=block_n,
            interpret=not _on_tpu(),
        ).astype(v.dtype)
    return out[:, 0] if squeeze else out


def flash_attention(q, k, v, *, causal=True, window=None, impl: str = "auto",
                    block_q: int = 128, block_k: int = 128):
    """Flash attention with GQA support.

    q: [B, Sq, Hq, Dh]; k, v: [B, Sk, Hkv, Dh] with Hq % Hkv == 0.
    Returns [B, Sq, Hq, Dh].
    """
    from repro.kernels.flash_attn import flash_attention_fwd

    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    # layout to [B, H, S, D]; repeat kv heads to the q-head count
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.repeat(jnp.transpose(k, (0, 2, 1, 3)), g, axis=1)
    vt = jnp.repeat(jnp.transpose(v, (0, 2, 1, 3)), g, axis=1)
    if impl == "xla" or (impl == "auto" and not _on_tpu()):
        out = ref.flash_attention_ref(qt, kt, vt, causal=causal, window=window)
    else:
        bq = min(block_q, sq)
        bk = min(block_k, kt.shape[2])
        pq = (-sq) % bq
        pk = (-kt.shape[2]) % bk
        qt2 = jnp.pad(qt, ((0, 0), (0, 0), (0, pq), (0, 0)))
        kt2 = jnp.pad(kt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vt2 = jnp.pad(vt, ((0, 0), (0, 0), (0, pk), (0, 0)))
        out = flash_attention_fwd(
            qt2, kt2, vt2, causal=causal, window=window,
            block_q=bq, block_k=bk, interpret=not _on_tpu(),
        )[:, :, :sq]
    return jnp.transpose(out, (0, 2, 1, 3))
