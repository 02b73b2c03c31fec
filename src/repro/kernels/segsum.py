"""Pallas TPU kernels: blocked prefix-sum and the in-order segmented scan.

The paper's hot loops (local-move scoring, aggregation, LP label-min) are
all reduce-by-key over *sorted* runs.  A segment reduction over sorted
ids is a streaming **blocked scan** with a carry, then the scan output at
each run's last row placed in its segment's slot
(``ops.segreduce_sorted``).

Two scan kernels live here:

* :func:`cumsum_blocked` — plain blocked cumsum (unsegmented; the original
  ``ops.segsum_sorted`` prefix-difference formulation rides on it).  The
  TPU compiler does not lower its ``cumsum``; it is off the main path.
* :func:`segscan_blocked` — segmented running reduce (sum/max/min) whose
  carry **resets at run starts** and whose additions apply strictly in
  index order.  The in-order guarantee is the load-bearing contract: the
  Louvain core's run sums must be bit-identical across every backend
  (sortscan XLA scatter, dense scatter-add, this kernel) because
  ulp-level differences flip delta-modularity tie-breaks and hence
  partitions (core/local_move.py's dense/sort equivalence).  Exactness is
  bought with a strictly sequential fold, one element per step on the
  scalar unit over blocks staged in scalar memory (SMEM); widening the
  in-order window to a vector layout is a later tuning step (ROADMAP),
  which has to keep or explicitly relax in-orderness.

Grid steps on TPU execute sequentially on a core, so the carry lives in
a scratch buffer that persists across steps (the flash-attention
accumulator pattern).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _default_interpret(interpret):
    """Resolve ``interpret=None`` from the backend at call time.

    Callers used to be responsible for passing ``interpret=not _on_tpu()``;
    forgetting it silently ran interpret-mode Pallas in production paths.
    ``None`` now means "compiled on TPU, emulated elsewhere"."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _cumsum_kernel(x_ref, o_ref, carry_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = x_ref[...].astype(jnp.float32)
    c = jnp.cumsum(x, axis=0)
    o_ref[...] = (c + carry_ref[...]).astype(o_ref.dtype)
    carry_ref[...] = carry_ref[...] + c[-1:, :]


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def cumsum_blocked(x, *, block_m: int = 1024, interpret: bool | None = None):
    """Inclusive prefix sum along axis 0 of ``x [M, D]`` (f32 accumulate).

    M must be a multiple of ``block_m`` (ops.py pads).  ``interpret=None``
    resolves from the backend (compiled on TPU, emulated elsewhere)."""
    m, d = x.shape
    assert m % block_m == 0, (m, block_m)
    grid = (m // block_m,)
    return pl.pallas_call(
        _cumsum_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_m, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, d), jnp.float32)],
        interpret=_default_interpret(interpret),
    )(x)


_SCAN_OPS = {
    "sum": jnp.add,
    "max": jnp.maximum,
    "min": jnp.minimum,
}


def scan_identity(op: str, dtype):
    """Identity element of ``op`` for ``dtype`` — also the empty-segment
    fill ``jax.ops.segment_{sum,max,min}`` uses, which the boundary gather
    in ops.py must reproduce for bit parity with the XLA path."""
    if op == "sum":
        return jnp.zeros((), dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        inf = jnp.array(jnp.inf, dtype)
        return -inf if op == "max" else inf
    info = jnp.iinfo(dtype)
    return jnp.array(info.min if op == "max" else info.max, dtype)


def _segscan_kernel(starts_ref, x_ref, o_ref, carry_ref, *, op):
    d, block_m = x_ref.shape
    ident = scan_identity(op, x_ref.dtype)
    combine = _SCAN_OPS[op]

    # one element per step on the scalar unit: the strict in-order fold.
    # Every sequence's first element starts a run, so the carry read
    # before it is discarded — no per-sequence initialisation needed.
    def body(i, carry):
        fresh = starts_ref[0, i] != 0
        out = []
        for c in range(d):
            v = combine(jnp.where(fresh, ident, carry[c]), x_ref[c, i])
            o_ref[c, i] = v
            out.append(v)
        return tuple(out)

    carry = jax.lax.fori_loop(0, block_m, body,
                              tuple(carry_ref[c] for c in range(d)))
    for c in range(d):
        carry_ref[c] = carry[c]


# TPU tiles a 1-D 32-bit array in runs of 1024 elements, and a kernel block
# of it must cover whole runs
BLOCK_GRANULE = 1024


def _segscan_call(xt, starts, *, op, block_m, interpret):
    """xt: [..., D, M]; starts: int32[..., 1, M].  Leading (batch) dims
    become outer grid axes; blocks walk each sequence in order."""
    *lead, d, m = xt.shape
    nb = len(lead)
    squeezed = (pl.Squeezed(),) * nb

    def index(*g):
        return (*g[:nb], 0, g[nb])

    smem = pltpu.SMEM
    return pl.pallas_call(
        functools.partial(_segscan_kernel, op=op),
        grid=(*lead, m // block_m),
        in_specs=[
            pl.BlockSpec((*squeezed, 1, block_m), index, memory_space=smem),
            pl.BlockSpec((*squeezed, d, block_m), index, memory_space=smem),
        ],
        out_specs=pl.BlockSpec((*squeezed, d, block_m), index,
                               memory_space=smem),
        out_shape=jax.ShapeDtypeStruct(xt.shape, xt.dtype),
        scratch_shapes=[smem((d,), xt.dtype)],
        interpret=interpret,
    )(starts, xt)


@functools.lru_cache(maxsize=None)
def _segscan_fn(op, block_m, interpret):
    @jax.custom_batching.custom_vmap
    def scan(xt, starts):
        return _segscan_call(xt, starts, op=op, block_m=block_m,
                             interpret=interpret)

    @scan.def_vmap
    def _batched(axis_size, in_batched, xt, starts):
        # the batch becomes a leading grid axis of the same kernel
        if not in_batched[0]:
            xt = jnp.broadcast_to(xt, (axis_size,) + xt.shape)
        if not in_batched[1]:
            starts = jnp.broadcast_to(starts, (axis_size,) + starts.shape)
        return scan(xt, starts), True

    return scan


@functools.partial(jax.jit, static_argnames=("op", "block_m", "interpret"))
def segscan_blocked(x, starts, *, op: str = "sum", block_m: int = 8192,
                    interpret: bool | None = None):
    """Segmented running reduce along axis 0: ``out[i] = fold(op, run(i))``
    over the elements of i's run up to and including i, folded strictly in
    index order (see module docstring for why in-orderness is load-
    bearing).

    x: [M, D] of a 32-bit dtype; starts: int32[M], nonzero at the first
    element of each run (block boundaries need no special casing — the
    carry persists in scratch across grid steps and resets exactly where
    ``starts`` says; element 0 always starts a run).  M must be a
    multiple of ``block_m``; compiled on TPU, ``block_m`` must also be a
    multiple of :data:`BLOCK_GRANULE` (ops.py pads and rounds; padding
    rows must have ``starts=1`` so they cannot leak a carry into real
    data).

    The fold runs on the scalar unit over blocks in scalar memory (SMEM),
    channel-major (``[D, M]``): each 1-D channel stays dense in HBM, where
    an ``[M, D]`` array with D of 1 or 2 would be padded to 128 lanes.
    Under ``vmap`` the batch becomes an outer grid axis.
    """
    m, d = x.shape
    assert m % block_m == 0, (m, block_m)
    assert starts.shape == (m,), (starts.shape, m)
    scan = _segscan_fn(op, block_m, _default_interpret(interpret))
    return scan(x.T, starts.at[0].set(1)[None, :]).T
