"""Local move returns its state unchanged: every vertex stays in its own
community (``repro.core.louvain.local_move``, the detection path's)."""
import importlib

from harness.faults import patched


def stale_local_move(src, dst, w, C0, K, Sigma0, two_m, **_):
    import jax.numpy as jnp

    return C0, Sigma0, jnp.int32(1)


def plant():
    louvain = importlib.import_module("repro.core.louvain")
    return patched(louvain, "local_move", stale_local_move)
