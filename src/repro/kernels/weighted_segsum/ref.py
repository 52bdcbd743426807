"""Pure-jnp oracle for the weighted segment-sum (centroid update) kernel."""

from __future__ import annotations

import jax.numpy as jnp

from ..dispatch import MATMUL_PRECISION

__all__ = ["weighted_segsum_ref"]


def weighted_segsum_ref(x, w, idx, k: int):
    """Weighted per-cluster sums.

    x: (n, d), w: (n,) weights, idx: (n,) i32 cluster ids in [0, k).
    Returns (sums (k, d) f32, totals (k,) f32):
        sums[c]   = Σ_{i: idx_i = c} w_i · x_i
        totals[c] = Σ_{i: idx_i = c} w_i
    """
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    oh = (idx[:, None] == jnp.arange(k)[None, :]).astype(jnp.float32)  # (n, k)
    oh = oh * w[:, None]
    return jnp.matmul(oh.T, x, precision=MATMUL_PRECISION), jnp.sum(oh, axis=0)
