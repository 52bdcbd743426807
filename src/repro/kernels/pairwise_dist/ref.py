"""Pure-jnp oracle for the pairwise-distance / assignment kernels."""

from __future__ import annotations

import jax.numpy as jnp

from ..dispatch import MATMUL_PRECISION

__all__ = ["pairwise_sqdist_ref", "assign_min_ref"]


def pairwise_sqdist_ref(x, c):
    """Squared Euclidean distances.  x: (n, d), c: (k, d) → (n, k) f32.

    Uses the same ‖x‖² + ‖c‖² − 2·x·cᵀ decomposition as the kernel so that
    numerical behaviour matches (clamped at 0).
    """
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)  # (n, 1)
    c2 = jnp.sum(c * c, axis=1)[None, :]  # (1, k)
    d2 = x2 + c2 - 2.0 * jnp.matmul(x, c.T, precision=MATMUL_PRECISION)
    return jnp.maximum(d2, 0.0)


def assign_min_ref(x, c):
    """Fused nearest-center assignment.  Returns (idx (n,) i32, dist (n,) f32)."""
    d2 = pairwise_sqdist_ref(x, c)
    return jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.min(d2, axis=1)
