"""Recovery-weighted combining (Lemma 3) — the universal primitive.

Lemma 3 states that for an assignment with Property 1 and recovery vector
``b``, any additively-decomposable statistic ``F(P) = Σ_{p∈P} f(p)`` obeys

    F(P) ≤ Σ_{i∈R} b_i · F(P_i) ≤ (1+δ)·F(P)     (coordinate-wise for f ≥ 0,
                                                   exact band for any f when
                                                   the achieved a ≡ 1).

:func:`resilient_sum` applies the combine host-side to stacked per-node
statistics; :func:`resilient_map_sum` evaluates and combines the nodes one
at a time inside a compiled step; :func:`resilient_psum` is the SPMD
in-graph form (a weighted ``psum`` over a mesh axis); :func:`mom_combine`
is a byzantine-robust median-of-means alternative (paper §5 future-work
direction).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "resilient_sum", "resilient_map_sum", "resilient_psum", "mom_combine", "weighted_union",
]


def resilient_sum(per_node_stats: Any, b_full: np.ndarray) -> Any:
    """``Σ_i b_i · stat_i`` over a pytree whose leaves are stacked on axis 0.

    ``b_full`` has one weight per node (zero for stragglers), so straggler
    contributions vanish regardless of their (stale/garbage) content.
    """
    b = jnp.asarray(b_full)

    def combine(leaf):
        leaf = jnp.asarray(leaf)
        w = b.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)
        return jnp.sum(w * leaf, axis=0)

    return jax.tree_util.tree_map(combine, per_node_stats)


def resilient_map_sum(fn, b, node_args: Sequence[Any], bcast: Sequence[Any] = ()) -> Any:
    """``Σ_i b_i · fn(*node_args[..][i], *bcast)``, accumulated one node at a
    time (a ``lax.scan``) inside the caller's compiled step.

    A ``vmap`` followed by :func:`resilient_sum` would hold every node's
    output at once — for a train step one full gradient tree per group,
    which does not fit a chip's memory at published model widths.  The
    fused masked steps of both executors combine through this one helper.
    """
    one = jax.eval_shape(fn, *(a[0] for a in node_args), *bcast)
    acc0 = jax.tree_util.tree_map(lambda o: jnp.zeros(o.shape, o.dtype), one)

    def body(acc, xs):
        w, sl = xs
        out = fn(*sl, *bcast)
        return jax.tree_util.tree_map(lambda a, o: a + w.astype(o.dtype) * o, acc, out), None

    acc, _ = jax.lax.scan(body, acc0, (jnp.asarray(b), tuple(node_args)))
    return acc


def resilient_psum(x: Any, my_weight, axis_name: str) -> Any:
    """In-SPMD Lemma-3 combine: ``psum_i(b_i · x_i)`` over ``axis_name``.

    ``my_weight`` is this shard's recovery weight (a scalar traced value,
    typically sliced from a replicated ``(groups,)`` input by group index).
    Straggling shards contribute with weight 0 — the collective itself always
    runs (SPMD adaptation; see DESIGN.md §4.2).
    """
    return jax.tree_util.tree_map(
        lambda leaf: jax.lax.psum(leaf * jnp.asarray(my_weight, leaf.dtype), axis_name), x
    )


def mom_combine(per_node_stats: Any, num_groups: int = 5) -> Any:
    """Median-of-means combine (byzantine-robust aggregator, beyond paper).

    Splits the node axis round-robin into ``num_groups`` buckets (every row
    used, bucket sizes within 1 of each other), averages within buckets, takes
    the coordinate-wise median across buckets and rescales by the node count.
    Robust to a minority of arbitrarily-corrupted node statistics at the cost
    of the δ guarantee.
    """

    def combine(leaf):
        leaf = jnp.asarray(leaf)
        s = leaf.shape[0]
        g = max(1, min(num_groups, s))
        # Round-robin bucketing: when s % g != 0 the leftover rows are spread
        # across the first buckets (sizes differ by ≤ 1) instead of being
        # dropped — dropping them while still scaling by s biases the sum
        # estimate toward the surviving rows.
        gid = jnp.arange(s) % g
        sums = jax.ops.segment_sum(leaf.astype(jnp.float32), gid, num_segments=g)
        counts = (s // g) + (jnp.arange(g) < s % g).astype(jnp.float32)
        means = sums / counts.reshape((g,) + (1,) * (leaf.ndim - 1))
        # Result stays float (like the pre-fix code): casting back to an
        # integer leaf dtype would silently truncate fractional medians.
        return jnp.median(means, axis=0) * s

    return jax.tree_util.tree_map(combine, per_node_stats)


def weighted_union(
    point_sets: Sequence[np.ndarray],
    weight_sets: Sequence[np.ndarray],
    b: np.ndarray,
    alive: Optional[np.ndarray] = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Union of per-node weighted point sets with Lemma-3 reweighting.

    Used by Algorithms 1/2/3: node ``i`` contributes points ``point_sets[i]``
    with weights ``b_i · weight_sets[i]``.  ``alive`` selects contributing
    nodes (stragglers dropped).  Returns (points (m, d), weights (m,)).
    """
    pts, wts = [], []
    idx = range(len(point_sets)) if alive is None else np.flatnonzero(np.asarray(alive))
    for i in idx:
        if b[i] == 0.0 or len(point_sets[i]) == 0:
            continue
        pts.append(np.asarray(point_sets[i]))
        wts.append(float(b[i]) * np.asarray(weight_sets[i], dtype=np.float64))
    if not pts:
        raise ValueError("no surviving nodes with data — cannot form union")
    return np.concatenate(pts, axis=0), np.concatenate(wts, axis=0)
