"""sift128_k512: data from the seed, and the plain reference of what the
deployment computes.

The reference is written from the paper's Algorithm 1 (arXiv:2002.08892,
§3.2) and imports nothing of the program.  It follows the same seeded draws
(one key per worker split from the solve's seed; k-median++ d-sampling by
``jax.random.categorical``; the coordinator's key from seed + 1), so a sound
program and the reference walk the same trajectory unless an f32 near-tie
parts them.  Every distance is computed at matmul precision ``highest`` (or,
for the control, ``high``), each worker's solve as its own program: a
vmapped XLA argmin is miscompiled on the chip at these sizes (PERF.md, Open
questions).  The checks of what a solve returns (cluster sizes, cost)
recompute it in float64 on the host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
_EPS = 1e-12
_WEISZFELD = 4


# --------------------------------------------------------------------- data


@functools.partial(jax.jit, static_argnames=("count", "n", "d", "planted"))
def _mixtures(key, *, count: int, n: int, d: int, planted: int, scale, noise):
    """``count`` datasets, each n points around ``planted`` centers."""

    def one(k):
        kc, kl, kn = jax.random.split(k, 3)
        centers = jax.random.normal(kc, (planted, d), jnp.float32) * scale
        labels = jax.random.randint(kl, (n,), 0, planted)
        return centers[labels] + jax.random.normal(kn, (n, d), jnp.float32) * noise

    return jax.vmap(one)(jax.random.split(key, count))


def make_datasets(seed: int, *, count: int, n: int, d: int, planted: int,
                  scale: float, noise: float) -> list:
    """Host copies of ``count`` seeded mixtures, made on the device in one
    call."""
    arr = _mixtures(
        jax.random.PRNGKey(seed), count=count, n=n, d=d, planted=planted,
        scale=jnp.float32(scale), noise=jnp.float32(noise),
    )
    host = np.asarray(jax.device_get(arr))
    del arr
    return [np.ascontiguousarray(host[i]) for i in range(count)]


# ---------------------------------------------------------------- placement


def cyclic_rows(n: int, s: int, ell: int) -> list:
    """Rows held by each worker: point j lives on workers j, j+1, …,
    j+ell−1 (mod s), in increasing order of j."""
    j = np.arange(n)
    return [
        np.flatnonzero(((j[:, None] + np.arange(ell)[None, :]) % s == i).any(axis=1))
        for i in range(s)
    ]


def shard_types(s: int, ell: int) -> np.ndarray:
    """(s, s) 0/1: column t is the holder set of every point j ≡ t (mod s)."""
    M = np.zeros((s, s), dtype=np.float64)
    for t in range(s):
        for u in range(ell):
            M[(t + u) % s, t] = 1.0
    return M


def min_delta_recovery(alive: np.ndarray, s: int, ell: int) -> tuple:
    """Least-δ recovery weights: min z s.t. 1 ≤ Σ_{i∋j} b_i ≤ z over every
    shard j with an alive holder, b ≥ 0, b = 0 off the alive set.  Returns
    (b_full (s,), delta, covered (s,) bool over shard types)."""
    from scipy.optimize import linprog

    alive = np.asarray(alive, bool)
    M = shard_types(s, ell)
    idx = np.flatnonzero(alive)
    MR = M[idx]
    covered = MR.sum(axis=0) > 0
    b_full = np.zeros(s)
    if not covered.any():
        return b_full, float("inf"), covered
    # One constraint pair per distinct holder set, in a fixed order.
    packed = np.packbits(MR[:, covered] > 0, axis=0)
    keys = np.ascontiguousarray(packed.T).view(np.dtype((np.void, packed.shape[0])))
    _, first = np.unique(keys.ravel(), return_index=True)
    Ac = MR[:, np.flatnonzero(covered)[first]]
    r, m = Ac.shape
    c = np.zeros(r + 1)
    c[-1] = 1.0
    A_ub = np.zeros((2 * m, r + 1))
    A_ub[:m, :r] = -Ac.T
    A_ub[m:, :r] = Ac.T
    A_ub[m:, r] = -1.0
    b_ub = np.concatenate([-np.ones(m), np.zeros(m)])
    res = linprog(c, A_ub=A_ub, b_ub=b_ub, bounds=[(0, None)] * r + [(1.0, None)],
                  method="highs")
    if not res.success:
        raise RuntimeError(f"reference recovery LP failed: {res.message}")
    b_full[idx] = np.maximum(res.x[:r], 0.0)
    a = b_full @ M
    return b_full, float(a[covered].max() - 1.0), covered


def recovery_weights(alive: np.ndarray, *, s: int, ell: int,
                     ignore_stragglers: bool = False) -> np.ndarray:
    """Each worker's recovery weight b (s,): the least-δ weights, or with
    ``ignore_stragglers`` weight 1 on every alive worker (the paper's Fig.
    1(b) baseline, which breaks the recovery guarantee)."""
    if ignore_stragglers:
        return np.asarray(alive, bool).astype(np.float64)
    return min_delta_recovery(alive, s, ell)[0]


def coverage_gap(summary_weights: np.ndarray, alive: np.ndarray, *, s: int, ell: int,
                 k: int, rows_per_node: int) -> float:
    """How far the weights a solve gave the coordinator fall outside the
    recovery band.  Worker i's center weights sum to b_i times its row
    count, so b_i is read back from them."""
    W = np.asarray(summary_weights, np.float64).reshape(s, k).sum(axis=1)
    return band_gap(W / rows_per_node, alive, s=s, ell=ell)


def band_gap(b: np.ndarray, alive: np.ndarray, *, s: int, ell: int) -> float:
    """How far recovery weights b fall outside the recovery band: every
    covered shard's total Σ_{i∋j} b_i must lie in [1, 1+δ*]."""
    _, delta, covered = min_delta_recovery(alive, s, ell)
    if not covered.any():
        return 0.0
    a = b @ shard_types(s, ell)
    a = a[covered]
    return float(np.max(np.maximum(np.maximum(1.0 - a, a - (1.0 + delta)), 0.0)))


# ---------------------------------------------------------------- reference


def dot(a, b, precision: str = "highest"):
    """a @ b at matmul precision ``highest`` (float32), or as precision
    ``high`` computes it: each operand split into a bfloat16 head and tail,
    the tail-by-tail product dropped.  The split rounds with
    ``reduce_precision``, which no compiler folds away, and each product of
    bfloat16 values is exact in float32, so ``high`` reads the same on the
    chip and on the CPU."""
    if precision == "highest":
        return jnp.dot(a, b, precision=HI)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")

    def split(v):
        head = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        return head, jax.lax.reduce_precision(v - head, exponent_bits=8, mantissa_bits=7)

    ah, al = split(a)
    bh, bl = split(b)
    return (jnp.dot(ah, bh, precision=HI) + jnp.dot(ah, bl, precision=HI)
            + jnp.dot(al, bh, precision=HI))


def _sqdist(x, x2, c, precision="highest"):
    return x2[:, None] + jnp.sum(c * c, axis=1)[None, :] - 2.0 * dot(x, c.T, precision)


def _nearest(x, x2, c, precision="highest"):
    d2 = _sqdist(x, x2, c, precision)
    idx = jnp.argmin(d2, axis=1)
    return idx, jnp.take_along_axis(d2, idx[:, None], axis=1)[:, 0]


def _logits(w, score):
    return jnp.where(w > 0, jnp.log(jnp.maximum(w * score, _EPS)), -jnp.inf)


@functools.partial(jax.jit, static_argnames=("k", "iters", "precision"))
def ref_lloyd(key, x, w, *, k: int, iters: int, precision: str = "highest"):
    """Weighted k-median: k-median++ seeding, then ``iters`` Lloyd rounds of
    nearest-center assignment and Weiszfeld steps.  Returns (centers,
    cluster weights Σw, cost Σ w·d)."""
    n, d = x.shape
    x2 = jnp.sum(x * x, axis=1)
    key0, key = jax.random.split(key)
    first = jax.random.categorical(key0, _logits(w, jnp.ones_like(w)))
    centers = jnp.broadcast_to(x[first][None, :], (k, d))

    def d2_to(c):
        return x2 + jnp.dot(c, c, precision=HI) - 2.0 * dot(x, c, precision)

    def seed_step(i, carry):
        centers, mind, key = carry
        key, sub = jax.random.split(key)
        nxt = jax.random.categorical(sub, _logits(w, jnp.sqrt(jnp.maximum(mind, 0.0))))
        c = x[nxt]
        return centers.at[i].set(c), jnp.minimum(mind, d2_to(c)), key

    centers, _, _ = jax.lax.fori_loop(1, k, seed_step, (centers, d2_to(x[first]), key))

    def weiszfeld(idx, c):
        def body(_, c):
            dist = jnp.sqrt(jnp.maximum(jnp.sum((x - c[idx]) ** 2, axis=1), _EPS))
            inv = w / dist
            sums = jax.ops.segment_sum(inv[:, None] * x, idx, num_segments=k)
            tot = jax.ops.segment_sum(inv, idx, num_segments=k)
            new = sums / jnp.maximum(tot, _EPS)[:, None]
            return jnp.where((tot > _EPS)[:, None], new, c)

        return jax.lax.fori_loop(0, _WEISZFELD, body, c)

    def lloyd_step(_, c):
        idx, _ = _nearest(x, x2, c, precision)
        return weiszfeld(idx, c)

    centers = jax.lax.fori_loop(0, iters, lloyd_step, centers)
    idx, d2 = _nearest(x, x2, centers, precision)
    tot = jax.ops.segment_sum(w, idx, num_segments=k)
    return centers, tot, jnp.sum(w * jnp.sqrt(jnp.maximum(d2, 0.0)))


@functools.partial(jax.jit, static_argnames=("precision",))
def _nearest_block(q, centers, precision="highest"):
    return _nearest(q, jnp.sum(q * q, axis=1), centers, precision)


COST_BLOCK = 32768


def _cost(points: np.ndarray, centers: np.ndarray, precision: str) -> float:
    """Σ over the points of the distance to the nearest center, as the
    solve computes it, in blocks of rows."""
    c = jnp.asarray(centers, jnp.float32)
    total = 0.0
    for lo in range(0, len(points), COST_BLOCK):
        _, d2 = _nearest_block(jnp.asarray(points[lo:lo + COST_BLOCK]), c, precision)
        total += float(jnp.sum(jnp.sqrt(jnp.maximum(d2, 0.0))))
    return total


def ref_kmedian(points: np.ndarray, alive: np.ndarray, *, k: int, s: int, ell: int,
                local_iters: int, coord_iters: int, seed: int,
                ignore_stragglers: bool = False, precision: str = "highest") -> dict:
    """Algorithm 1 end to end: each alive worker's local k-median, the
    recovery-weighted union, the coordinator's k-median, its objective and
    the cost over all points.

    ``precision="high"`` computes every distance one step below what the
    deployment states (the control); ``ignore_stragglers`` breaks the
    recovery guarantee (weights 1 on every alive worker, the paper's Fig.
    1(b) baseline).
    """
    alive = np.asarray(alive, bool)
    d = points.shape[1]
    b = recovery_weights(alive, s=s, ell=ell, ignore_stragglers=ignore_stragglers)
    rows = cyclic_rows(len(points), s, ell)
    keys = jax.random.split(jax.random.PRNGKey(seed), s)
    y = np.zeros((s, k, d), np.float32)
    wy = np.zeros((s, k), np.float32)
    for i in np.flatnonzero(alive):
        x = jnp.asarray(points[rows[i]])
        c, tot, _ = ref_lloyd(keys[i], x, jnp.ones((x.shape[0],), jnp.float32),
                              k=k, iters=local_iters, precision=precision)
        y[i] = np.asarray(c)
        wy[i] = np.float32(b[i]) * np.asarray(tot)
    centers, _, _ = ref_lloyd(
        jax.random.PRNGKey(seed + 1), jnp.asarray(y.reshape(s * k, d)),
        jnp.asarray(wy.reshape(s * k)), k=k, iters=coord_iters, precision=precision,
    )
    centers = np.asarray(centers)
    y, wy = y.reshape(s * k, d), wy.reshape(s * k)
    return {
        "centers": centers,
        "summary_points": y,
        "summary_weights": wy,
        "objective": coordinator_objective(y, wy, centers),
        "cost": _cost(points, centers, precision),
    }


def coordinator_objective(y: np.ndarray, wy: np.ndarray, centers: np.ndarray) -> float:
    """The coordinator's weighted k-median objective at its centers:
    Σ_c w(c)·d(c, centers) over the union of the workers' weighted centers."""
    _, d2 = _nearest_block(jnp.asarray(y, jnp.float32), jnp.asarray(centers, jnp.float32))
    return float(np.sum(np.asarray(wy, np.float64) * np.sqrt(np.maximum(np.asarray(d2, np.float64), 0.0))))


# -------------------------------------------------- checks in float64


F64_BLOCK = 16384


def nearest_f64(points: np.ndarray, centers: np.ndarray) -> tuple:
    """Nearest center of every row: (indices, distances), in float64 on the
    host, in blocks of rows."""
    c = np.asarray(centers, np.float64)
    c2 = (c * c).sum(1)
    idx = np.empty(len(points), np.int64)
    dist = np.empty(len(points), np.float64)
    for lo in range(0, len(points), F64_BLOCK):
        q = np.asarray(points[lo:lo + F64_BLOCK], np.float64)
        d2 = (q * q).sum(1)[:, None] + c2[None, :] - 2.0 * q @ c.T
        j = np.argmin(d2, axis=1)
        idx[lo:lo + len(q)] = j
        dist[lo:lo + len(q)] = np.sqrt(np.maximum(d2[np.arange(len(q)), j], 0.0))
    return idx, dist


def recost_gap(points: np.ndarray, centers: np.ndarray, cost: float) -> float:
    """How far a reported cost lies from Σ over all points of the distance
    to the nearest returned center (float64), relative to it."""
    want = float(nearest_f64(points, centers)[1].sum())
    return abs(float(cost) - want) / want


def size_gap(points: np.ndarray, summary_points: np.ndarray, summary_weights: np.ndarray,
             *, s: int, ell: int, k: int) -> float:
    """The worst alive worker's share of rows counted to a center that is
    not their nearest: the cluster sizes read back from the weights the
    worker gave the coordinator (weight / b_i, b_i read back from their
    sum) against the sizes of its own rows at its own centers (float64):
    Σ_c |size(c) − size_f64(c)| / (2 · rows)."""
    rows = cyclic_rows(len(points), s, ell)
    W = np.asarray(summary_weights, np.float64).reshape(s, k)
    Y = np.asarray(summary_points).reshape(s, k, -1)
    worst = 0.0
    for i in range(s):
        m = len(rows[i])
        b = W[i].sum() / m
        if b <= 0:
            continue  # a straggler gives the coordinator nothing
        got = np.rint(W[i] / b)
        idx, _ = nearest_f64(points[rows[i]], Y[i])
        want = np.bincount(idx, minlength=k)
        worst = max(worst, float(np.abs(got - want).sum()) / (2 * m))
    return worst


# ------------------------------------------------------------------ serving


def ref_nearest(queries: np.ndarray, centers: np.ndarray) -> tuple:
    """Nearest center of every query row: (indices, squared distances),
    computed in float64 on the host."""
    q = np.asarray(queries, np.float64)
    c = np.asarray(centers, np.float64)
    d2 = (q * q).sum(1)[:, None] + (c * c).sum(1)[None, :] - 2.0 * q @ c.T
    idx = np.argmin(d2, axis=1)
    return idx, np.maximum(d2[np.arange(len(q)), idx], 0.0)
