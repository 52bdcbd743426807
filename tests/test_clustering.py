"""End-to-end tests for the paper's Algorithms 1–3 and the coreset layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="property tests need the optional hypothesis dep")
from hypothesis import given, settings, strategies as st

from repro.core import (
    bernoulli_assignment,
    centralized_pca,
    clustering_cost,
    fixed_count_stragglers,
    fractional_repetition_assignment,
    ignore_stragglers_kmedian,
    lloyd,
    lloyd_subspace,
    pca_cost,
    plusplus_init,
    relaxed_coreset_rank,
    resilient_kmedian,
    resilient_pca,
    resilient_subspace_clustering,
    sensitivity_coreset,
    singleton_assignment,
    subspace_cost,
    uniform_coreset,
)
from repro.data.synthetic import franti_s1_like, gaussian_mixture, planted_subspaces


@pytest.fixture(scope="module")
def s1():
    return franti_s1_like(1500)


def test_lloyd_kmeans_recovers_planted_clusters():
    pts, centers, _ = gaussian_mixture(800, 6, 4, spread=0.02, rng=np.random.default_rng(1))
    res = lloyd(jax.random.PRNGKey(0), jnp.asarray(pts), 6, iters=25)
    # Every found center is near a planted center.
    d = np.sqrt(((np.asarray(res.centers)[:, None] - centers[None]) ** 2).sum(-1)).min(1)
    assert (d < 0.15).all()
    assert np.isfinite(float(res.cost))


def test_lloyd_weighted_ignores_zero_weight_padding():
    pts, _, _ = gaussian_mixture(400, 4, 3, rng=np.random.default_rng(2))
    padded = np.concatenate([pts, np.full((100, 3), 1e6, np.float32)])
    w = np.concatenate([np.ones(400), np.zeros(100)]).astype(np.float32)
    res_pad = lloyd(
        jax.random.PRNGKey(3), jnp.asarray(padded), 4, weights=jnp.asarray(w), iters=15
    )
    # Padded garbage points must not attract centers.
    assert np.abs(np.asarray(res_pad.centers)).max() < 100.0


def _full_recompute_plusplus(key, x, k, w, median):
    """Reference seeding: every step re-assigns every row against
    all k slots (unchosen slots hold the first point) with ``assign_min``."""
    from repro.kernels.pairwise_dist.ops import assign_min

    d = x.shape[1]

    def logits_of(score):
        return jnp.where(w > 0, jnp.log(jnp.maximum(w * score, 1e-12)), -jnp.inf)

    key0, key = jax.random.split(key)
    first = jax.random.categorical(key0, logits_of(jnp.ones_like(w)))
    centers0 = jnp.broadcast_to(x[first][None, :], (k, d))

    def body(i, carry):
        centers, key = carry
        key, sub = jax.random.split(key)
        _, d2 = assign_min(x, centers, impl="xla_ref")
        score = d2 if not median else jnp.sqrt(jnp.maximum(d2, 0.0))
        nxt = jax.random.categorical(sub, logits_of(score))
        return centers.at[i].set(x[nxt]), key

    return jax.lax.fori_loop(1, k, body, (centers0, key))[0]


@pytest.mark.parametrize("median", [True, False], ids=["median", "means"])
@pytest.mark.parametrize("padded", [False, True], ids=["dense", "zero_weight_pad"])
def test_incremental_seeding_draws_the_full_recompute_rows(median, padded):
    k = 8
    pts, _, _ = gaussian_mixture(
        600, k, 6, spread=0.01, box=10.0, rng=np.random.default_rng(11)
    )
    w = np.ones(len(pts), np.float32)
    if padded:  # far-off rows that a nonzero weight would make certain picks
        pts = np.concatenate([pts, np.full((40, 6), 1e3, np.float32)])
        w = np.concatenate([w, np.zeros(40, np.float32)])
    x, wj = jnp.asarray(pts, jnp.float32), jnp.asarray(w)
    for seed in range(4):
        key = jax.random.PRNGKey(seed)
        new = np.asarray(plusplus_init(key, x, k, weights=wj, median=median))
        old = np.asarray(_full_recompute_plusplus(key, x, k, wj, median))
        np.testing.assert_array_equal(new, old)
        if padded:
            assert np.abs(new).max() < 100.0


def test_seeding_makes_no_assign_min_call(monkeypatch):
    from repro.kernels.pairwise_dist import ops as pd

    def refuse(*args, **kwargs):
        raise AssertionError("seeding called assign_min")

    monkeypatch.setattr(pd, "assign_min", refuse)
    # A shape no other test seeds at, so no cached trace can hide a call.
    x = jnp.asarray(np.random.default_rng(5).normal(size=(37, 3)), jnp.float32)
    centers = plusplus_init(jax.random.PRNGKey(9), x, 5, median=True)
    assert centers.shape == (5, 3)
    assert np.isin(np.asarray(centers), np.asarray(x)).all(axis=None)


def test_kmedian_cost_uses_unsquared_distance():
    pts = np.array([[0.0, 0.0], [2.0, 0.0]], np.float32)
    c = jnp.asarray([[0.0, 0.0]], jnp.float32)
    assert float(clustering_cost(jnp.asarray(pts), c, median=True)) == pytest.approx(2.0)
    assert float(clustering_cost(jnp.asarray(pts), c, median=False)) == pytest.approx(4.0)


def test_algorithm1_beats_ignoring_stragglers(s1):
    pts, _, _ = s1
    rng = np.random.default_rng(0)
    s, t, k = 10, 3, 15
    alive = fixed_count_stragglers(s, t, rng)
    central = lloyd(jax.random.PRNGKey(0), jnp.asarray(pts), k, iters=30, median=True)
    redundant = bernoulli_assignment(len(pts), s, ell=2.0, rng=rng)
    out_res = resilient_kmedian(pts, k, redundant, alive, local_iters=10, coord_iters=25)
    out_ign = ignore_stragglers_kmedian(
        pts, k, singleton_assignment(len(pts), s), alive, local_iters=10, coord_iters=25
    )
    c_central = float(central.cost)
    # Theorem 3 bound with the achieved delta (generous empirical slack).
    assert out_res.cost <= 3.0 * (1.0 + out_res.recovery.delta) * c_central
    # Redundancy must not be worse than ignoring stragglers (paper Fig 1).
    assert out_res.cost <= out_ign.cost * 1.05


def test_algorithm1_fr_assignment_exact_band(s1):
    pts, _, _ = s1
    a = fractional_repetition_assignment(len(pts), 12, 3)
    alive = fixed_count_stragglers(12, 2, np.random.default_rng(5))
    out = resilient_kmedian(pts, 15, a, alive, local_iters=8, coord_iters=20)
    assert out.recovery.feasible
    assert out.recovery.delta <= 1e-6  # FR: exact recovery band


def test_sensitivity_coreset_epsilon_band():
    pts, _, _ = gaussian_mixture(2000, 5, 4, rng=np.random.default_rng(3))
    x = jnp.asarray(pts)
    cs = sensitivity_coreset(jax.random.PRNGKey(0), x, k=5, m=500)
    rng = np.random.default_rng(4)
    # ε-coreset property over random center sets (empirical band).
    for _ in range(5):
        C = jnp.asarray(rng.normal(size=(5, 4)), jnp.float32)
        full = float(clustering_cost(x, C))
        approx = float(clustering_cost(cs.points, C, weights=cs.weights))
        assert abs(approx - full) / full < 0.35
    # Total weight approximates n.
    assert float(cs.weights.sum()) == pytest.approx(2000, rel=0.3)


def test_uniform_coreset_weight_normalization():
    pts, _, _ = gaussian_mixture(1000, 3, 2, rng=np.random.default_rng(6))
    cs = uniform_coreset(jax.random.PRNGKey(1), jnp.asarray(pts), 200)
    assert float(cs.weights.sum()) == pytest.approx(1000, rel=0.25)


def test_algorithm2_subspace_clustering_quality():
    pts, _ = planted_subspaces(900, 3, 8, 2, noise=0.01, rng=np.random.default_rng(7))
    a = bernoulli_assignment(len(pts), 8, ell=3.0, rng=np.random.default_rng(8))
    alive = fixed_count_stragglers(8, 2, np.random.default_rng(9))
    out = resilient_subspace_clustering(pts, 2, 3, a, alive, coreset_size=256)
    central = lloyd_subspace(jax.random.PRNGKey(2), jnp.asarray(pts), 3, 2)
    # Theorem 4: within alpha(1+8delta) of optimal; empirically compare to the
    # same solver run centrally, with generous slack for coreset noise.
    assert out.cost <= max(5.0 * float(central.cost), float(central.cost) + 2.0)


def test_algorithm2_r0_reduces_to_kmeans():
    pts, _, _ = gaussian_mixture(600, 4, 5, rng=np.random.default_rng(10))
    sol = lloyd_subspace(jax.random.PRNGKey(0), jnp.asarray(pts), 4, 0)
    km = lloyd(jax.random.PRNGKey(0), jnp.asarray(pts), 4, iters=15)
    assert float(sol.cost) <= 1.5 * float(km.cost) + 1e-3


def test_relaxed_coreset_rank_formula():
    assert relaxed_coreset_rank(5, 1.0) == 9  # r + r/δ − 1
    assert relaxed_coreset_rank(2, 0.5) == 5
    assert relaxed_coreset_rank(1, 0.25) == 4


def test_algorithm3_pca_theorem5_band():
    pts, _ = planted_subspaces(800, 1, 24, 4, noise=0.05, rng=np.random.default_rng(11))
    pts = pts - pts.mean(0, keepdims=True)
    delta = 0.25
    # ell high enough that every shard keeps a live replica after t=3 of 10
    # nodes straggle (P[shard uncovered] = (1−p_a)^7 ≈ 1e-5 at p_a = 0.8).
    a = bernoulli_assignment(len(pts), 10, ell=8.0, rng=np.random.default_rng(12))
    alive = fixed_count_stragglers(10, 3, np.random.default_rng(13))
    out = resilient_pca(pts, 4, delta, a, alive)
    opt = float(pca_cost(jnp.asarray(pts), centralized_pca(jnp.asarray(pts), 4)))
    assert out.recovery.feasible
    # Theorem 5: cost ≤ (1+4δ)·OPT — with the achieved (LP) delta.
    band = 1.0 + 4.0 * max(delta, out.recovery.delta)
    assert out.cost <= band * opt * 1.05 + 1e-6
    # Communication is r1·|R| rows, independent of n.
    assert out.sketch_rows <= out.r1 * int(alive.sum())


def test_algorithm3_pca_exact_when_no_stragglers():
    pts, _ = planted_subspaces(500, 1, 16, 3, noise=0.0, rng=np.random.default_rng(14))
    pts = pts - pts.mean(0, keepdims=True)  # linear PCA; remove affine offset
    a = fractional_repetition_assignment(len(pts), 8, 2)
    out = resilient_pca(pts, 3, 0.5, a, np.ones(8, dtype=bool))
    # Noise-free planted subspace: residual ≈ 0.
    assert out.cost <= 1e-3 * float(jnp.sum(jnp.asarray(pts) ** 2))


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(min_value=0, max_value=1000))
def test_resilient_kmedian_never_catastrophic(seed):
    """Property: under the Theorem-6 regime the resilient cost is bounded by a
    modest multiple of the centralized heuristic — never the unbounded blowup
    the ignore-stragglers scheme exhibits when clusters are dropped."""
    rng = np.random.default_rng(seed)
    pts, _, _ = gaussian_mixture(600, 8, 2, spread=0.02, rng=rng)
    a = bernoulli_assignment(len(pts), 10, ell=3.0, rng=rng)
    alive = fixed_count_stragglers(10, 3, rng)
    out = resilient_kmedian(pts, 8, a, alive, local_iters=8, coord_iters=20)
    central = lloyd(jax.random.PRNGKey(seed), jnp.asarray(pts), 8, iters=20, median=True)
    assert out.cost <= 3.0 * (1.0 + max(out.recovery.delta, 0.5)) * float(central.cost)
