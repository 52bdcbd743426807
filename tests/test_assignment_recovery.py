"""Property and unit tests for the assignment schemes and recovery solvers."""

import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="property tests need the optional hypothesis dep")
from hypothesis import given, settings, strategies as st

from repro.core import (
    adversarial_stragglers,
    bernoulli_assignment,
    cyclic_assignment,
    fixed_count_stragglers,
    fractional_repetition_assignment,
    lp_recovery,
    min_cover_after_stragglers,
    node_loads,
    random_stragglers,
    satisfies_property1,
    shard_replication,
    singleton_assignment,
    solve_recovery,
    theorem6_ell,
    uniform_recovery,
)
from repro.core.recovery import jax_recovery


def test_theorem6_ell_monotonic():
    # Smaller delta and larger straggler probability both demand more replication.
    assert theorem6_ell(1000, 0.25, 0.1) > theorem6_ell(1000, 0.5, 0.1)
    assert theorem6_ell(1000, 0.5, 0.3) > theorem6_ell(1000, 0.5, 0.1)
    assert theorem6_ell(10_000, 0.5, 0.1) > theorem6_ell(100, 0.5, 0.1)


def test_bernoulli_shapes_and_cover():
    rng = np.random.default_rng(0)
    a = bernoulli_assignment(500, 20, ell=4.0, rng=rng)
    assert a.matrix.shape == (20, 500)
    assert shard_replication(a).min() >= 1  # ensure_cover
    assert a.params["p_a"] == pytest.approx(0.2)


def test_fractional_repetition_structure():
    a = fractional_repetition_assignment(120, 12, 3)
    # Every shard replicated exactly ell times; loads balanced within a group.
    assert (shard_replication(a) == 3).all()
    assert node_loads(a).sum() == 3 * 120


def test_fr_exact_recovery_under_adversary():
    a = fractional_repetition_assignment(100, 12, 4)
    alive = adversarial_stragglers(a, 3)  # ell-1 adversarial stragglers
    res = lp_recovery(a, alive)
    assert res.feasible and res.delta <= 1e-9  # exact: a ≡ 1
    assert len(res.uncovered) == 0


def test_cyclic_tolerates_ell_minus_1():
    a = cyclic_assignment(97, 10, 4)
    alive = adversarial_stragglers(a, 3)
    res = lp_recovery(a, alive)
    assert res.feasible
    assert len(res.uncovered) == 0


def test_singleton_loses_data_on_any_straggler():
    a = singleton_assignment(50, 10)
    alive = fixed_count_stragglers(10, 1, np.random.default_rng(0))
    assert min_cover_after_stragglers(a, alive) == 0
    res = lp_recovery(a, alive)
    assert len(res.uncovered) > 0  # information irrecoverably lost


def test_lp_recovery_band_is_minimal():
    # On an exactly-coverable instance, LP must find delta == 0.
    a = fractional_repetition_assignment(60, 8, 2)
    alive = np.ones(8, dtype=bool)
    res = lp_recovery(a, alive)
    assert res.feasible and res.delta <= 1e-9
    # And b must be supported only on alive nodes.
    assert res.b_full.shape == (8,)
    assert (res.b_full >= 0).all()


@pytest.mark.parametrize("s,ell", [(5, 1), (8, 2), (10, 4)])
def test_cyclic_matches_its_definition(s, ell):
    # Shard j → nodes {j, …, j+ell−1} mod s, written as the plain loop.
    n = 37
    want = np.zeros((s, n), dtype=np.uint8)
    for j in range(n):
        for r in range(ell):
            want[(j + r) % s, j] = 1
    np.testing.assert_array_equal(cyclic_assignment(n, s, ell).matrix, want)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_lp_recovery_matches_the_undeduplicated_lp(seed):
    # lp_recovery keeps one constraint pair per distinct alive-replica set;
    # the optimum must equal the LP over every shard's constraints.
    from scipy.optimize import linprog

    rng = np.random.default_rng(seed)
    a = bernoulli_assignment(200, 10, ell=3.0, rng=rng)
    alive = random_stragglers(10, 0.3, rng)
    A = a.matrix[alive].astype(np.float64)
    A = A[:, A.sum(axis=0) > 0]
    r, m = A.shape
    A_ub = np.block([[-A.T, np.zeros((m, 1))], [A.T, -np.ones((m, 1))]])
    b_ub = np.concatenate([-np.ones(m), np.zeros(m)])
    c = np.zeros(r + 1)
    c[-1] = 1.0
    full = linprog(c, A_ub=A_ub, b_ub=b_ub, bounds=[(0, None)] * r + [(1.0, None)], method="highs")
    res = lp_recovery(a, alive)
    assert res.delta == pytest.approx(full.x[-1] - 1.0, abs=1e-7)


def test_uniform_recovery_matches_paper_form():
    rng = np.random.default_rng(1)
    n, s, p_t, delta = 2000, 50, 0.1, 0.5
    a = bernoulli_assignment(n, s, delta=delta, p_straggler=p_t, rng=rng)
    alive = random_stragglers(s, p_t, rng)
    res = uniform_recovery(a, alive)
    # All alive weights equal (the paper's closed form).
    nz = res.b[res.b > 0]
    assert np.allclose(nz, nz[0])
    # Theorem 6 regime: Property 1 should hold for this realization.
    assert res.feasible
    assert res.delta <= delta + 0.25  # slack: single realization, finite n


def test_recovery_result_coverage_fraction():
    a = singleton_assignment(30, 6)
    alive = np.array([True, True, True, False, False, True])
    res = lp_recovery(a, alive)
    assert 0.0 < res.covered_fraction < 1.0


def test_jax_recovery_agrees_with_lp():
    rng = np.random.default_rng(2)
    a = bernoulli_assignment(80, 12, ell=5.0, rng=rng)
    alive = fixed_count_stragglers(12, 2, rng)
    lp = lp_recovery(a, alive)
    b = np.asarray(jax_recovery(a.submatrix(alive), iters=800))
    achieved = b @ a.submatrix(alive)
    covered = a.submatrix(alive).sum(axis=0) > 0
    if lp.feasible:
        assert achieved[covered].min() >= 1.0 - 1e-4
        # Heuristic solver: band within a constant factor of the LP optimum
        # (PGD+rescale is not minimax; it trades band quality for being
        # jit-able on-device).
        assert achieved[covered].max() <= 4.0 * (1.0 + lp.delta)


def test_satisfies_property1_exhaustive_small():
    a = fractional_repetition_assignment(40, 6, 3)
    assert satisfies_property1(a, t=2, delta=1e-6)
    # Killing an entire replica set of 3 CAN lose a shard only if all three
    # replicas die; t=3 adversarial breaks FR with ell=3.
    assert not satisfies_property1(a, t=3, delta=1e-6)


@settings(max_examples=15, deadline=None)
@given(
    s=st.integers(min_value=4, max_value=16),
    ell=st.integers(min_value=2, max_value=4),
    t=st.integers(min_value=0, max_value=2),
    n=st.integers(min_value=10, max_value=200),
)
def test_cyclic_property1_hypothesis(s, ell, t, n):
    """Cyclic assignment tolerates any t ≤ ell−1 stragglers with b ≥ 0."""
    if ell > s or t >= ell:
        return
    a = cyclic_assignment(n, s, ell)
    rng = np.random.default_rng(n * 31 + s)
    alive = fixed_count_stragglers(s, t, rng)
    res = lp_recovery(a, alive)
    assert res.feasible
    assert len(res.uncovered) == 0


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_lemma3_sandwich_property(seed):
    """Lemma 3: cost(P,C,w) ≤ Σ b_i cost(P_i,C,w) ≤ (1+δ)cost(P,C,w)
    for arbitrary centers and weights — checked on the achieved δ."""
    rng = np.random.default_rng(seed)
    n, s, d = 150, 8, 3
    pts = rng.normal(size=(n, d))
    w = rng.random(n) + 0.1
    a = bernoulli_assignment(n, s, ell=4.0, rng=rng)
    alive = fixed_count_stragglers(s, 2, rng)
    res = lp_recovery(a, alive)
    if not res.feasible:
        return
    C = rng.normal(size=(4, d))
    dists = np.sqrt(((pts[:, None, :] - C[None, :, :]) ** 2).sum(-1)).min(1)
    full = float((w * dists).sum())
    parts = sum(
        res.b_full[i] * float((w[a.shards_of(i)] * dists[a.shards_of(i)]).sum())
        for i in range(s)
        if res.b_full[i] > 0
    )
    assert full * (1 - 1e-6) <= parts <= (1 + res.delta) * full * (1 + 1e-6)
