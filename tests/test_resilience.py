"""Elastic resilience runtime tests: ResilienceSession state sharing,
on-device recovery (fused compiled step), elastic re-assignment, the
straggler scenario protocol, and the PR's satellite fixes.

Multi-round MESH tests follow the repo's forced-host-device pattern
(subprocess with XLA_FLAGS, like tests/test_distributed_executor.py) so the
in-process suite keeps its single-device assumptions and tier-1 stays fast.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pts(n=160, d=3, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, d)).astype(np.float32)


# ------------------------------------------------------ satellite: adversary


def _adversarial_reference(assignment, t):
    """The pre-vectorization scalar greedy (kept verbatim as the oracle)."""
    A = assignment.matrix.astype(np.int64)
    alive = np.ones(assignment.num_nodes, dtype=bool)
    for _ in range(min(t, assignment.num_nodes - 1)):
        best_node, best_key = None, None
        cover = A[alive].sum(axis=0)
        for i in np.flatnonzero(alive):
            c = cover - A[i]
            key = (int(c.min()), -int((c == c.min()).sum()), -int(A[i].sum()))
            if best_key is None or key < best_key:
                best_key, best_node = key, i
        alive[best_node] = False
    return alive


def test_adversarial_vectorized_matches_reference():
    from repro.core import (
        adversarial_stragglers,
        bernoulli_assignment,
        cyclic_assignment,
        fractional_repetition_assignment,
        singleton_assignment,
    )

    cases = [
        cyclic_assignment(37, 9, 3),
        fractional_repetition_assignment(24, 8, 2),
        singleton_assignment(20, 6),
    ]
    for seed in range(4):
        cases.append(
            bernoulli_assignment(30, 7, ell=2.5, rng=np.random.default_rng(seed))
        )
    for a in cases:
        for t in (0, 1, 2, 3):
            got = adversarial_stragglers(a, t)
            want = _adversarial_reference(a, t)
            np.testing.assert_array_equal(got, want, err_msg=f"{a.scheme} t={t}")


# ------------------------------------------------- satellite: nnls degeneracy


def _degenerate_nnls_assignment():
    """NNLS pins b_0 to exactly 0 here: serving shard 0 (unique to node 0)
    costs more over-coverage on the 4 triple-replicated shards than it saves
    (KKT multiplier at the boundary), so covered shard 0 ends with zero mass."""
    from repro.core.assignment import Assignment

    mat = np.zeros((3, 13), dtype=np.uint8)
    mat[0, 0] = 1      # shard 0: node 0 only
    mat[:, 1:5] = 1    # shards 1-4: everyone
    mat[1, 5:9] = 1    # shards 5-8: node 1 only
    mat[2, 9:13] = 1   # shards 9-12: node 2 only
    return Assignment(matrix=mat, scheme="crafted", params={})


def test_nnls_degenerate_is_explicitly_infeasible():
    from repro.core.recovery import nnls_recovery

    a = _degenerate_nnls_assignment()
    res = nnls_recovery(a, np.ones(3, dtype=bool))
    assert res.method == "nnls"
    assert res.feasible is False
    assert res.a[0] <= 1e-12  # the raw, unscaled b came back


def test_solve_recovery_auto_skips_degenerate_nnls_to_lp():
    from repro.core.recovery import solve_recovery

    a = _degenerate_nnls_assignment()
    res = solve_recovery(a, np.ones(3, dtype=bool), method="auto")
    assert res.method == "lp"
    assert res.feasible
    assert res.a.min() >= 1.0 - 1e-7


# ------------------------------------- satellite: simulator reset/determinism


def test_deadline_simulator_determinism_and_reset():
    from repro.core import DeadlineStragglerSimulator

    kw = dict(num_nodes=7, seed=11, p_spike=0.3, persistence=0.7)
    s1 = DeadlineStragglerSimulator(**kw)
    s2 = DeadlineStragglerSimulator(**kw)
    run1 = [s1.step() for _ in range(8)]
    run2 = [s2.step() for _ in range(8)]
    for r1, r2 in zip(run1, run2):  # same seed → same stream
        np.testing.assert_array_equal(r1.alive, r2.alive)
        np.testing.assert_array_equal(r1.spiked, r2.spiked)
        np.testing.assert_allclose(r1.latencies, r2.latencies)
    s1.reset()
    replay = [s1.step() for _ in range(8)]
    for r1, r2 in zip(run1, replay):  # reset → replay
        np.testing.assert_array_equal(r1.alive, r2.alive)
        np.testing.assert_array_equal(r1.spiked, r2.spiked)
        assert r1.index == r2.index


def test_step_record_carries_spike_state():
    from repro.core import DeadlineStragglerSimulator

    kw = dict(num_nodes=5, seed=0, p_spike=0.5, persistence=1.0)
    sim = DeadlineStragglerSimulator(**kw)
    recs = [sim.step() for _ in range(6)]
    assert any(r.spiked.any() for r in recs)
    # The record owns a SNAPSHOT: mutating it must not corrupt the stream.
    recs[2].spiked[:] = ~recs[2].spiked
    tail = [sim.step() for _ in range(3)]
    ref = DeadlineStragglerSimulator(**kw)
    for _ in range(6):
        ref.step()
    for got, want in zip(tail, [ref.step() for _ in range(3)]):
        np.testing.assert_array_equal(got.spiked, want.spiked)
        np.testing.assert_array_equal(got.alive, want.alive)


# ------------------------------------------------------- scenario protocol


def test_scenario_factory_and_reset_replay():
    from repro.core import cyclic_assignment, make_scenario

    a = cyclic_assignment(24, 6, 2)
    for name, kw in (
        ("iid", {"p_straggler": 0.3, "seed": 2}),
        ("fixed", {"t": 2, "seed": 2}),
        ("deadline", {"seed": 2, "p_spike": 0.3}),
    ):
        scen = make_scenario(name, 6, **kw)
        first = [next(scen) for _ in range(5)]
        scen.reset()
        again = [next(scen) for _ in range(5)]
        for r1, r2 in zip(first, again):
            np.testing.assert_array_equal(r1.alive, r2.alive)
            assert r1.index == r2.index
        assert first[0].alive.shape == (6,)

    adv = make_scenario("adversarial", 6, assignment=a, t=1)
    s1, s2 = next(adv), next(adv)
    np.testing.assert_array_equal(s1.alive, s2.alive)  # stateless adversary
    with pytest.raises(ValueError, match="assignment"):
        make_scenario("adversarial", 6)
    with pytest.raises(ValueError, match="unknown scenario"):
        make_scenario("lunch-break", 6)


# ------------------------------------------------ scenario: trace replay


def test_trace_scenario_roundtrip_reset_and_loop(tmp_path):
    from repro.core import TraceScenario, make_scenario, record_trace

    path = str(tmp_path / "trace.jsonl")
    src = make_scenario("deadline", 5, seed=3, p_spike=0.3)
    assert record_trace(src, 6, path) == 6
    scen = make_scenario("trace", 5, path=path)
    src.reset()
    first = [next(scen) for _ in range(6)]
    for got, want in zip(first, [next(src) for _ in range(6)]):
        np.testing.assert_array_equal(got.alive, want.alive)
        np.testing.assert_allclose(got.latencies, want.latencies)
    # Infinite iterator: wraps around past the recorded rounds...
    np.testing.assert_array_equal(next(scen).alive, first[0].alive)
    assert next(scen).index == 7
    # ...and reset() replays from step 0.
    scen.reset()
    again = [next(scen) for _ in range(6)]
    for r1, r2 in zip(first, again):
        np.testing.assert_array_equal(r1.alive, r2.alive)
        assert r1.index == r2.index
    # loop=False yields exactly the recorded rounds.
    finite = TraceScenario(5, path, loop=False)
    assert len(list(finite)) == 6
    assert len(finite) == 6


def test_trace_scenario_ignores_extra_row_keys(tmp_path):
    """BENCH-row-style annotations (name/us_per_call/derived) ride along."""
    from repro.core import TraceScenario

    path = tmp_path / "annotated.jsonl"
    path.write_text(
        '{"name": "scen_cell", "us_per_call": 1.0, "derived": "x", "alive": [1, 0, 1]}\n'
        '{"alive": [0, 1, 1], "index": 7}\n'
    )
    scen = TraceScenario(3, str(path))
    np.testing.assert_array_equal(next(scen).alive, [True, False, True])
    np.testing.assert_array_equal(next(scen).alive, [False, True, True])


def test_trace_scenario_input_validation(tmp_path):
    import pytest as _pytest

    from repro.core import TraceScenario, make_scenario

    bad_len = tmp_path / "bad_len.jsonl"
    bad_len.write_text('{"alive": [1, 0]}\n')
    with _pytest.raises(ValueError, match="entries"):
        TraceScenario(3, str(bad_len))
    no_alive = tmp_path / "no_alive.jsonl"
    no_alive.write_text('{"latencies": [1.0]}\n')
    with _pytest.raises(ValueError, match="'alive'"):
        TraceScenario(1, str(no_alive))
    empty = tmp_path / "empty.jsonl"
    empty.write_text("\n")
    with _pytest.raises(ValueError, match="empty trace"):
        TraceScenario(1, str(empty))
    with _pytest.raises(ValueError, match="path="):
        make_scenario("trace", 3)


# --------------------------------------------------- session: shared cache


def test_session_one_cache_across_algorithms_and_plan():
    from repro.core import ResilienceSession, cyclic_assignment, fixed_count_stragglers

    pts = _pts(120)
    a = cyclic_assignment(120, 6, 2)
    alive = fixed_count_stragglers(6, 1, np.random.default_rng(3))
    sess = ResilienceSession(a)
    out = sess.kmedian(pts, 3, alive, local_iters=3, coord_iters=4)
    sess.cost(pts, out.centers, alive)
    sess.pca(pts, 2, 0.5, alive)
    sess.coreset(pts, 3, 16, alive)
    assert sess.stats.host_solves == 1  # one pattern, solved once, shared 4×
    assert sess.stats.cache_hits == 3


def test_kmedian_span_tree_names_each_host_step():
    """One solve records its pack (with the fingerprint inside it) and the
    four pipeline steps as siblings, under no span that encloses the whole
    solve; the second solve of the same array is a pack hit."""
    from repro.core import ResilienceSession, cyclic_assignment
    from repro.obs import configure_buffer
    from repro.obs import trace as trace_mod

    pts = _pts(96)
    alive = np.array([True, False, True, True])
    trace_mod.flush()  # pause spans queued by earlier tests go to the old ring
    prev = trace_mod._BUFFER
    buf = configure_buffer(512)
    try:
        sess = ResilienceSession(cyclic_assignment(96, 4, 2))
        for _ in range(2):
            sess.kmedian(pts, 3, alive, local_iters=2, coord_iters=2)
        rows = [r for r in buf.rows()
                if r["name"].startswith(("session.", "kmedian."))]
    finally:
        trace_mod._BUFFER = prev
    rows.sort(key=lambda r: r["span"])  # ids are handed out as spans start
    names = [r["name"] for r in rows]
    solve = ["session.pack", "session.fingerprint", "kmedian.upload", "kmedian.local",
             "kmedian.coordinator", "kmedian.cost"]
    assert names == ["session.recovery_solve"] + solve + solve
    by_id = {r["span"]: r for r in rows}
    for r in rows:
        if r["name"] == "session.fingerprint":
            assert by_id[r["parent"]]["name"] == "session.pack"
        else:
            assert r["parent"] is None
    packs = [r for r in rows if r["name"] == "session.pack"]
    assert [p["attrs"]["hit"] for p in packs] == [False, True]
    assert packs[0]["attrs"]["rows"] == 4 * 48
    assert packs[0]["attrs"]["bytes"] == 4 * 48 * 3 * 4 + 4 * 48 * 4
    fp = next(r for r in rows if r["name"] == "session.fingerprint")
    assert fp["attrs"]["bytes"] == pts.nbytes


def test_coverage_validation_computed_once_per_pattern():
    """Satellite fix: the per-call shard-coverage re-validation in the
    algorithm prelude is hoisted into the session and cached per pattern —
    repeated streaming solves against a seen pattern skip the host-side
    work.  ``SessionStats.coverage_checks`` counts actual computations."""
    from repro.core import ResilienceSession, cyclic_assignment

    pts = _pts(90)
    a = cyclic_assignment(90, 6, 2)
    alive = np.array([True, True, False, True, True, True])
    sess = ResilienceSession(a)
    sess.coreset(pts, 3, 8, alive)
    sess.coreset(pts, 3, 8, alive)
    sess.kmedian(pts, 3, alive, local_iters=2, coord_iters=2)
    assert sess.stats.coverage_checks == 1  # one pattern → one validation
    other = np.array([True, False, True, True, True, True])
    sess.cost(pts, np.zeros((3, 3), np.float32), other)
    assert sess.stats.coverage_checks == 2  # new pattern → one more
    sess.coreset(pts, 3, 8, other)
    assert sess.stats.coverage_checks == 2
    # The all-dead guard still fires (now from the cached validation).
    with pytest.raises(ValueError, match="no surviving"):
        sess.prepare(pts, np.zeros(6, dtype=bool))


def test_coverage_validation_invalidated_with_pattern_cache():
    """An elastic patch drops exactly the coverage entries it can change —
    the same rule as the recovery cache."""
    from repro.core import ElasticPolicy, ResilienceSession, cyclic_assignment

    sess = ResilienceSession(
        cyclic_assignment(40, 8, 2), elastic=ElasticPolicy(enabled=True, patience=2)
    )
    dead_67 = np.ones(8, dtype=bool)
    dead_67[[6, 7]] = False
    uncovered_before = sess.validate_coverage(dead_67)
    assert len(uncovered_before) > 0  # adjacent cyclic nodes → coverage lost
    assert sess.stats.coverage_checks == 1
    for _ in range(3):
        sess.observe(dead_67)
    assert sess.stats.elastic_patches >= 1
    # The patch re-replicated the at-risk shards onto nodes alive in this
    # pattern → the stale entry must be recomputed, and is now covered.
    assert len(sess.validate_coverage(dead_67)) == 0
    assert sess.stats.coverage_checks == 2


def test_coverage_entry_from_caller_rec_also_invalidated():
    """A coverage entry seeded via validate_coverage(alive, rec=...) never
    touches the recovery cache — the patch sweep must still drop it (it is
    keyed independently), or it would serve pre-patch uncovered ids."""
    from repro.core import ElasticPolicy, ResilienceSession, cyclic_assignment
    from repro.core.recovery import solve_recovery

    a = cyclic_assignment(40, 8, 2)
    sess = ResilienceSession(a, elastic=ElasticPolicy(enabled=True, patience=2))
    dead = np.ones(8, dtype=bool)
    dead[[6, 7]] = False
    rec = solve_recovery(a, dead)  # host-side, bypasses sess._cache
    assert len(sess.validate_coverage(dead, rec)) > 0
    assert sess.stats.host_solves == 0  # cache really was bypassed
    for _ in range(3):
        sess.observe(dead)
    assert sess.stats.elastic_patches >= 1
    assert len(sess.validate_coverage(dead)) == 0  # recomputed post-patch
    assert sess.stats.coverage_checks == 2


def test_entry_points_without_session_unchanged():
    """session=None must reproduce the old per-call behaviour exactly."""
    from repro.core import (
        cyclic_assignment,
        fixed_count_stragglers,
        resilient_kmedian,
    )

    pts = _pts(100, seed=5)
    a = cyclic_assignment(100, 5, 2)
    alive = fixed_count_stragglers(5, 1, np.random.default_rng(1))
    o1 = resilient_kmedian(pts, 3, a, alive, local_iters=3, coord_iters=4)
    o2 = resilient_kmedian(pts, 3, a, alive, local_iters=3, coord_iters=4)
    assert o1.cost == pytest.approx(o2.cost)


def test_training_plan_rides_the_session_cache():
    from repro.train.resilient import make_plan

    plan = make_plan(6, 6, redundancy=2, scheme="cyclic")
    alive = np.array([True, True, False, True, True, True])
    plan.group_weights(alive)
    plan.group_weights(alive)
    plan.recovery(alive)
    assert plan.session.stats.host_solves == 1
    assert plan.session.stats.cache_hits == 2


# ---------------------------------------- on-device recovery (satellite 4)


def test_jax_recovery_masked_parity_with_lp():
    """Device-solver weights must land in the LP's feasibility band (within
    tolerance) on all three construction families."""
    from repro.core import (
        bernoulli_assignment,
        cyclic_assignment,
        fixed_count_stragglers,
        fractional_repetition_assignment,
        jax_recovery_masked,
        lp_recovery,
    )

    rng = np.random.default_rng(0)
    cases = [
        cyclic_assignment(60, 8, 3),
        fractional_repetition_assignment(64, 8, 2),
        bernoulli_assignment(60, 10, ell=4.0, rng=rng),
    ]
    for a in cases:
        alive = fixed_count_stragglers(a.num_nodes, 2, rng)
        lp = lp_recovery(a, alive)
        b = np.asarray(
            jax_recovery_masked(a.matrix.astype(np.float32), alive, iters=500)
        )
        assert (b[~alive] == 0).all(), "stragglers must get zero weight"
        ach = b @ a.matrix
        covered = a.matrix[alive].sum(axis=0) > 0
        if lp.feasible:
            assert ach[covered].min() >= 1.0 - 1e-3, a.scheme
            # Heuristic band: within a constant factor of the LP optimum.
            assert ach[covered].max() <= 4.0 * (1.0 + lp.delta), a.scheme


def test_jax_recovery_masked_uncovered_shard_pattern():
    from repro.core import jax_recovery_masked, lp_recovery, singleton_assignment

    a = singleton_assignment(30, 6)
    alive = np.array([True, True, False, True, True, True])
    lp = lp_recovery(a, alive)
    assert len(lp.uncovered) > 0
    b = np.asarray(jax_recovery_masked(a.matrix.astype(np.float32), alive, iters=300))
    ach = b @ a.matrix
    covered = a.matrix[alive].sum(axis=0) > 0
    assert np.isfinite(b).all()
    assert (ach[~covered] == 0).all()  # lost shards stay lost, no NaN/Inf
    assert ach[covered].min() >= 1.0 - 1e-3  # covered band still achieved
    np.testing.assert_array_equal(np.flatnonzero(~covered), lp.uncovered)


def test_step_cost_no_host_solve_no_recompile_lemma3_band():
    """The fused path: unseen straggler patterns are runtime data — zero host
    solves, zero re-lowers, and the estimate stays in the Lemma-3 band."""
    import jax
    import jax.numpy as jnp

    from repro.core import (
        ResilienceSession,
        clustering_cost,
        cyclic_assignment,
        fixed_count_stragglers,
        lloyd,
    )
    from repro.core.executor import get_executor

    pts = _pts(150, seed=7)
    a = cyclic_assignment(150, 6, 2)  # δ = 0 band for any single straggler
    centers = np.asarray(
        lloyd(jax.random.PRNGKey(0), jnp.asarray(pts), 3, iters=4).centers
    )
    true = float(clustering_cost(jnp.asarray(pts), jnp.asarray(centers)))
    sess = ResilienceSession(a)
    ex = get_executor(None)
    est0 = sess.step_cost(pts, centers, fixed_count_stragglers(6, 1, np.random.default_rng(0)))
    n_compiled = len(ex._jitted)
    for seed in (1, 2, 3):  # three more previously-unseen patterns
        alive = fixed_count_stragglers(6, 1, np.random.default_rng(seed))
        est = sess.step_cost(pts, centers, alive)
        assert true * (1 - 1e-4) <= est <= true * 1.5
    assert len(ex._jitted) == n_compiled, "new pattern must not re-lower"
    assert sess.stats.host_solves == 0
    assert sess.stats.device_solves == 4
    assert true * (1 - 1e-4) <= est0 <= true * 1.5


# ----------------------------------------------------- elastic re-assignment


def _persistent_spike_scenario(s=8, seed=6):
    from repro.core import make_scenario

    # persistence=1.0: spiked nodes never recover — the elastic regime.
    return make_scenario(
        "deadline", s, seed=seed, p_spike=0.06, persistence=1.0,
        spike_scale=6.0, deadline=2.0,
    )


def test_elastic_repairs_coverage_disabled_loses_it():
    from repro.core import ElasticPolicy, ResilienceSession, cyclic_assignment

    def run(enabled):
        sess = ResilienceSession(
            cyclic_assignment(160, 8, 2),
            elastic=ElasticPolicy(enabled=enabled, patience=2),
        )
        scen = _persistent_spike_scenario()
        uncovered = [sess.observe(next(scen))["uncovered"] for _ in range(16)]
        return sess, uncovered

    s_on, u_on = run(True)
    s_off, u_off = run(False)
    assert s_on.stats.elastic_patches >= 1
    assert all(u == 0 for u in u_on[-6:]), f"elastic must restore coverage: {u_on}"
    assert any(u > 0 for u in u_off[-6:]), f"disabled run must report loss: {u_off}"
    assert s_off.stats.uncovered_rounds > s_on.stats.uncovered_rounds


def test_elastic_patch_invalidates_only_affected_patterns():
    from repro.core import ElasticPolicy, ResilienceSession, cyclic_assignment

    sess = ResilienceSession(
        cyclic_assignment(40, 8, 2), elastic=ElasticPolicy(enabled=True, patience=2)
    )
    # Prime the host cache: one pattern with every healthy node alive, one
    # with ALL potential patch targets (nodes 0..5) dead.
    dead_67 = np.ones(8, dtype=bool)
    dead_67[[6, 7]] = False
    only_67 = ~dead_67
    sess.recovery(dead_67)
    sess.recovery(only_67)
    assert sess.stats.host_solves == 2
    # Persistent stragglers 6, 7 → patch re-replicates their shards onto the
    # healthy nodes 0..5.
    for _ in range(3):
        sess.observe(dead_67)
    assert sess.stats.elastic_patches >= 1
    # dead_67 has patched nodes alive → its cached result is stale → dropped;
    # only_67 has every patched node dead (b=0 there, the new matrix entries
    # never enter bᵀA_R) → it must SURVIVE the patch.
    solves_before, hits_before = sess.stats.host_solves, sess.stats.cache_hits
    sess.recovery(only_67)
    assert sess.stats.cache_hits == hits_before + 1, "unaffected entry was dropped"
    res = sess.recovery(dead_67)
    assert sess.stats.host_solves == solves_before + 1, "stale entry was kept"
    assert res.feasible and len(res.uncovered) == 0


def test_elastic_patch_repairs_recovery_after_coverage_loss():
    """After the patch, the pattern that used to lose shards becomes exactly
    recoverable (the re-replicated shards have live replicas)."""
    from repro.core import ElasticPolicy, ResilienceSession, cyclic_assignment

    a = cyclic_assignment(40, 8, 2)
    sess = ResilienceSession(a, elastic=ElasticPolicy(enabled=True, patience=2))
    dead = np.ones(8, dtype=bool)
    dead[[6, 7]] = False  # adjacent under cyclic ell=2 → coverage lost
    assert len(sess.recovery(dead).uncovered) > 0
    for _ in range(3):
        sess.observe(dead)
    assert sess.stats.elastic_patches >= 1
    assert sess.assignment.scheme.endswith("+elastic")
    res = sess.recovery(dead)
    assert len(res.uncovered) == 0 and res.feasible


def test_step_cost_tracks_dataset_switches():
    """The resident device placement must follow the points argument even
    when host-path calls (cost/prepare) repack a different dataset between
    step_cost calls — regression for a stale-resident aliasing bug."""
    import jax
    import jax.numpy as jnp

    from repro.core import ResilienceSession, cyclic_assignment, lloyd

    a = cyclic_assignment(80, 4, 2)
    pts_a = _pts(80, seed=1)
    pts_b = pts_a + 100.0  # wildly different cost against the same centers
    centers = np.asarray(
        lloyd(jax.random.PRNGKey(0), jnp.asarray(pts_a), 2, iters=3).centers
    )
    alive = np.array([True, True, True, False])
    sess = ResilienceSession(a)
    est_a = sess.step_cost(pts_a, centers, alive)
    sess.cost(pts_b, centers, alive)  # host path repacks for pts_b
    est_b = sess.step_cost(pts_b, centers, alive)
    fresh = ResilienceSession(a).step_cost(pts_b, centers, alive)
    assert est_b == pytest.approx(fresh, rel=1e-6)
    assert est_b > 10 * est_a  # and definitely not pts_a's cost


def test_in_place_mutation_invalidates_pack_cache():
    """Identity-keyed caching must not survive an in-place edit of the
    caller's points array (content fingerprint regression)."""
    import jax
    import jax.numpy as jnp

    from repro.core import ResilienceSession, cyclic_assignment, lloyd

    a = cyclic_assignment(80, 4, 2)
    pts = _pts(80, seed=2)
    centers = np.asarray(
        lloyd(jax.random.PRNGKey(0), jnp.asarray(pts), 2, iters=3).centers
    )
    alive = np.array([True, True, False, True])
    sess = ResilienceSession(a)
    est1 = sess.step_cost(pts, centers, alive)
    c1 = sess.cost(pts, centers, alive)
    pts *= 3.0  # in-place: same object, new contents
    est2 = sess.step_cost(pts, centers, alive)
    c2 = sess.cost(pts, centers, alive)
    fresh = ResilienceSession(a)
    assert est2 == pytest.approx(fresh.step_cost(pts, centers, alive), rel=1e-6)
    assert c2 == pytest.approx(fresh.cost(pts, centers, alive), rel=1e-6)
    assert est2 != pytest.approx(est1, rel=1e-3)
    assert c2 != pytest.approx(c1, rel=1e-3)


def test_session_rejects_foreign_assignment_and_executor():
    from repro.core import (
        ElasticPolicy,
        ResilienceSession,
        cyclic_assignment,
        resilient_cost,
        resilient_kmedian,
    )

    pts = _pts(40, seed=4)
    a = cyclic_assignment(40, 8, 2)
    other = cyclic_assignment(40, 8, 3)  # same node count, different matrix
    sess = ResilienceSession(a, elastic=ElasticPolicy(enabled=True, patience=2))
    alive = np.ones(8, dtype=bool)
    with pytest.raises(ValueError, match="not the session's assignment"):
        resilient_kmedian(pts, 2, other, alive, session=sess,
                          local_iters=2, coord_iters=2)
    with pytest.raises(ValueError, match="conflicts with the session's"):
        resilient_cost(pts, np.zeros((2, 3), np.float32), a, alive,
                       session=sess, executor="mesh")
    # The ORIGINAL assignment stays accepted after an elastic patch (lineage).
    dead = alive.copy()
    dead[[6, 7]] = False
    for _ in range(3):
        sess.observe(dead)
    assert sess.stats.elastic_patches >= 1
    assert sess.assignment is not a
    est = resilient_cost(pts, np.zeros((2, 3), np.float32), a, dead, session=sess)
    assert np.isfinite(est)


def test_step_cost_all_dead_raises():
    from repro.core import ResilienceSession, cyclic_assignment

    sess = ResilienceSession(cyclic_assignment(40, 4, 2))
    with pytest.raises(ValueError, match="no surviving"):
        sess.step_cost(_pts(40), np.zeros((2, 3), np.float32), np.zeros(4, bool))


def test_recovery_method_conflict_with_session_raises():
    from repro.core import ResilienceSession, cyclic_assignment, resilient_kmedian

    a = cyclic_assignment(60, 6, 2)
    sess = ResilienceSession(a, recovery_method="lp")
    alive = np.array([True] * 5 + [False])
    with pytest.raises(ValueError, match="conflicts with the session"):
        resilient_kmedian(
            _pts(60), 3, a, alive, recovery_method="uniform", session=sess
        )
    # Explicitly matching (or omitted) methods are fine.
    out = sess.kmedian(_pts(60), 3, alive, local_iters=2, coord_iters=2,
                       recovery_method="lp")
    assert np.isfinite(out.cost)


def _skewed_assignment():
    """Max load 8 on nodes 0/1; nodes 6/7 exclusively hold shards 16–19.
    Killing 6 and 7 puts those shards at risk, and the patch targets (the
    least-loaded healthy nodes 4/5, load 4 → ≤ 8) fit inside the existing
    padding — exercising the INCREMENTAL re-pack/re-place branch."""
    from repro.core.assignment import Assignment

    mat = np.zeros((8, 20), dtype=np.uint8)
    mat[0, 0:8] = 1
    mat[1, 8:16] = 1
    mat[2, 0:8] = 1
    mat[3, 8:16] = 1
    mat[4, 0:4] = 1
    mat[5, 4:8] = 1
    mat[6, 16:20] = 1
    mat[7, 16:20] = 1
    return Assignment(matrix=mat, scheme="skewed", params={})


def test_patch_does_not_mutate_handed_out_pack():
    """Arrays returned by prepare() must stay stable across an elastic patch
    (copy-on-patch), or a caller's in-flight algorithm would see mixed
    pre-/post-patch placements."""
    from repro.core import ElasticPolicy, ResilienceSession
    from repro.core.kmedian import prepare_resilient_run

    pts = _pts(20, seed=3)
    sess = ResilienceSession(
        _skewed_assignment(), elastic=ElasticPolicy(enabled=True, patience=2)
    )
    dead = np.ones(8, dtype=bool)
    dead[[6, 7]] = False
    # Make the pack + placement resident, then hand out the host arrays.
    sess.step_cost(pts, np.zeros((2, 3), np.float32), dead)
    _, _, _, _, xs, ws = prepare_resilient_run(pts, None, dead, session=sess)
    xs_snap, ws_snap = xs.copy(), ws.copy()
    for _ in range(3):
        sess.observe(dead)
    assert sess.stats.elastic_patches >= 1
    assert sess.stats.moved_node_blocks >= 1, "incremental branch did not run"
    np.testing.assert_array_equal(xs, xs_snap)
    np.testing.assert_array_equal(ws, ws_snap)
    # The session's own view DID move on: fresh arrays with the re-replicated
    # shards now weighted on the patch-target nodes.
    _, _, _, _, xs2, ws2 = prepare_resilient_run(pts, None, dead, session=sess)
    assert xs2 is not xs
    assert ws2[[4, 5]].sum() > ws[[4, 5]].sum()


def test_executor_update_node_rows_local():
    from repro.core.executor import get_executor

    ex = get_executor(None)
    arr = ex.place_node_stacked(np.arange(12, dtype=np.float32).reshape(6, 2))
    out = np.asarray(ex.update_node_rows(arr, [0, 3], np.full((2, 2), 9.0, np.float32)))
    want = np.arange(12, dtype=np.float32).reshape(6, 2)
    want[[0, 3]] = 9.0
    np.testing.assert_array_equal(out, want)


def test_executor_update_node_rows_mesh_single_device():
    from repro.core.executor import get_executor

    ex = get_executor("mesh")
    arr = ex.place_node_stacked(np.arange(12, dtype=np.float32).reshape(6, 2))
    out = np.asarray(ex.update_node_rows(arr, [1, 4], np.full((2, 2), 7.0, np.float32)))
    want = np.arange(12, dtype=np.float32).reshape(6, 2)
    want[[1, 4]] = 7.0
    np.testing.assert_array_equal(out, want)


def test_session_mesh_matches_local_single_device():
    import jax
    import jax.numpy as jnp

    from repro.core import ResilienceSession, cyclic_assignment, fixed_count_stragglers, lloyd

    pts = _pts(140, seed=9)
    a = cyclic_assignment(140, 6, 2)
    alive = fixed_count_stragglers(6, 1, np.random.default_rng(4))
    centers = np.asarray(
        lloyd(jax.random.PRNGKey(1), jnp.asarray(pts), 3, iters=4).centers
    )
    sl = ResilienceSession(a)
    sm = ResilienceSession(a, executor="mesh")
    cl = sl.step_cost(pts, centers, alive)
    cm = sm.step_cost(pts, centers, alive)
    assert cm == pytest.approx(cl, rel=1e-5)
    kl = sl.kmedian(pts, 3, alive, local_iters=3, coord_iters=4)
    km = sm.kmedian(pts, 3, alive, local_iters=3, coord_iters=4)
    assert km.cost == pytest.approx(kl.cost, rel=1e-5)


# --------------------------------------- multi-round mesh run (8 devices)


def test_multiround_session_parity_8_devices():
    """Forced-host-device pattern: a full multi-round elastic run — scenario
    stream, per-round fused step_cost, mid-run re-assignment with block
    re-placement — must agree local↔mesh at 1e-5 per round, with zero host
    solves on the hot path and zero uncovered shards after the patch."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax
        import jax.numpy as jnp
        assert jax.device_count() == 8
        from repro.core import (ResilienceSession, ElasticPolicy,
                                cyclic_assignment, lloyd, make_scenario)
        rng = np.random.default_rng(0)
        pts = rng.normal(size=(160, 3)).astype(np.float32)
        centers = np.asarray(lloyd(jax.random.PRNGKey(0), jnp.asarray(pts), 3,
                                   iters=4).centers)
        def run(executor):
            sess = ResilienceSession(
                cyclic_assignment(160, 8, 2), executor=executor,
                elastic=ElasticPolicy(enabled=True, patience=2))
            scen = make_scenario("deadline", 8, seed=6, p_spike=0.06,
                                 persistence=1.0, spike_scale=6.0, deadline=2.0)
            costs, uncovered = [], []
            for _ in range(12):
                step = next(scen)
                ev = sess.observe(step)
                uncovered.append(ev["uncovered"])
                if step.alive.any():
                    costs.append(sess.step_cost(pts, centers, step.alive))
            return sess, costs, uncovered
        sl, cl, ul = run("local")
        sm, cm, um = run("mesh")
        assert ul == um, (ul, um)
        for a, b in zip(cl, cm):
            assert abs(a / b - 1.0) <= 1e-5, (a, b)
        assert sl.stats.host_solves == 0 and sm.stats.host_solves == 0
        assert sl.stats.elastic_patches >= 1 and sm.stats.elastic_patches >= 1
        assert ul[-1] == 0, ul   # coverage restored after the patch
        print("MULTIROUND_PARITY_OK")
        """
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=540, env=env,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "MULTIROUND_PARITY_OK" in out.stdout


# ------------------------------------------------ bench: re-solve counters


def test_bench_scenarios_reports_zero_host_solves():
    """Acceptance hook: the compiled-step path must show host_solves=0 on the
    emitted rows even though every round's pattern starts unseen."""
    sys.path.insert(0, _REPO)
    try:
        from benchmarks import common
        from benchmarks.bench_scenarios import run as bench_run

        mark = len(common.ROWS)
        bench_run(n=120, s=6, k=3, rounds=3, executors=("local",))
        rows = common.ROWS[mark:]
    finally:
        sys.path.pop(0)
    def field(derived, key):
        return int(derived.split(key + "=")[1].split()[0])

    cells = [r for r in rows if "host_solves=" in r[2]]
    assert len(cells) == 20  # 5 schemes × 4 scenarios
    for name, _us, derived in cells:
        assert field(derived, "host_solves") == 0, (name, derived)
        assert field(derived, "device_solves") > 0, (name, derived)
    assert any(field(d, "patches") > 0 for _n, _u, d in cells), (
        "sweep never exercised an elastic patch"
    )

# ------------------------------------- randomized recovery-parity oracle


def _recovered_gradient(b_full, A, shard_grads):
    """Lemma 3 on gradients in linear-algebra form: node i's local gradient
    is Σ_{s∈P_i} g_s; the combine is Σ_i b_i·(A g)_i = Σ_s (bᵀA)_s g_s."""
    per_node = A.astype(np.float64) @ shard_grads  # (s, d)
    return np.asarray(b_full, np.float64) @ per_node


def test_recovery_parity_oracle_fuzzed_patterns():
    """Seeded fuzz over straggler patterns: host-LP vs on-device-PGD
    recovered gradients pinned at 1e-5 wherever the exact band is achievable
    (FR always; cyclic for any ℓ−1 stragglers — δ* = 0 patterns), and
    band-bounded for Bernoulli (where the LP optimum is non-unique, so the
    two solvers legitimately pick different points of the feasible set)."""
    from repro.core import (
        bernoulli_assignment,
        cyclic_assignment,
        fixed_count_stragglers,
        fractional_repetition_assignment,
    )
    from repro.core.recovery import jax_recovery_masked, lp_recovery

    rng = np.random.default_rng(0)
    d = 5
    cases = [
        ("fr", fractional_repetition_assignment(24, 8, 2), 1, True),
        ("fr", fractional_repetition_assignment(24, 8, 2), 3, True),  # per-group deaths
        ("cyclic", cyclic_assignment(24, 8, 2), 1, True),
        ("cyclic", cyclic_assignment(24, 8, 3), 2, False),  # δ* > 0: band only
        ("bernoulli", bernoulli_assignment(24, 8, ell=4.0, rng=rng), 1, False),
    ]
    exact_checked = 0
    for name, a, t, exact in cases:
        A = a.matrix
        shard_grads = rng.normal(size=(a.num_shards, d))
        truth = shard_grads.sum(axis=0)
        for seed in range(6):
            alive = fixed_count_stragglers(a.num_nodes, t, np.random.default_rng(seed))
            if (A[alive].sum(axis=0) == 0).any():
                continue  # degenerate patterns exercised separately below
            lp = lp_recovery(a, alive)
            assert lp.feasible
            b_dev = np.asarray(
                jax_recovery_masked(A.astype(np.float32), alive, iters=1200)
            )
            assert (b_dev[~alive] == 0).all(), "stragglers must get zero weight"
            g_host = _recovered_gradient(lp.b_full, A, shard_grads)
            g_dev = _recovered_gradient(b_dev, A, shard_grads)
            scale = np.abs(truth).max()
            if exact:
                # δ* = 0 band is a point: both solvers must land on it.
                np.testing.assert_allclose(g_dev, g_host, atol=1e-5 * scale)
                np.testing.assert_allclose(g_dev, truth, atol=1e-5 * scale)
                exact_checked += 1
            else:
                # Non-unique optimum: pin each solver to ITS achieved band —
                # |recovered − truth| ≤ δ_achieved · Σ_s |g_s| coordinatewise.
                gmass = np.abs(shard_grads).sum(axis=0)
                for b in (lp.b_full, b_dev):
                    ach = np.asarray(b, np.float64) @ A
                    assert ach.min() >= 1.0 - 1e-3
                    bound = (ach.max() - 1.0) * gmass + 1e-4 * scale
                    assert (np.abs(_recovered_gradient(b, A, shard_grads) - truth) <= bound).all()
    assert exact_checked >= 10  # the 1e-5 pins actually ran


def test_recovery_parity_oracle_cost_path():
    """The same oracle through the REAL paths: `session.step_cost` (PGD
    inside the compiled step) vs the host-LP `resilient_cost` — 1e-5 on FR
    (δ = 0), for several fuzzed coverage-preserving patterns."""
    import jax
    import jax.numpy as jnp

    from repro.core import (
        ResilienceSession,
        fractional_repetition_assignment,
        lloyd,
        resilient_cost,
    )

    pts = _pts(120, seed=11)
    a = fractional_repetition_assignment(120, 6, 2)
    centers = np.asarray(
        lloyd(jax.random.PRNGKey(2), jnp.asarray(pts), 3, iters=4).centers
    )
    sess = ResilienceSession(a)
    for seed in range(4):
        rng = np.random.default_rng(seed)
        alive = np.ones(6, dtype=bool)
        alive[rng.integers(0, 6)] = False
        if (a.matrix[alive].sum(axis=0) == 0).any():
            continue
        dev = sess.step_cost(pts, centers, alive)
        host = float(resilient_cost(pts, centers, a, alive, recovery_method="lp"))
        assert dev == pytest.approx(host, rel=1e-5), (seed, dev, host)
    assert sess.stats.host_solves == 0  # the fused path never host-solved


def test_step_weights_degenerate_pattern_falls_back_to_host():
    """Uncovered-shard patterns must fall back to the host solver's
    best-effort weights — covered shards keep their full mass; the device
    solver (which masks lost shards out of its objective) is not consulted."""
    from repro.train.resilient import make_plan

    plan = make_plan(6, 6, redundancy=1, scheme="singleton")
    alive = np.array([True, True, False, True, True, True])  # shard 2 lost
    sess = plan.session
    before = sess.stats.device_solves
    w = plan.step_weights(alive)
    assert sess.stats.device_solves == before, "device solver must be skipped"
    assert sess.stats.host_solves == 1
    a_ach = w.astype(np.float64) @ plan.current_assignment.matrix
    covered = plan.current_assignment.matrix[alive].sum(axis=0) > 0
    np.testing.assert_allclose(a_ach[covered], 1.0, atol=1e-7)  # mass preserved
    assert (a_ach[~covered] == 0).all()  # lost shard reported, not faked
    # Coverage-preserving patterns use the device path (no new host solves).
    plan2 = make_plan(6, 6, redundancy=2, scheme="fr")
    w2 = plan2.step_weights(np.array([True, False, True, True, True, True]))
    assert plan2.session.stats.host_solves == 0
    assert plan2.session.stats.device_solves == 1
    np.testing.assert_allclose(
        w2.astype(np.float64) @ plan2.current_assignment.matrix, 1.0, atol=1e-4
    )


def test_step_weights_follow_elastic_patch():
    """After the session patches the assignment, plan.step_weights must
    solve against the PATCHED matrix (the pattern that lost coverage before
    the patch becomes device-solvable after it)."""
    from repro.core import ElasticPolicy, ResilienceSession
    from repro.core.assignment import cyclic_assignment
    from repro.train.resilient import RedundantShardPlan

    a = cyclic_assignment(8, 8, 2)
    plan = RedundantShardPlan(
        assignment=a, num_groups=8,
        session=ResilienceSession(a, elastic=ElasticPolicy(enabled=True, patience=2)),
    )
    dead = np.ones(8, dtype=bool)
    dead[[6, 7]] = False  # adjacent cyclic nodes: shard coverage lost
    w0 = plan.step_weights(dead)  # host fallback (uncovered)
    assert plan.session.stats.host_solves == 1
    for _ in range(3):
        plan.session.observe(dead)
    assert plan.session.stats.elastic_patches >= 1
    assert plan.current_assignment is not plan.assignment
    w1 = plan.step_weights(dead)  # patched matrix covers everything → device
    assert plan.session.stats.device_solves == 1
    A_cur = plan.current_assignment.matrix
    assert not (A_cur[dead].sum(axis=0) == 0).any()
    np.testing.assert_allclose(w1.astype(np.float64) @ A_cur, 1.0, atol=1e-3)
    assert w1.shape == w0.shape == (8,)


# ----------------------------------------- satellite: shards_per_group guard


def test_shards_per_group_raises_on_unbalanced():
    """Regression: shards_per_group used to report loads[0] as if uniform —
    on an unbalanced assignment that mis-sizes every consumer.  It must
    raise a clear ValueError instead (max_load/group_load serve unbalanced
    plans)."""
    from repro.core.assignment import Assignment
    from repro.train.resilient import RedundantShardPlan, make_plan

    mat = np.zeros((3, 6), dtype=np.uint8)
    mat[0, :4] = 1   # load 4
    mat[1, 3:] = 1   # load 3
    mat[2, [0, 5]] = 1  # load 2
    plan = RedundantShardPlan(
        assignment=Assignment(matrix=mat, scheme="crafted", params={}),
        num_groups=3,
    )
    with pytest.raises(ValueError, match="load-balanced"):
        _ = plan.shards_per_group
    assert plan.max_load == 4
    assert [plan.group_load(g) for g in range(3)] == [4, 3, 2]
    # Balanced constructions keep the uniform answer.
    assert make_plan(4, 8, redundancy=2, scheme="cyclic").shards_per_group == 4


def test_elastic_reshard_plan_survives_unbalanced_loads():
    """The group-manager's takeover path produces unbalanced plans on
    purpose; plan construction must accept them (only shards_per_group
    raises) and the data pipeline keeps its construction-time shapes."""
    from repro.data.pipeline import RedundantDataPipeline
    from repro.train.elastic import ElasticGroupManager
    from repro.train.resilient import make_plan

    plan = make_plan(4, 8, redundancy=2, scheme="cyclic")
    pipe = RedundantDataPipeline(plan, vocab=64, microbatch=1, seq_len=8)
    shape_before = pipe.batch_shape
    mgr = ElasticGroupManager(plan)
    mgr.mark_dead(0)
    mgr.mark_dead(1)  # adjacent deaths → coverage lost → reshard
    assert mgr.reshard_count >= 1
    with pytest.raises(ValueError, match="load-balanced"):
        _ = mgr.plan.shards_per_group
    assert mgr.plan.max_load >= 2
    assert pipe.batch_shape == shape_before  # static shapes snapshotted


def test_session_owns_permanent_loss_and_reshard():
    """The permanent-loss/reshard machinery lives in ResilienceSession (the
    group manager is a facade): covered losses re-solve once, coverage loss
    reshards, listeners fire, and every pattern cache is dropped."""
    from repro.core.assignment import cyclic_assignment
    from repro.core.resilience import ResilienceSession

    sess = ResilienceSession(cyclic_assignment(8, 4, 2))
    events = []
    sess.add_patch_listener(lambda moved, om, nm: events.append((tuple(moved), om, nm)))

    res = sess.permanent_loss(3)
    assert sess.stats.reshards == 0 and len(res.uncovered) == 0
    assert sess.permanent_dead == {3}
    assert not sess.alive_mask()[3] and sess.alive_mask()[0]

    res2 = sess.permanent_loss(2)  # adjacent deaths → coverage lost
    assert sess.stats.reshards == 1
    assert len(res2.uncovered) == 0  # survivors cover everything again
    assert sess.assignment.scheme == "elastic_cyclic"
    assert events and len(events[0][0]) > 0  # listener saw the changed rows
    assert sess.version == 1
    # Dead rows hold nothing; survivors hold all 8 shards.
    m = sess.assignment.matrix
    assert m[2].sum() == 0 and m[3].sum() == 0
    assert (m[[0, 1]].sum(axis=0) > 0).all()
    assert sess.pattern_covers(sess.alive_mask())

    sess.permanent_join(3)  # warm takeover: no reshard on joins
    assert sess.permanent_dead == {2} and sess.stats.reshards == 1


# --------------------------------------- scenario-matrix conformance test


_SCENARIO_MATRIX = ("iid", "fixed", "adversarial", "deadline", "trace")


@pytest.mark.parametrize("kind", _SCENARIO_MATRIX)
def test_scenario_matrix_reset_replay_conformance(kind, tmp_path):
    """Every make_scenario kind obeys the iterator contract uniformly:
    deterministic given its construction args, reset() replays the exact
    stream (masks AND step indices), records own their masks, and mask
    shapes match the node count."""
    from repro.core import cyclic_assignment, make_scenario, record_trace

    s = 6
    kw = {}
    if kind in ("iid", "fixed", "deadline"):
        kw["seed"] = 5
    if kind == "iid":
        kw["p_straggler"] = 0.3
    if kind == "fixed":
        kw["t"] = 2
    if kind == "adversarial":
        kw["assignment"] = cyclic_assignment(24, s, 2)
        kw["t"] = 1
    if kind == "trace":
        path = str(tmp_path / "conformance.jsonl")
        src = make_scenario("deadline", s, seed=9, p_spike=0.4)
        record_trace(src, 7, path)
        kw["path"] = path

    scen = make_scenario(kind, s, **kw)
    twin = make_scenario(kind, s, **kw)
    first = [next(scen) for _ in range(7)]
    for i, rec in enumerate(first):
        assert rec.alive.shape == (s,) and rec.alive.dtype == bool
        assert rec.index == i
    # Same construction args → identical stream (cross-instance determinism).
    for r1, r2 in zip(first, [next(twin) for _ in range(7)]):
        np.testing.assert_array_equal(r1.alive, r2.alive)
        np.testing.assert_allclose(r1.latencies, r2.latencies)
    # Records own their masks: corrupting one must not perturb the stream.
    first[3].alive[:] = ~first[3].alive
    scen.reset()
    again = [next(scen) for _ in range(7)]
    for i, (r1, r2) in enumerate(zip(first, again)):
        if i == 3:
            np.testing.assert_array_equal(~r1.alive, r2.alive)
        else:
            np.testing.assert_array_equal(r1.alive, r2.alive)
        assert r1.index == r2.index


def test_scenario_trace_roundtrip_equality(tmp_path):
    """record_trace → make_scenario("trace") reproduces EVERY source kind's
    mask stream exactly (the conformance matrix's round-trip leg)."""
    from repro.core import cyclic_assignment, make_scenario, record_trace

    s = 5
    sources = {
        "iid": {"p_straggler": 0.25, "seed": 3},
        "fixed": {"t": 1, "seed": 3},
        "adversarial": {"assignment": cyclic_assignment(20, s, 2), "t": 2},
        "deadline": {"seed": 3, "p_spike": 0.3},
    }
    for name, kw in sources.items():
        path = str(tmp_path / f"{name}.jsonl")
        src = make_scenario(name, s, **kw)
        assert record_trace(src, 5, path) == 5
        src.reset()
        replay = make_scenario("trace", s, path=path)
        for _ in range(5):
            want, got = next(src), next(replay)
            np.testing.assert_array_equal(got.alive, want.alive, err_msg=name)
            if want.latencies.size:
                np.testing.assert_allclose(got.latencies, want.latencies)
