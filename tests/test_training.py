"""Training substrate tests: resilient gradient recovery, checkpoint/restart,
gradient compression, elastic regrouping, end-to-end loss descent."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.qwen3_4b import smoke_config
from repro.core.recovery import lp_recovery
from repro.data.pipeline import RedundantDataPipeline
from repro.models import transformer as T
from repro.train.checkpoint import (
    latest_step,
    list_checkpoints,
    restore_checkpoint,
    save_checkpoint,
)
from repro.train.compression import (
    CompressionConfig,
    compress_with_error_feedback,
    dequantize_int8,
    init_ef_state,
    quantize_int8,
)
from repro.train.elastic import ElasticGroupManager
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state
from repro.train.resilient import make_plan
from repro.train.trainer import Trainer, TrainerConfig
from repro.train.train_step import init_train_state, make_train_step

pytestmark = pytest.mark.slow  # model-zoo compile-heavy; run via `make test-all`


@pytest.fixture(scope="module")
def cfg():
    return smoke_config().validate()


def _grads(params, batch, cfg):
    ctx = T.ModelContext()
    return jax.grad(lambda p: T.loss_fn(p, batch, cfg, ctx)[0])(params)


def _tree_allclose(a, b, rtol=1e-4, atol=1e-5):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32), rtol=rtol, atol=atol
        )


# ------------------------------------------------------- recovery on grads


def test_fr_plan_exact_gradient_recovery(cfg):
    """THE core claim applied to training: with the FR assignment (δ=0) the
    b-weighted gradient under stragglers EQUALS the full-data gradient of the
    unique batch, exactly (up to fp tolerance)."""
    G, S = 4, 4
    plan = make_plan(G, S, redundancy=2, scheme="fr")
    pipe = RedundantDataPipeline(plan, vocab=cfg.vocab, microbatch=1, seq_len=32)
    params = T.init_params(jax.random.PRNGKey(0), cfg)

    # Full-data gradient: every shard once, uniform weights.
    uniq = jnp.asarray(pipe.unique_batch(0))
    full = _grads(params, {"tokens": uniq}, cfg)

    # Straggler pattern killing one group; FR with ell=2 survives.
    alive = np.array([True, False, True, True])
    w, rec = plan.group_weights(alive)
    assert rec.feasible and rec.delta <= 1e-9
    batch = {"tokens": jnp.asarray(pipe.batch(0)), "group_weights": jnp.asarray(w)}
    resilient = _grads(params, batch, cfg)
    _tree_allclose(full, resilient, rtol=2e-3, atol=2e-4)


def test_singleton_plan_loses_gradient_information(cfg):
    """Counterfactual: without redundancy the straggler's shards vanish — the
    gradient measurably differs from the full-data gradient."""
    G, S = 4, 4
    plan = make_plan(G, S, redundancy=1, scheme="singleton")
    pipe = RedundantDataPipeline(plan, vocab=cfg.vocab, microbatch=1, seq_len=32)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    uniq = jnp.asarray(pipe.unique_batch(0))
    full = _grads(params, {"tokens": uniq}, cfg)
    alive = np.array([True, False, True, True])
    w = plan.degraded_weights(alive)
    batch = {"tokens": jnp.asarray(pipe.batch(0)), "group_weights": jnp.asarray(w)}
    lossy = _grads(params, batch, cfg)
    diffs = [
        float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
        for a, b in zip(jax.tree_util.tree_leaves(full), jax.tree_util.tree_leaves(lossy))
    ]
    assert max(diffs) > 1e-4


def test_cyclic_plan_bounded_distortion(cfg):
    """Cyclic assignment under 1 straggler: recovered gradient within the
    (1+δ) reweighting band of the full gradient — cosine similarity high."""
    G, S = 6, 6
    plan = make_plan(G, S, redundancy=3, scheme="cyclic")
    pipe = RedundantDataPipeline(plan, vocab=cfg.vocab, microbatch=1, seq_len=32)
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    uniq = jnp.asarray(pipe.unique_batch(0))
    full = _grads(params, {"tokens": uniq}, cfg)
    alive = np.ones(G, dtype=bool)
    alive[2] = False
    w, rec = plan.group_weights(alive)
    assert rec.feasible
    batch = {"tokens": jnp.asarray(pipe.batch(0)), "group_weights": jnp.asarray(w)}
    resilient = _grads(params, batch, cfg)
    fv = jnp.concatenate([g.astype(jnp.float32).ravel() for g in jax.tree_util.tree_leaves(full)])
    rv = jnp.concatenate([g.astype(jnp.float32).ravel() for g in jax.tree_util.tree_leaves(resilient)])
    cos = float(fv @ rv / (jnp.linalg.norm(fv) * jnp.linalg.norm(rv)))
    assert cos > 0.99


def test_pipeline_replicas_bit_identical(cfg):
    plan = make_plan(4, 4, redundancy=2, scheme="cyclic")
    pipe = RedundantDataPipeline(plan, vocab=256, microbatch=2, seq_len=16)
    b = pipe.batch(3)
    # shard s appears in groups s and (s-1) mod 4 (cyclic ell=2).
    g0 = b[: 2 * 2]  # group 0's shards: 0, 3 → rows [shard0, shard3]
    shards0 = plan.group_shards(0)
    for g in range(1, 4):
        shared = np.intersect1d(shards0, plan.group_shards(g))
        for s in shared:
            i0 = list(shards0).index(s)
            ig = list(plan.group_shards(g)).index(s)
            a = b[0 * 4 + i0 * 2 : 0 * 4 + i0 * 2 + 2]
            c = b[g * 4 + ig * 2 : g * 4 + ig * 2 + 2]
            np.testing.assert_array_equal(a, c)


# ------------------------------------------------------------- optimizer


def test_adamw_descends_quadratic():
    cfg_o = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=100, weight_decay=0.0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = init_opt_state(params)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(cfg_o, params, grads, state)
    assert float(jnp.abs(params["w"]).max()) < 0.5


def test_grad_clip_caps_update_norm():
    cfg_o = AdamWConfig(lr=1e-3, grad_clip=1.0, warmup_steps=0, total_steps=10)
    params = {"w": jnp.zeros(4)}
    state = init_opt_state(params)
    _, _, m = adamw_update(cfg_o, params, {"w": jnp.full(4, 1e6)}, state)
    assert float(m["grad_norm"]) > 1e5  # reported pre-clip


# ------------------------------------------------------------ checkpoint


def test_checkpoint_roundtrip(cfg):
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 7, state)
        template = init_train_state(jax.random.PRNGKey(42), cfg)  # different init
        restored, step = restore_checkpoint(d, template)
        assert step == 7
        _tree_allclose(state.params, restored.params, rtol=0, atol=0)


def test_checkpoint_rotation_and_latest(cfg):
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    with tempfile.TemporaryDirectory() as d:
        for s in (5, 10, 15, 20):
            save_checkpoint(d, s, state, keep=2)
        assert list_checkpoints(d) == [15, 20]
        assert latest_step(d) == 20


def test_interrupt_resume_trajectory_equivalence(cfg):
    """Kill after step 6, resume from the step-5 checkpoint: the final state
    must match an uninterrupted run bit-for-bit at matching data order —
    checkpoint/restart is lossless (stragglers disabled for determinism)."""
    with tempfile.TemporaryDirectory() as d:
        tc = TrainerConfig(
            num_groups=4, num_shards=4, redundancy=2, microbatch=1, seq_len=32,
            steps=10, ckpt_every=5, ckpt_dir=d, simulate_stragglers=False,
        )
        oc = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
        # Uninterrupted run.
        t1 = Trainer(cfg, tc, oc)
        final1 = t1.run()
        # Interrupted: run to step 5 (ckpt), new trainer resumes.
        with tempfile.TemporaryDirectory() as d2:
            tc2_a = TrainerConfig(**{**tc.__dict__, "steps": 5, "ckpt_dir": d2})
            Trainer(cfg, tc2_a, oc).run()
            tc2_b = TrainerConfig(**{**tc.__dict__, "steps": 10, "ckpt_dir": d2})
            t2 = Trainer(cfg, tc2_b, oc)
            final2 = t2.run()
        _tree_allclose(final1.params, final2.params, rtol=1e-5, atol=1e-6)


def test_trainer_warm_start_is_invisible_to_the_trajectory(cfg, monkeypatch):
    """run() pre-compiles the step with one discarded all-alive step: the
    report must record it, session/elastic stats must not see it, and the
    resulting trajectory must be bit-identical to a warm-start-less run."""
    monkeypatch.delenv("REPRO_WARM_START", raising=False)
    tc = TrainerConfig(
        num_groups=4, num_shards=4, redundancy=2, microbatch=1, seq_len=32,
        steps=3, simulate_stragglers=False,
    )
    oc = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=3)
    t_warm = Trainer(cfg, tc, oc)
    assert t_warm.warmup_report is None
    final_warm = t_warm.run()
    rep = t_warm.warmup_report
    assert rep is not None and rep.warmed == 1 and rep.errors == 0
    assert len(t_warm.history) == 3, "the warm-up step must not enter history"

    t_cold = Trainer(cfg, TrainerConfig(**{**tc.__dict__, "warm_start": False}), oc)
    final_cold = t_cold.run()
    assert t_cold.warmup_report is None
    _tree_allclose(final_warm.params, final_cold.params, rtol=0, atol=0)

    # The env opt-out beats the config default.
    monkeypatch.setenv("REPRO_WARM_START", "0")
    t_off = Trainer(cfg, tc, oc)
    t_off.run()
    assert t_off.warmup_report is None


# ----------------------------------------------------------- compression


def test_int8_quantization_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 300)), jnp.float32)
    q, s, n = quantize_int8(x, block=128)
    x2 = dequantize_int8(q, s, n)
    err = np.abs(np.asarray(x2) - np.asarray(x))
    bound = np.asarray(s).max()  # ≤ one quantization bin
    assert err.max() <= bound + 1e-6


def test_error_feedback_accumulates_residual():
    ccfg = CompressionConfig(block=64)
    grads = {"w": jnp.full((8, 64), 1e-4)}
    ef = init_ef_state(grads)
    out1, ef1 = compress_with_error_feedback(ccfg, grads, ef)
    # Second application re-injects the residual; cumulative transmitted mass
    # approaches the true mass.
    out2, ef2 = compress_with_error_feedback(ccfg, grads, ef1)
    total_sent = np.asarray(out1["w"] + out2["w"]).sum()
    total_true = 2 * np.asarray(grads["w"]).sum()
    assert abs(total_sent - total_true) <= abs(np.asarray(ef2["w"]).sum()) + 1e-3


def test_training_with_compression_descends(cfg):
    tc = TrainerConfig(
        num_groups=4, num_shards=4, redundancy=2, microbatch=2, seq_len=48,
        steps=30, simulate_stragglers=False, compression=CompressionConfig(block=128),
    )
    t = Trainer(cfg, tc, AdamWConfig(lr=5e-3, warmup_steps=3, total_steps=30))
    t.run()
    losses = [h["loss"] for h in t.history]
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


# -------------------------------------------------------------- elastic


def test_elastic_transient_vs_permanent():
    plan = make_plan(6, 6, redundancy=2, scheme="cyclic")
    mgr = ElasticGroupManager(plan)
    w, rec = mgr.step_weights(np.array([False, True, False, False, False, False]))
    assert w[1] == 0 and rec.feasible  # transient straggler handled by b
    mgr.mark_dead(3)
    w2, rec2 = mgr.step_weights()
    assert w2[3] == 0 and rec2.feasible  # ell=2 covers one permanent death
    assert mgr.reshard_count == 0


def test_elastic_reshard_on_coverage_loss():
    plan = make_plan(4, 8, redundancy=2, scheme="cyclic")
    mgr = ElasticGroupManager(plan)
    # Kill two ADJACENT groups: cyclic ell=2 loses the shards they shared.
    mgr.mark_dead(0)
    mgr.mark_dead(1)
    assert mgr.reshard_count >= 1  # coverage lost → re-shard happened
    w, rec = mgr.step_weights()
    assert len(rec.uncovered) == 0  # survivors now cover everything


# ------------------------------------------------------------ end-to-end


def test_training_under_stragglers_descends(cfg):
    tc = TrainerConfig(
        num_groups=4, num_shards=4, redundancy=2, microbatch=2, seq_len=48,
        steps=40, simulate_stragglers=True, straggler_deadline=1.6,
    )
    t = Trainer(cfg, tc, AdamWConfig(lr=5e-3, warmup_steps=4, total_steps=40))
    t.run()
    losses = [h["loss"] for h in t.history if "loss" in h]
    straggled = sum(h.get("stragglers", 0) > 0 for h in t.history)
    assert straggled > 0  # the simulator actually fired
    assert np.mean(losses[-8:]) < np.mean(losses[:8]) - 0.01


# ------------------------------------- mesh-native on-device recovery path


def test_device_recovery_bit_matches_clean_run_fr(cfg):
    """THE tentpole claim at trainer level: with FR (δ = 0) the fused
    compiled-step path (recovery PGD over the runtime alive mask INSIDE the
    step) produces the SAME parameter trajectory under a coverage-preserving
    straggler pattern as with no stragglers — with zero host solves."""
    import json

    def run(trace_rows, tmpdir):
        path = os.path.join(tmpdir, "trace.jsonl")
        with open(path, "w") as f:
            for row in trace_rows:
                f.write(json.dumps({"alive": row}) + "\n")
        tc = TrainerConfig(
            num_groups=4, num_shards=4, redundancy=2, scheme="fr",
            microbatch=1, seq_len=32, steps=5, simulate_stragglers=True,
            straggler_scenario="trace", scenario_kwargs={"path": path},
            device_recovery=True, resident_steps=2,
        )
        t = Trainer(cfg, tc, AdamWConfig(lr=5e-3, warmup_steps=2, total_steps=5))
        return t, t.run()

    with tempfile.TemporaryDirectory() as d:
        t_clean, s_clean = run([[1, 1, 1, 1]] * 5, d)
        t_strag, s_strag = run([[1, 0, 1, 1]] * 5, d)
    _tree_allclose(s_clean.params, s_strag.params, rtol=1e-5, atol=1e-6)
    for t in (t_clean, t_strag):
        assert t.plan.session.stats.host_solves == 0
        assert t.plan.session.stats.device_solves == 5
    assert all(h["stragglers"] == 1 for h in t_strag.history)
    assert not any(h.get("fallback") for h in t_strag.history)


def test_device_recovery_no_recompile_across_patterns(cfg):
    """Unseen straggler patterns are runtime data: after the first compiled
    step, new masks must not add jit-cache entries (zero re-lowers)."""
    tc = TrainerConfig(
        num_groups=4, num_shards=4, redundancy=2, scheme="fr",
        microbatch=1, seq_len=32, steps=5, simulate_stragglers=True,
        straggler_scenario="fixed", scenario_kwargs={"t": 1},
        device_recovery=True, resident_steps=2,
    )
    t = Trainer(cfg, tc, AdamWConfig(lr=5e-3, warmup_steps=2, total_steps=5))
    state, start = t.init_state()
    srec = next(t.scenario)
    state, _ = t._device_recovery_step(state, 0, srec.alive)
    ex = t.plan.session.executor
    n_compiled = len(ex._jitted)
    patterns = set()
    for step in range(1, 5):
        srec = next(t.scenario)
        patterns.add(srec.alive.tobytes())
        state, rec = t._device_recovery_step(state, step, srec.alive)
        assert rec is not None
    assert len(patterns) > 1, "scenario never varied the pattern"
    assert len(ex._jitted) == n_compiled, "a new pattern re-lowered the step"
    assert t.plan.session.stats.host_solves == 0


def test_device_recovery_run_consumes_the_passed_state(cfg):
    """On the fused path the update donates its state: ``run(state)`` deletes
    the caller's buffers (the warm-up does not), and the returned state is
    the one to keep."""
    tc = TrainerConfig(
        num_groups=4, num_shards=4, redundancy=2, scheme="fr",
        microbatch=1, seq_len=32, steps=2, simulate_stragglers=False,
        device_recovery=True, resident_steps=2,
    )
    t = Trainer(cfg, tc, AdamWConfig(lr=5e-3, warmup_steps=2, total_steps=2))
    state, _ = t.init_state()
    leaf = jax.tree_util.tree_leaves(state.params)[0]
    t.warmup(state)
    assert not leaf.is_deleted(), "the warm-up consumed the caller's state"
    out = t.run(state)
    assert leaf.is_deleted(), "run() kept the donated state alive"
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree_util.tree_leaves(out.params))


def test_device_recovery_degenerate_pattern_falls_back(cfg):
    """A pattern that loses a shard entirely (singleton scheme, one dead
    group) must take the host best-effort path — the step still applies an
    update from the surviving shards' mass instead of silently training on
    device-dropped weights."""
    import json

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "trace.jsonl")
        with open(path, "w") as f:
            for _ in range(3):
                f.write(json.dumps({"alive": [1, 0, 1, 1]}) + "\n")
        tc = TrainerConfig(
            num_groups=4, num_shards=4, redundancy=1, scheme="singleton",
            microbatch=1, seq_len=32, steps=3, simulate_stragglers=True,
            straggler_scenario="trace", scenario_kwargs={"path": path},
            device_recovery=True, resident_steps=1,
        )
        t = Trainer(cfg, tc, AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=3))
        t.run()
    assert all(h.get("fallback") for h in t.history)
    sess = t.plan.session.stats
    assert sess.host_solves == 1          # one pattern, cached after that
    assert sess.device_solves == 0
    assert all("loss" in h for h in t.history)  # training continued


def test_device_recovery_elastic_patch_moves_only_changed_blocks(cfg):
    """Persistent stragglers → ElasticPolicy patch → the trainer re-packs
    ONLY the moved groups' resident token blocks (update_node_rows), the
    recovered path returns to the device solver, and coverage is restored."""
    import json

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "trace.jsonl")
        with open(path, "w") as f:
            for _ in range(8):
                f.write(json.dumps({"alive": [1, 1, 1, 1, 0, 0]}) + "\n")
        tc = TrainerConfig(
            num_groups=6, num_shards=6, redundancy=2, scheme="cyclic",
            microbatch=1, seq_len=32, steps=6, simulate_stragglers=True,
            straggler_scenario="trace", scenario_kwargs={"path": path},
            device_recovery=True, elastic_patience=2, patch_headroom=2,
            resident_steps=2,
        )
        t = Trainer(cfg, tc, AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=6))
        t.run()
    s = t.plan.session.stats
    assert s.elastic_patches >= 1
    assert s.moved_node_blocks >= 1, "incremental re-place did not run"
    assert s.full_repacks == 0, "patch should fit inside the headroom"
    # Pre-patch the pattern is uncovered (host fallback); post-patch the
    # device path serves it with zero uncovered shards.
    assert t.history[0]["fallback"] is True
    assert t.history[-1]["fallback"] is False
    A = t.plan.current_assignment.matrix
    alive = np.array([1, 1, 1, 1, 0, 0], dtype=bool)
    assert int((A[alive].sum(axis=0) == 0).sum()) == 0
    # Resident validity mask reflects the patched membership: some healthy
    # group now holds more shards than its original load.
    valid = np.asarray(t._res_valid)[: t.plan.num_groups]
    assert valid.sum() > t.tcfg.num_shards * t.tcfg.redundancy - 1


def test_device_recovery_descends_under_stragglers(cfg):
    tc = TrainerConfig(
        num_groups=4, num_shards=4, redundancy=2, scheme="fr",
        microbatch=2, seq_len=48, steps=30, simulate_stragglers=True,
        straggler_deadline=1.6, device_recovery=True, resident_steps=4,
    )
    t = Trainer(cfg, tc, AdamWConfig(lr=5e-3, warmup_steps=3, total_steps=30))
    t.run()
    losses = [h["loss"] for h in t.history if "loss" in h]
    straggled = sum(h.get("stragglers", 0) > 0 for h in t.history)
    assert straggled > 0
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    # Coverage-preserving rounds never host-solve; rounds where BOTH replicas
    # of a shard straggled legitimately take the best-effort host fallback.
    s = t.plan.session.stats
    fallbacks = sum(bool(h.get("fallback")) for h in t.history)
    assert s.host_solves <= max(fallbacks, s.uncovered_rounds)
    assert s.device_solves == len(losses) - fallbacks


# --------------------------------------- acceptance: 8-device mesh training


def test_mesh_training_8_devices_parity_and_patching():
    """ISSUE-5 acceptance: an 8-forced-host-device MESH training run under a
    straggler scenario — recovered-gradient parity ≤ 1e-5 against the
    no-straggler run for coverage-preserving patterns, host_solves == 0
    after warmup, and zero uncovered shards after an elastic patch with only
    the moved blocks re-placed (SessionStats counters)."""
    import subprocess
    import sys
    import textwrap

    code = textwrap.dedent(
        """
        import os, json, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        assert jax.device_count() == 8
        from repro.configs.qwen3_4b import smoke_config
        from repro.train.trainer import Trainer, TrainerConfig
        from repro.train.optimizer import AdamWConfig

        cfg = smoke_config().validate()
        tmpdir = tempfile.TemporaryDirectory()
        def leaves(tree):
            return [np.asarray(l, np.float32) for l in jax.tree_util.tree_leaves(tree)]

        def trace(name, rows):
            path = os.path.join(tmpdir.name, name + ".jsonl")
            with open(path, "w") as f:
                for r in rows:
                    f.write(json.dumps({"alive": r}) + "\\n")
            return path

        def run(rows, **kw):
            path = trace("run%d" % len(os.listdir(tmpdir.name)), rows)
            tc = TrainerConfig(
                num_groups=8, num_shards=8, redundancy=2, scheme="fr",
                microbatch=1, seq_len=32, steps=4, simulate_stragglers=True,
                straggler_scenario="trace", scenario_kwargs={"path": path},
                device_recovery=True, executor="mesh", resident_steps=2, **kw)
            t = Trainer(cfg, tc, AdamWConfig(lr=5e-3, warmup_steps=2, total_steps=4))
            return t, t.run()

        # (1) gradient/trajectory parity: coverage-preserving FR pattern vs clean.
        t_clean, s_clean = run([[1]*8]*4)
        t_strag, s_strag = run([[1,1,0,1,1,1,1,1]]*4)
        for a, b in zip(leaves(s_clean.params), leaves(s_strag.params)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        assert t_clean.plan.session.stats.host_solves == 0
        assert t_strag.plan.session.stats.host_solves == 0
        assert t_strag.plan.session.stats.device_solves == 4

        # (2) elastic patch on mesh: persistent adjacent deaths (cyclic) →
        # re-replication, only moved blocks placed, coverage restored, and
        # the post-patch steps stay on the device path (no host solves
        # beyond the pre-patch degenerate fallback).
        path = trace("patch", [[1,1,1,1,1,1,0,0]] * 8)
        tc = TrainerConfig(
            num_groups=8, num_shards=8, redundancy=2, scheme="cyclic",
            microbatch=1, seq_len=32, steps=6, simulate_stragglers=True,
            straggler_scenario="trace", scenario_kwargs={"path": path},
            device_recovery=True, executor="mesh", elastic_patience=2,
            patch_headroom=2, resident_steps=2)
        t = Trainer(cfg, tc, AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=6))
        t.run()
        s = t.plan.session.stats
        assert s.elastic_patches >= 1, s.as_dict()
        assert s.moved_node_blocks >= 1, s.as_dict()
        assert s.full_repacks == 0, s.as_dict()
        A = t.plan.current_assignment.matrix
        alive = np.array([1,1,1,1,1,1,0,0], dtype=bool)
        assert int((A[alive].sum(axis=0) == 0).sum()) == 0
        assert t.history[-1]["fallback"] is False
        post_patch = [h for h in t.history if h.get("patches", 0) >= 1 and not h["fallback"]]
        assert post_patch and all(h["host_solves"] == s.host_solves for h in post_patch[-1:])

        # (3) regression: the degenerate host-fallback path on a mesh whose
        # device count does NOT divide G (resident blocks padded 4 -> 8)
        # must align the weight vector with the padded node axis, not crash.
        path = trace("degenerate", [[1,0,1,1]] * 3)
        tc = TrainerConfig(
            num_groups=4, num_shards=4, redundancy=1, scheme="singleton",
            microbatch=1, seq_len=32, steps=3, simulate_stragglers=True,
            straggler_scenario="trace", scenario_kwargs={"path": path},
            device_recovery=True, executor="mesh", resident_steps=1)
        t = Trainer(cfg, tc, AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=3))
        t.run()
        assert all(h.get("fallback") for h in t.history)
        assert all("loss" in h for h in t.history)
        assert t.plan.session.stats.host_solves == 1  # one pattern, cached
        tmpdir.cleanup()
        print("MESH_TRAIN_ACCEPTANCE_OK")
        """
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=540, env=env,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "MESH_TRAIN_ACCEPTANCE_OK" in out.stdout
