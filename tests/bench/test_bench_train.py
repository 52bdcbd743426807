"""The resilient training cell at a tiny size on the CPU: a sound run is
correct; the control, a fault planted in the reference, and each fault
planted in the program come out not correct."""

import pytest

CELL = "train.qwen3_1_7b.fr4"
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture
def fresh_step_cache():
    import repro.train.trainer as tr

    tr._jitted_apply_fn.cache_clear()
    yield
    tr._jitted_apply_fn.cache_clear()


def test_sound_run_line(run_tiny):
    line = run_tiny(CELL)
    assert list(line) == KEYS
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0


def test_control_and_half_batch_are_not_correct(readings_tiny):
    got, limits = readings_tiny(CELL)
    assert all(got["sound"][k] <= v for k, v in limits.items()), got
    assert any(got["control"][k] > v for k, v in limits.items()), got
    assert any(got["half_batch"][k] > v for k, v in limits.items())


def test_change_norm_sums_in_float64():
    """A leaf's change is as long as hundreds of millions of elements; its
    norm is read with the squares summed in float64, where a float32 sum
    reads low."""
    import numpy as np
    import harness

    ref = harness.config_module("qwen3_1_7b")
    a = np.random.default_rng(0).standard_normal(1 << 24, dtype=np.float32)
    b = np.zeros_like(a)
    exact = float(np.sqrt(np.sum(np.square(a.astype(np.float64)))))
    assert abs(ref.host_diff_norm(a, b) / exact - 1) < 1e-9


def test_state_returned_unchanged(run_tiny, monkeypatch, fresh_step_cache):
    import repro.train.trainer as tr

    make = tr.make_recovered_apply_fn

    def unchanged(*a, **kw):
        apply = make(*a, **kw)

        def step(state, stats):
            _, metrics = apply(state, stats)
            return state, metrics

        return step

    monkeypatch.setattr(tr, "make_recovered_apply_fn", unchanged)
    assert run_tiny(CELL)["correct"] is False


def test_half_of_each_sequence_left_out(run_tiny, monkeypatch):
    import repro.train.trainer as tr

    make = tr.make_group_grad_fn

    def half(cfg, ctx):
        stats = make(cfg, ctx)

        def group(tokens_pool, valid, params, pool_idx):
            return stats(tokens_pool[..., : tokens_pool.shape[-1] // 2], valid, params, pool_idx)

        return group

    monkeypatch.setattr(tr, "make_group_grad_fn", half)
    assert run_tiny(CELL)["correct"] is False


def test_loss_altered(run_tiny, monkeypatch, fresh_step_cache):
    import repro.train.trainer as tr

    make = tr.make_recovered_apply_fn

    def altered(*a, **kw):
        apply = make(*a, **kw)

        def step(state, stats):
            state, metrics = apply(state, stats)
            return state, dict(metrics, loss=metrics["loss"] * 1.05)

        return step

    monkeypatch.setattr(tr, "make_recovered_apply_fn", altered)
    assert run_tiny(CELL)["correct"] is False
