"""The Algorithm 1 cell at a tiny size on the CPU: a sound run is correct,
and the control and each fault the cell can have come out not correct."""

import numpy as np

CELL = "alg1.sift128_k512.deadline"
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def test_sound_run_line(run_tiny):
    line = run_tiny(CELL)
    assert list(line) == KEYS
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"solve_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"


def test_control_is_not_correct(readings_tiny):
    got, limits = readings_tiny(CELL)
    assert all(got["sound"][k] <= v for k, v in limits.items()), got
    assert any(got["control"][k] > v for k, v in limits.items()), got


def test_recovery_broken_is_not_correct(readings_tiny):
    got, limits = readings_tiny(CELL)
    assert got["recovery"]["weight_gap"] > limits["weight_gap"], got


def test_cluster_sizes_altered(run_tiny, monkeypatch):
    import jax.numpy as jnp

    import repro.core.kmedian as km

    make = km._local_solve_fn

    def altered(*a):
        one = make(*a)

        def shifted(key, x, w, b):
            centers, wts = one(key, x, w, b)
            return centers, jnp.roll(wts, 1)  # each worker's total unchanged

        return shifted

    monkeypatch.setattr(km, "_local_solve_fn", altered)
    line = run_tiny(CELL)
    assert line["correct"] is False
    assert line["checks"]["size_gap"]["value"] > line["checks"]["size_gap"]["limit"]


def test_cost_altered(run_tiny, monkeypatch):
    import repro.core.kmedian as km

    pipeline = km._coordinator_pipeline

    def altered(*a, **kw):
        centers, cost, y, wy = pipeline(*a, **kw)
        return centers, cost * 1.001, y, wy

    monkeypatch.setattr(km, "_coordinator_pipeline", altered)
    line = run_tiny(CELL)
    assert line["correct"] is False
    assert line["checks"]["recost_gap"]["value"] > line["checks"]["recost_gap"]["limit"]


def test_half_of_each_shard_left_out(run_tiny, monkeypatch):
    import repro.core.kmedian as km

    pack = km.pack_local_shards

    def half(points, assignment):
        xs, ws = pack(points, assignment)
        ws[:, ws.shape[1] // 2:] = 0.0  # the mean is taken over the rest
        return xs, ws

    monkeypatch.setattr(km, "pack_local_shards", half)
    assert run_tiny(CELL)["correct"] is False


def test_answer_altered(run_tiny, monkeypatch):
    import repro.core.kmedian as km

    pipeline = km._coordinator_pipeline

    def altered(*a, **kw):
        centers, cost, y, wy = pipeline(*a, **kw)
        return centers + np.float32(1.0), cost, y, wy

    monkeypatch.setattr(km, "_coordinator_pipeline", altered)
    assert run_tiny(CELL)["correct"] is False
