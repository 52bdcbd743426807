"""Measured-first autotune: opt-out semantics, budget, tiebreakers, warmup.

Complements ``test_autotune_persist.py`` (disk lifecycle) and
``test_dispatch.py`` (defer-under-trace): these pin the SELECTION semantics
— measured-first is the default, the analytic model is only a prior, the
baseline (ref) wins back any pick without a measured win, the per-bucket
budget truncates gracefully — and the ``warmup`` API all three tiers share.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune, dispatch
from repro.kernels.pairwise_dist import ops as pd


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune.AUTOTUNE_CACHE_ENV, str(tmp_path / "cache"))
    dispatch.clear_autotune_cache()
    yield
    dispatch.clear_autotune_cache()


# ------------------------------------------------------- opt-out semantics


def test_measured_first_is_the_opt_out_default(monkeypatch):
    monkeypatch.delenv(autotune.AUTOTUNE_ENV, raising=False)
    assert autotune.autotune_enabled(), "unset env must mean measured-first ON"
    for off in ("0", "off", "False", "NO", "none", "model", "analytic"):
        monkeypatch.setenv(autotune.AUTOTUNE_ENV, off)
        assert not autotune.autotune_enabled(), off
    for on in ("1", "on", "measured", "yes"):
        monkeypatch.setenv(autotune.AUTOTUNE_ENV, on)
        assert autotune.autotune_enabled(), on


def test_warm_start_is_the_opt_out_default(monkeypatch):
    monkeypatch.delenv(autotune.WARM_START_ENV, raising=False)
    assert autotune.warm_start_enabled()
    monkeypatch.setenv(autotune.WARM_START_ENV, "0")
    assert not autotune.warm_start_enabled()


def test_env_knobs_parse_with_garbage_tolerance(monkeypatch):
    monkeypatch.setenv(autotune.TRIALS_ENV, "7")
    assert autotune.measure_trials() == 7
    monkeypatch.setenv(autotune.TRIALS_ENV, "0")
    assert autotune.measure_trials() == 1, "at least one timed rep"
    monkeypatch.setenv(autotune.TRIALS_ENV, "not-a-number")
    assert autotune.measure_trials() == autotune.DEFAULT_TRIALS
    monkeypatch.setenv(autotune.BUDGET_ENV, "2500")
    assert autotune.measure_budget_s() == pytest.approx(2.5)
    monkeypatch.setenv(autotune.NOISE_ENV, "0.25")
    assert autotune.noise_rel() == pytest.approx(0.25)
    monkeypatch.setenv(autotune.MIN_BYTES_ENV, "64")
    assert autotune.worth_measuring(64) and not autotune.worth_measuring(63)
    monkeypatch.delenv(autotune.MIN_BYTES_ENV, raising=False)
    assert not autotune.worth_measuring(autotune.DEFAULT_MIN_BYTES - 1)


# ----------------------------------------------------- measurement policy


def test_budget_truncation_keeps_the_calibrated_prior(monkeypatch):
    """A zero budget still measures the FIRST candidate (the analytic
    default), then stops: the prior ends up calibrated, later candidates
    never get the chance to displace it, and the stop is counted."""
    monkeypatch.setenv(autotune.AUTOTUNE_ENV, "1")
    monkeypatch.setenv(autotune.BUDGET_ENV, "0")
    benched = []
    cands = [dispatch.BlockConfig(0, b) for b in (32, 64, 128)]

    def bench(cfg):
        benched.append(cfg.bk)
        return lambda: None

    got = autotune.tuned_block_config(
        "budget_op", (4000, 64), jnp.float32,
        default=cands[0], candidates=cands, bench=bench,
    )
    assert got == cands[0]
    assert benched == [32], "only the default fits a zero budget"
    info = dispatch.autotune_cache_info()
    assert info["budget_stops"] == 1 and info["measured"] == 1
    # The truncated pass still caches: the bucket does not re-measure.
    benched.clear()
    again = autotune.tuned_block_config(
        "budget_op", (4000, 64), jnp.float32,
        default=cands[0], candidates=cands, bench=bench,
    )
    assert again == got and benched == []


def _controlled_times(table):
    """Patchable _measure_pass: every candidate 'measures' its table time."""
    def fake(ordered, bench):
        return {cand: table[cand] for cand in ordered if cand in table}
    return fake


def test_noise_floor_keeps_the_prior_seat(monkeypatch):
    monkeypatch.setenv(autotune.AUTOTUNE_ENV, "1")
    monkeypatch.setattr(
        autotune, "_measure_pass",
        _controlled_times({"xla_broadcast": 1.00, "xla_chunked": 0.95}),
    )
    got = autotune.tuned_strategy(
        "noise_op", (4096, 512, 64), jnp.float32, default="xla_broadcast",
        candidates=("xla_broadcast", "xla_chunked"), bench=lambda n: (lambda: None),
    )
    assert got == "xla_broadcast", "a 5% edge is below the 10% noise floor"


def test_baseline_wins_back_picks_without_a_measured_win(monkeypatch):
    """The attention regression class: a streaming rung that does NOT beat
    ref past the noise floor must resolve to ref, even when the analytic
    prior suggested the streaming rung."""
    monkeypatch.setenv(autotune.AUTOTUNE_ENV, "1")
    # In-memory discipline only: the disk cache would rehydrate the first
    # pick after clear_autotune_cache(), masking the second scenario.
    monkeypatch.setenv(autotune.AUTOTUNE_CACHE_ENV, "off")
    monkeypatch.setattr(
        autotune, "_measure_pass",
        _controlled_times(
            {"xla_ref": 1.00, "xla_broadcast": 1.30, "xla_chunked": 0.97}
        ),
    )
    got = autotune.tuned_strategy(
        "baseline_op", (4096, 512, 64), jnp.float32, default="xla_broadcast",
        candidates=("xla_ref", "xla_broadcast", "xla_chunked"),
        bench=lambda n: (lambda: None), baseline="xla_ref",
    )
    assert got == "xla_ref", "3% over ref is noise, not a win"
    # A real (>noise) win DOES displace the baseline.
    dispatch.clear_autotune_cache()
    monkeypatch.setattr(
        autotune, "_measure_pass",
        _controlled_times(
            {"xla_ref": 1.00, "xla_broadcast": 1.30, "xla_chunked": 0.80}
        ),
    )
    got = autotune.tuned_strategy(
        "baseline_op", (4096, 512, 64), jnp.float32, default="xla_broadcast",
        candidates=("xla_ref", "xla_broadcast", "xla_chunked"),
        bench=lambda n: (lambda: None), baseline="xla_ref",
    )
    assert got == "xla_chunked", "a 20% measured win beats the baseline"


def test_auto_never_picks_a_rung_measured_slower_than_ref(monkeypatch):
    """Ladder boundary pin (the assign_min_chunked regression class): just
    past the materialization budget the analytic prior is a streaming rung —
    but when ref MEASURES fastest, the selector must return ref anyway."""
    monkeypatch.setenv(autotune.AUTOTUNE_ENV, "1")
    monkeypatch.setenv(autotune.AUTOTUNE_CACHE_ENV, "off")  # no disk rehydration

    class Spec:
        def __init__(self, shape):
            self.shape = shape
            self.dtype = jnp.float32

    # n·k·4 = 64 MB: past MATERIALIZE_BUDGET (analytic prior: broadcast,
    # k·d small) yet within the 4× ref-candidate window, so ref is measured.
    n, k, d = 8192, 2048, 8
    assert dispatch.ladder_strategy(n, k, d) == "broadcast"
    monkeypatch.setattr(
        autotune, "_measure_pass",
        _controlled_times(
            {"xla_ref": 1.0, "xla_broadcast": 1.5, "xla_chunked": 2.0}
        ),
    )
    assert pd._select_assign("cpu", Spec((n, d)), Spec((k, d))) == "xla_ref"
    # And the flip side: with a genuine streaming win the rung keeps it.
    dispatch.clear_autotune_cache()
    monkeypatch.setattr(
        autotune, "_measure_pass",
        _controlled_times(
            {"xla_ref": 1.0, "xla_broadcast": 0.5, "xla_chunked": 2.0}
        ),
    )
    assert pd._select_assign("cpu", Spec((n, d)), Spec((k, d))) == "xla_broadcast"


def test_deferred_under_trace_returns_default_uncached(monkeypatch):
    monkeypatch.setenv(autotune.AUTOTUNE_ENV, "1")
    calls, picks = [], []

    def bench(name):
        calls.append(name)
        return lambda: None

    def resolve(_x):
        picks.append(autotune.tuned_strategy(
            "trace_op", (64, 64), jnp.float32, default="a",
            candidates=("a", "b"), bench=bench, inputs=(_x,),
        ))
        return _x

    jax.jit(resolve)(jnp.zeros(2))
    assert picks == ["a"], "traced resolution must fall back to the default"
    assert calls == [], "no bench may execute while a trace is active"
    info = dispatch.autotune_cache_info()
    assert info["deferred"] == 1 and info["strategies"] == {}
    # Eagerly, the same bucket measures and caches (either no-op candidate
    # may win the timing — what matters is that measurement happened).
    resolve(jnp.zeros(2))
    assert set(calls) == {"a", "b"}
    assert picks[-1] in ("a", "b")
    assert dispatch.autotune_cache_info()["strategies"]


# ------------------------------------------------------------------ warmup


def test_warmup_runs_plan_counts_errors_and_reports():
    def boom():
        raise RuntimeError("compile blew up")

    plan = [
        ("bucket-a", lambda: jnp.zeros((4, 4))),
        boom,
        ("bucket-b", lambda: jnp.ones((2, 2)) * 2.0),
    ]
    report = autotune.warmup(plan)
    assert report.warmed == 2 and report.errors == 1
    assert report.labels == ("bucket-a", "bucket-b")
    assert report.seconds >= 0.0
    merged = report.merge(autotune.WarmupReport(warmed=1, errors=2))
    assert merged.warmed == 3 and merged.errors == 3
    assert merged.labels == report.labels


def test_warmup_primes_the_measured_caches(monkeypatch):
    """Running a tier's plan eagerly must trigger the pending measurements,
    so post-warmup traffic (traced or not) hits a hot cache."""
    monkeypatch.setenv(autotune.AUTOTUNE_ENV, "1")
    monkeypatch.setenv(autotune.MIN_BYTES_ENV, "1")  # tiny shapes measure too
    dispatch.clear_autotune_cache()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(96, 7)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(24, 7)), jnp.float32)
    report = autotune.warmup([("assign", lambda: pd.assign_min(x, c))])
    assert report.warmed == 1 and report.errors == 0
    assert report.measured > 0, "warmup must trigger the bucket measurements"
    assert dispatch.autotune_cache_info()["strategies"], (
        "the strategy winner must be cached for later traced callers"
    )
