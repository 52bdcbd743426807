"""Dispatch-layer tests: impl parity, auto-selection policy, autotune cache.

These run WITHOUT hypothesis (they are tier-1: the suite must catch a
mis-dispatch — e.g. interpret-mode Pallas selected off-TPU — mechanically).
Interpret-mode parity uses tiny shapes so the interpreter costs milliseconds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.pairwise_dist import ops as pd_ops
from repro.kernels.pairwise_dist import ref as pd_ref
from repro.kernels.weighted_segsum import ops as ss_ops
from repro.kernels.weighted_segsum import ref as ss_ref

ALL_OPS = ("pairwise_sqdist", "assign_min", "weighted_segsum", "flash_attention")


@pytest.fixture(autouse=True)
def _isolated_autotune_cache(tmp_path, monkeypatch):
    """Point the persistent autotune cache at a fresh per-test directory so
    winners persisted by earlier runs (or other tests) can't mask the
    measurement behaviour these tests assert on."""
    monkeypatch.setenv(dispatch.AUTOTUNE_CACHE_ENV, str(tmp_path / "autotune"))
    dispatch.clear_autotune_cache()
    yield
    dispatch.clear_autotune_cache()


# ------------------------------------------------------------ auto policy


def test_auto_never_selects_interpret_off_tpu(monkeypatch):
    """Tier-1 default dispatch must resolve every op to a compiled impl."""
    monkeypatch.delenv(dispatch.INTERPRET_ENV, raising=False)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    w = jnp.asarray(rng.random(64), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 16, 64), jnp.int32)
    q = jnp.zeros((1, 16, 2, 8), jnp.float32)
    calls = {
        "pairwise_sqdist": ((x, c), {}),
        "assign_min": ((x, c), {}),
        "weighted_segsum": ((x, w, idx, 16), {}),
        "flash_attention": ((q, q, q), dict(causal=True, window=None, scale=None)),
    }
    for op in ALL_OPS:
        args, kw = calls[op]
        info = dispatch.resolve(op, "auto", *args, **kw)
        assert not info.debug_only, f"{op} auto-selected debug impl {info.name}"
        if dispatch.backend() != "tpu":
            assert info.name != "pallas_interpret"
            assert info.name.startswith("xla_"), (op, info.name)


def test_auto_respects_streaming_budget(monkeypatch):
    # Pure shape-policy probe: opt out of measurement, otherwise the huge
    # ShapeDtypeStruct buckets below would trigger real (multi-second)
    # measurement passes on synthetic data.
    monkeypatch.setenv(dispatch.AUTOTUNE_ENV, "0")
    x_small = jnp.zeros((64, 4), jnp.float32)
    c_small = jnp.zeros((16, 4), jnp.float32)
    if dispatch.backend() == "tpu":
        pytest.skip("off-TPU policy test")
    assert dispatch.resolve("assign_min", "auto", x_small, c_small).name == "xla_ref"
    # jax.eval_shape-style structs carry .shape, enough for the selector —
    # no giant arrays needed to probe the policy.
    # Past the materialization budget but with k*d inside the broadcast
    # budget, the ladder's middle rung wins (PR 7: this exact shape was the
    # 1.56 s chunked hot spot).
    x_big = jax.ShapeDtypeStruct((1 << 17, 32), jnp.float32)
    c_big = jax.ShapeDtypeStruct((1 << 11, 32), jnp.float32)
    assert dispatch.resolve("assign_min", "auto", x_big, c_big).name == "xla_broadcast"
    # Blow the broadcast budget too (k*d = 2^21 elems) -> chunked streaming.
    x_huge = jax.ShapeDtypeStruct((1 << 17, 1 << 10), jnp.float32)
    c_huge = jax.ShapeDtypeStruct((1 << 11, 1 << 10), jnp.float32)
    assert dispatch.resolve("assign_min", "auto", x_huge, c_huge).name == "xla_chunked"


def test_interpret_env_var_forces_interpret(monkeypatch):
    monkeypatch.setenv(dispatch.INTERPRET_ENV, "1")
    x = jnp.zeros((8, 4), jnp.float32)
    c = jnp.zeros((4, 4), jnp.float32)
    assert dispatch.resolve("assign_min", "auto", x, c).name == "pallas_interpret"


def test_legacy_aliases_resolve():
    x = jnp.zeros((8, 4), jnp.float32)
    c = jnp.zeros((4, 4), jnp.float32)
    assert dispatch.resolve("assign_min", "ref", x, c).name == "xla_ref"
    name = dispatch.resolve("assign_min", "pallas", x, c).name
    assert name == ("pallas_tpu" if dispatch.backend() == "tpu" else "pallas_interpret")
    with pytest.raises(KeyError):
        dispatch.resolve("assign_min", "no_such_impl", x, c)
    with pytest.raises(KeyError):
        dispatch.resolve("no_such_op", "auto")


def test_explicit_impl_honors_backend_gate():
    """impl='pallas_tpu' off-TPU must be a clear dispatch error, not an
    opaque Mosaic lowering failure (debug impls stay usable anywhere)."""
    if dispatch.backend() == "tpu":
        pytest.skip("off-TPU policy test")
    x = jnp.zeros((8, 4), jnp.float32)
    c = jnp.zeros((4, 4), jnp.float32)
    with pytest.raises(KeyError, match="not available on backend"):
        dispatch.resolve("assign_min", "pallas_tpu", x, c)
    assert dispatch.resolve("assign_min", "pallas_interpret", x, c).debug_only


def test_interpret_toggle_after_compile(monkeypatch):
    """The debug env var must bite even for a shape that was already traced
    and compiled with the default dispatch (resolution is eager per call)."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(24, 5)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(6, 5)), jnp.float32)
    monkeypatch.delenv(dispatch.INTERPRET_ENV, raising=False)
    i1, d1 = pd_ops.assign_min(x, c)  # compiles the XLA path for this shape
    monkeypatch.setenv(dispatch.INTERPRET_ENV, "1")
    assert dispatch.resolve("assign_min", "auto", x, c).name == "pallas_interpret"
    i2, d2 = pd_ops.assign_min(x, c)  # same shape, now the interpret path
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=2e-5, atol=2e-4)


def test_autotune_defers_under_trace_and_measures_eagerly(monkeypatch):
    """Measurement is eager-only: inside an active jit trace the bench inputs
    would be staged tracers, so the tuned_* calls DEFER — analytic default,
    uncached — and the same bucket measures for real on the next eager call.
    Results must be correct either way."""
    if dispatch.backend() == "tpu":
        pytest.skip("exercises the off-TPU chunked path")
    from repro.kernels import autotune

    monkeypatch.setenv(dispatch.AUTOTUNE_ENV, "1")
    monkeypatch.setenv(autotune.MIN_BYTES_ENV, "1")  # measure even tiny shapes
    dispatch.clear_autotune_cache()
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=(96, 7)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(150, 7)), jnp.float32)
    # The public wrapper jits the impl body: the inner tuned call sees an
    # active trace and defers without caching the unmeasured default.
    idx, dist = pd_ops.assign_min(x, c, impl="xla_chunked")
    iref, dref = pd_ref.assign_min_ref(x, c)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(iref))
    info = dispatch.autotune_cache_info()
    assert info["deferred"] >= 1, "traced tuned_* call must defer"
    assert not any(k[0] == "assign_min_chunked" for k in info["entries"]), (
        "deferred default must not be cached"
    )
    # Eager call: trace state is clean, so the bucket measures and caches.
    pd_ops._assign_min_chunked(x, c)
    info = dispatch.autotune_cache_info()
    assert info["measured"] > 0, "bench callables never executed"
    assert any(k[0] == "assign_min_chunked" for k in info["entries"])
    dispatch.clear_autotune_cache()


# ------------------------------------------------------------- block model


def test_pick_blocks_respects_vmem_budget():
    for n, k, d in [(10_000, 4096, 8), (512, 64, 4096), (100, 7, 16), (1, 1, 1)]:
        cfg = dispatch.pick_blocks(n, k, d)
        # TPU tiling: every block is a whole number of 128-wide lanes.
        assert cfg.bn % dispatch.LANE == 0 and cfg.bk % dispatch.LANE == 0
        assert cfg.bn >= dispatch.LANE and cfg.bk >= dispatch.LANE
        assert dispatch.block_footprint(cfg.bn, cfg.bk, d) <= max(
            dispatch.VMEM_BUDGET,
            # floor: the minimum 128×128 tile may exceed the budget for huge d
            dispatch.block_footprint(dispatch.LANE, dispatch.LANE, d),
        )


def test_autotune_cache_and_bucketing(monkeypatch):
    monkeypatch.setenv(dispatch.AUTOTUNE_ENV, "1")
    dispatch.clear_autotune_cache()
    cands = [dispatch.BlockConfig(0, 64), dispatch.BlockConfig(0, 128)]
    calls = []

    def bench(cfg):
        calls.append(cfg)
        return lambda: None

    kw = dict(default=cands[0], candidates=cands, bench=bench)
    got1 = dispatch.tuned_block_config("toy_op", (1000, 37), jnp.float32, **kw)
    n_meas = len(calls)
    assert n_meas == len(cands)
    # 1001 buckets with 1000 (same power of two) → cache hit, no re-measure.
    got2 = dispatch.tuned_block_config("toy_op", (1001, 40), jnp.float32, **kw)
    assert len(calls) == n_meas and got2 == got1
    info = dispatch.autotune_cache_info()
    assert info["hits"] >= 1 and info["measured"] == n_meas
    dispatch.clear_autotune_cache()


def test_autotune_disabled_uses_model_default(monkeypatch):
    # Measured-first is the default, so disabling takes an explicit opt-out.
    monkeypatch.setenv(dispatch.AUTOTUNE_ENV, "0")
    dispatch.clear_autotune_cache()
    default = dispatch.BlockConfig(0, 512)

    def bench(cfg):  # must never be called when autotuning is off
        raise AssertionError("measured while disabled")

    cands = [default, dispatch.BlockConfig(0, 256)]
    got = dispatch.tuned_block_config(
        "toy_op2", (64, 64), jnp.float32, default=default,
        candidates=cands, bench=bench,
    )
    assert got == default
    # The unmeasured default must NOT be cached: enabling REPRO_AUTOTUNE
    # later in the same process has to trigger real measurement.
    assert not dispatch.autotune_cache_info()["entries"]
    monkeypatch.setenv(dispatch.AUTOTUNE_ENV, "1")
    dispatch.tuned_block_config(
        "toy_op2", (64, 64), jnp.float32, default=default,
        candidates=cands, bench=lambda cfg: (lambda: None),
    )
    assert dispatch.autotune_cache_info()["measured"] == len(cands)
    dispatch.clear_autotune_cache()


# ----------------------------------------------------------- impl parity


@pytest.mark.parametrize(
    "n,k,d",
    [
        (96, 24, 8),     # n % bn != 0, k % bk != 0
        (70, 37, 512),   # d ≥ 512 — the old 1e18-padding NaN regression
        (33, 1, 3),      # k=1 edge
        (128, 64, 16),   # exact multiples
    ],
)
def test_assign_min_impl_parity(n, k, d):
    rng = np.random.default_rng(n * 7 + k)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
    iref, dref = pd_ref.assign_min_ref(x, c)
    for impl in ("auto", "xla_ref", "xla_chunked", "pallas_interpret"):
        idx, dist = pd_ops.assign_min(x, c, impl=impl)
        assert np.isfinite(np.asarray(dist)).all(), impl
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(iref), err_msg=impl)
        np.testing.assert_allclose(
            np.asarray(dist), np.asarray(dref), rtol=2e-5, atol=2e-4, err_msg=impl
        )


def test_assign_min_padded_centers_no_nan_poisoning():
    """Regression: padded center columns used to carry coordinate 1e18, so
    ‖c‖² overflowed to inf and a mixed real/padded k-block could produce
    inf − inf = NaN, silently corrupting the argmin."""
    rng = np.random.default_rng(3)
    # k=37 pads up to the block size; d=600 makes ‖pad‖² overflow under the
    # old scheme (600 · 10³⁶ ≫ f32 max).
    x = jnp.asarray(rng.normal(size=(48, 600)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(37, 600)), jnp.float32)
    idx, dist = pd_ops.assign_min(x, c, impl="pallas_interpret")
    assert np.isfinite(np.asarray(dist)).all()
    iref, _ = pd_ref.assign_min_ref(x, c)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(iref))
    assert int(np.asarray(idx).max()) < 37  # padding can never win


def test_pairwise_sqdist_impl_parity():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(70, 13)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(37, 13)), jnp.float32)
    want = pd_ref.pairwise_sqdist_ref(x, c)
    for impl in ("auto", "xla_ref", "pallas_interpret"):
        got = pd_ops.pairwise_sqdist(x, c, impl=impl)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3, err_msg=impl
        )


def test_weighted_segsum_impl_parity():
    rng = np.random.default_rng(6)
    n, k, d = 213, 17, 9
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w = jnp.asarray(rng.random(n), jnp.float32)
    idx = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    s_ref, t_ref = ss_ref.weighted_segsum_ref(x, w, idx, k)
    for impl in ("auto", "xla_ref", "xla_segment", "pallas_interpret"):
        s, t = ss_ops.weighted_segsum(x, w, idx, k, impl=impl)
        np.testing.assert_allclose(
            np.asarray(s), np.asarray(s_ref), rtol=2e-5, atol=1e-3, err_msg=impl
        )
        np.testing.assert_allclose(
            np.asarray(t), np.asarray(t_ref), rtol=2e-5, atol=1e-4, err_msg=impl
        )


def test_flash_attention_impl_parity():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 16)), jnp.float32)
    want = fa_ref.attention_ref(q, k, v, causal=True)
    for impl in ("auto", "xla_chunked", "xla_ref", "pallas_interpret"):
        got = fa_ops.flash_attention(q, k, v, causal=True, impl=impl)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-4, err_msg=impl
        )
    # A 0-d array scale must keep working (it is coerced to a static float).
    got = fa_ops.flash_attention(q, k, v, causal=True, scale=jnp.float32(0.25))
    want = fa_ref.attention_ref(q, k, v, causal=True, scale=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-4)
    # ...and so must a TRACED scale through an outer jit (xla impls only).
    got = jax.jit(lambda s: fa_ops.flash_attention(q, k, v, causal=True, scale=s))(
        jnp.float32(0.25)
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-4)


def test_chunked_assign_min_matches_over_chunk_boundaries():
    """Centers straddling several chunks: argmin ties must break toward the
    earliest center, exactly like the oracle."""
    rng = np.random.default_rng(8)
    x_np = np.asarray(rng.normal(size=(32, 4)), np.float32)
    base = np.asarray(rng.normal(size=(4,)), np.float32)
    # duplicate centers in different chunks → tie on purpose
    c = np.asarray(rng.normal(size=(300, 4)), np.float32)
    c[7] = base
    c[250] = base
    x_np[0] = base
    x = jnp.asarray(x_np)
    iref, dref = pd_ref.assign_min_ref(x, jnp.asarray(c))
    idx, dist = pd_ops.assign_min(x, jnp.asarray(c), impl="xla_chunked")
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(iref))
    assert int(np.asarray(idx)[0]) == 7  # first duplicate wins


# --------------------------------------------------- core-layer threading


def test_lloyd_parity_across_impls():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(200, 6)), jnp.float32)
    from repro.core import kmeans

    costs = {}
    for impl in ("auto", "xla_ref"):
        res = kmeans.lloyd(jax.random.PRNGKey(0), x, 5, iters=4, impl=impl)
        costs[impl] = float(res.cost)
    assert costs["auto"] == pytest.approx(costs["xla_ref"], rel=1e-5)
