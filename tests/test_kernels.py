"""Per-kernel allclose tests against the pure-jnp oracles.

Shape/dtype sweeps exercise padding paths, GQA group mapping, and the causal
block-skip logic of the flash kernel (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="property tests need the optional hypothesis dep")
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.pairwise_dist import kernel as pd_kernel
from repro.kernels.pairwise_dist import ops as pd_ops
from repro.kernels.pairwise_dist import ref as pd_ref
from repro.kernels.weighted_segsum import kernel as ss_kernel
from repro.kernels.weighted_segsum import ops as ss_ops
from repro.kernels.weighted_segsum import ref as ss_ref


# ---------------------------------------------------------------- pairwise


@pytest.mark.parametrize("n,k,d", [(256, 128, 8), (512, 128, 64), (256, 256, 17)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_sqdist_kernel_sweep(n, k, d, dtype):
    rng = np.random.default_rng(n + k + d)
    x = jnp.asarray(rng.normal(size=(n, d)), dtype)
    c = jnp.asarray(rng.normal(size=(k, d)), dtype)
    got = pd_kernel.pairwise_sqdist_kernel_call(x, c, bn=128, bk=128, interpret=True)
    want = pd_ref.pairwise_sqdist_ref(x, c)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("n,k,d", [(256, 128, 4), (512, 256, 32)])
def test_assign_min_kernel_sweep(n, k, d):
    rng = np.random.default_rng(7 * n + k)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
    idx, dist = pd_kernel.assign_min_kernel_call(x, c, bn=128, bk=128, interpret=True)
    iref, dref = pd_ref.assign_min_ref(x, c)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(iref))
    np.testing.assert_allclose(np.asarray(dist), np.asarray(dref), rtol=2e-5, atol=2e-4)


def test_assign_min_ops_padding_path():
    # Non-multiple shapes go through the pad/unpad wrapper.
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1000, 13)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(37, 13)), jnp.float32)
    idx, dist = pd_ops.assign_min(x, c)
    iref, dref = pd_ref.assign_min_ref(x, c)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(iref))


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=300),
    k=st.integers(min_value=1, max_value=50),
    d=st.integers(min_value=1, max_value=24),
)
def test_pairwise_ops_property(n, k, d):
    rng = np.random.default_rng(n * 100 + k)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
    got = pd_ops.pairwise_sqdist(x, c)
    want = pd_ref.pairwise_sqdist_ref(x, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-3)
    assert (np.asarray(got) >= 0).all()  # invariant: squared distances


@pytest.mark.parametrize("n,k,d", [(300, 200, 16), (1000, 37, 5)])
def test_assign_min_pallas_under_vmap(n, k, d):
    # LocalExecutor vmaps every local solve over the node axis: the kernel's
    # lane-dense (1, n) rows must stay right with the extra batch grid axis.
    rng = np.random.default_rng(n + k)
    x = jnp.asarray(rng.normal(size=(3, n, d)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
    idx, dist = jax.vmap(lambda a: pd_ops.assign_min(a, c, impl="pallas_interpret"))(x)
    for b in range(3):
        iref, dref = pd_ref.assign_min_ref(x[b], c)
        np.testing.assert_array_equal(np.asarray(idx[b]), np.asarray(iref))
        np.testing.assert_allclose(np.asarray(dist[b]), np.asarray(dref), rtol=2e-5, atol=2e-4)


# ---------------------------------------------------------------- segsum


@pytest.mark.parametrize("n,k,d", [(512, 16, 8), (1024, 64, 32), (512, 7, 5)])
def test_weighted_segsum_kernel_sweep(n, k, d):
    rng = np.random.default_rng(n + k)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w = jnp.asarray(rng.random(n), jnp.float32)
    idx = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    s_got, t_got = ss_kernel.weighted_segsum_kernel_call(x, w, idx, k, bn=256, interpret=True)
    s_ref, t_ref = ss_ref.weighted_segsum_ref(x, w, idx, k)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_ref), rtol=2e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(t_got), np.asarray(t_ref), rtol=2e-5, atol=1e-4)


def test_bf16_terms_carry_every_f32_bit():
    # The segment-sum kernel's f32 sums rest on this: three bf16 terms add
    # back to the f32 value exactly, across magnitudes.
    rng = np.random.default_rng(5)
    v = (rng.standard_normal(4096) * 10.0 ** rng.integers(-6, 7, 4096)).astype(np.float32)
    hi, mid, lo = ss_kernel._bf16_terms(jnp.asarray(v))
    back = hi.astype(jnp.float32) + mid.astype(jnp.float32) + lo.astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(back), v)
    assert not np.array_equal(np.asarray(hi.astype(jnp.float32)), v)  # bf16 alone rounds


def test_weighted_segsum_pallas_under_vmap():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(3, 300, 16)), jnp.float32)
    w = jnp.asarray(rng.random((3, 300)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 37, (3, 300)), jnp.int32)
    sums, tot = jax.vmap(
        lambda a, ww, ii: ss_ops.weighted_segsum(a, ww, ii, 37, impl="pallas_interpret")
    )(x, w, idx)
    for b in range(3):
        s_ref, t_ref = ss_ref.weighted_segsum_ref(x[b], w[b], idx[b], 37)
        np.testing.assert_allclose(np.asarray(sums[b]), np.asarray(s_ref), rtol=2e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(tot[b]), np.asarray(t_ref), rtol=2e-5, atol=1e-5)


def test_weighted_segsum_mass_conservation():
    # Invariant: Σ_c totals[c] == Σ_i w_i and Σ_c sums[c] == Σ_i w_i·x_i.
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(777, 6)), jnp.float32)
    w = jnp.asarray(rng.random(777), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 9, 777), jnp.int32)
    sums, tot = ss_ops.weighted_segsum(x, w, idx, 9)
    np.testing.assert_allclose(float(tot.sum()), float(w.sum()), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(sums.sum(0)), np.asarray((w[:, None] * x).sum(0)), rtol=1e-4, atol=1e-3
    )


# ---------------------------------------------------------------- flash attn


@pytest.mark.parametrize("B,T,H,KV,dh", [(2, 256, 4, 2, 64), (1, 128, 8, 8, 32), (2, 512, 4, 1, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_pallas_vs_ref(B, T, H, KV, dh, causal):
    rng = np.random.default_rng(B * T + H)
    q = jnp.asarray(rng.normal(size=(B, T, H, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, KV, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, KV, dh)), jnp.float32)
    got = fa_ops.flash_attention(q, k, v, causal=causal, impl="pallas")
    want = fa_ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_pallas_grad_matches_ref(causal):
    # The kernel has no backward of its own; its custom VJP must give the
    # reference attention's gradient, which is what a train step uses.
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.normal(size=(2, 128, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 128, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 128, 2, 32)), jnp.float32)

    def loss(impl):
        return lambda q, k, v: jnp.sum(
            jnp.sin(fa_ops.flash_attention(q, k, v, causal=causal, impl=impl))
        )

    got = jax.grad(loss("pallas"), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss("ref"), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_pallas_dtypes(dtype):
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.normal(size=(1, 256, 4, 64)), dtype)
    k = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), dtype)
    v = jnp.asarray(rng.normal(size=(1, 256, 2, 64)), dtype)
    got = fa_ops.flash_attention(q, k, v, causal=True, impl="pallas")
    want = fa_ref.attention_ref(q, k, v, causal=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )
    assert got.dtype == dtype


@pytest.mark.parametrize("T", [128, 384, 1024])
def test_flash_chunked_vs_ref(T):
    rng = np.random.default_rng(T)
    q = jnp.asarray(rng.normal(size=(2, T, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, T, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, T, 2, 32)), jnp.float32)
    got = fa_ops.flash_attention(q, k, v, causal=True, impl="chunked")
    want = fa_ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-4)


def test_flash_chunked_window_matches_masked_ref():
    rng = np.random.default_rng(5)
    B, T, H, KV, dh, W = 1, 256, 4, 2, 32, 64
    q = jnp.asarray(rng.normal(size=(B, T, H, dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, KV, dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, KV, dh)), jnp.float32)
    got = fa_ops.flash_attention(q, k, v, causal=True, window=W, impl="chunked")
    # Masked oracle.
    g = H // KV
    s = jnp.einsum(
        "bthd,bshd->bhts",
        q.astype(jnp.float32),
        jnp.repeat(k, g, axis=2).astype(jnp.float32),
    ) * dh**-0.5
    qp, kp = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = (qp >= kp) & (kp > qp - W)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    want = jnp.einsum("bhts,bshd->bthd", p, jnp.repeat(v, g, axis=2).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-4)


def test_decode_attention_matches_prefix_ref():
    rng = np.random.default_rng(9)
    B, S, H, KV, dh, cur = 2, 96, 4, 2, 32, 57
    q = jnp.asarray(rng.normal(size=(B, 1, H, dh)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, S, KV, dh)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, S, KV, dh)), jnp.float32)
    got = fa_ops.decode_attention(q, kc, vc, cur)
    want = fa_ref.attention_ref(q, kc[:, :cur], vc[:, :cur], causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-4)


def test_decode_attention_per_batch_lengths():
    rng = np.random.default_rng(10)
    B, S, H, KV, dh = 3, 64, 2, 1, 16
    q = jnp.asarray(rng.normal(size=(B, 1, H, dh)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, S, KV, dh)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, S, KV, dh)), jnp.float32)
    lens = jnp.asarray([5, 33, 64])
    got = fa_ops.decode_attention(q, kc, vc, lens)
    for b in range(B):
        want = fa_ref.attention_ref(
            q[b : b + 1], kc[b : b + 1, : int(lens[b])], vc[b : b + 1, : int(lens[b])],
            causal=False,
        )
        np.testing.assert_allclose(
            np.asarray(got[b : b + 1]), np.asarray(want), rtol=2e-5, atol=2e-4
        )
