"""Latent attention (MLA) against a plain jnp MLA, and the attention kernel
at a query/key head dim that differs from the value head dim."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import ops as fa
from repro.models import attention as A
from repro.models import transformer as T
from repro.models.registry import ModelConfig


def _cfg():
    return ModelConfig(
        name="mla-tiny", family="moe", vocab=64, d_model=64, n_layers=1, n_heads=4,
        n_kv_heads=4, d_ff=96, head_dim=16, scan_unit=("mla_mlp",), kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
        rope_theta=50000.0, rms_eps=1e-5, compute_dtype="float32",
    ).validate()


def _rms(x, s, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * s


def _rope_pairs(x, theta):
    T_, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
    ang = np.arange(T_)[:, None] * inv[None, :]
    cos, sin = np.cos(ang)[None, :, None, :], np.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def _plain_mla(p, x, c):
    """DeepSeek-V2 §2.1 written out: per-head [q_nope, RoPE(q_r)] against
    [k_nope, RoPE(k_r) shared], softmax at (nope + rope)^-0.5, values v."""
    B, T_, _ = x.shape
    H, dn, dr, dv, r = c.n_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, c.kv_lora_rank
    q = (x @ p["wq"]).reshape(B, T_, H, dn + dr)
    kv = x @ p["wkv_a"]
    kvb = (_rms(kv[..., :r], p["kv_norm"], c.rms_eps) @ p["wkv_b"]).reshape(B, T_, H, dn + dv)
    k_r = _rope_pairs(kv[..., None, r:], c.rope_theta)
    q = jnp.concatenate([q[..., :dn], _rope_pairs(q[..., dn:], c.rope_theta)], -1)
    k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(k_r, (B, T_, H, dr))], -1)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * (dn + dr) ** -0.5
    s = jnp.where(np.tril(np.ones((T_, T_), bool)), s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), kvb[..., dn:])
    return o.reshape(B, T_, H * dv) @ p["wo"]


@pytest.mark.parametrize("T_", [16, 48])
def test_mla_block_matches_plain_mla(T_):
    c = _cfg()
    p = A.mla_init(jax.random.PRNGKey(0), c)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T_, c.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, (k, v) = A.mla_apply(p, x, c, positions=jnp.arange(T_), impl="xla_ref")
        want = _plain_mla(p, x, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert k.shape == (2, T_, 4, 24) and v.shape == (2, T_, 4, 12)


def test_mla_decode_matches_full_forward():
    """Decoding token by token through the per-head caches gives the logits
    of the parallel forward."""
    c = dataclasses.replace(_cfg(), n_layers=2, lead=("mla_mlp",)).validate()
    ctx = T.ModelContext(attn_impl="xla_ref")
    params = T.init_params(jax.random.PRNGKey(2), c)
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, c.vocab)
    with jax.default_matmul_precision("highest"):
        full, _, _ = T.forward_train(params, {"tokens": toks}, c, ctx)
        cache = T.init_cache(c, 1, 8)
        outs = []
        for t in range(8):
            lg, cache = T.decode_step(params, cache, toks[:, t:t + 1], jnp.asarray(t), c, ctx)
            outs.append(lg[:, 0])
    np.testing.assert_allclose(np.stack(outs, 1), np.asarray(full), rtol=1e-4, atol=1e-4)


def _plain_softmax(q, k, v):
    s = jnp.einsum("bthd,bshd->bhts", q, k) * q.shape[-1] ** -0.5
    T_ = q.shape[1]
    s = jnp.where(np.tril(np.ones((T_, T_), bool)), s, -jnp.inf)
    return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla_chunked", "xla_ref"])
def test_attention_dk_ne_dv_forward_and_grad(impl):
    """q, k at dk = 48 and v at dv = 32: each implementation against a plain
    softmax, the output and the gradient of every input."""
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    B, T_, H, dk, dv = 1, 256, 2, 48, 32
    q = jax.random.normal(ks[0], (B, T_, H, dk), jnp.float32)
    k = jax.random.normal(ks[1], (B, T_, H, dk), jnp.float32)
    v = jax.random.normal(ks[2], (B, T_, H, dv), jnp.float32)
    g = jax.random.normal(ks[3], (B, T_, H, dv), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(q, k, v, impl=impl), q, k, v)
        want, vjp_ref = jax.vjp(_plain_softmax, q, k, v)
        grads, grads_ref = vjp(g), vjp_ref(g)
    assert got.shape == (B, T_, H, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
    for a, b in zip(grads, grads_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)
