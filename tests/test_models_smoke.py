"""Per-architecture smoke tests (deliverable f): reduced configs of the same
family, one forward/train step on CPU, asserting output shapes + no NaNs."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import transformer as T
from repro.models.registry import get_config, list_archs

pytestmark = pytest.mark.slow  # model-zoo compile-heavy; run via `make test-all`

ARCH_MODULES = {
    "qwen3-4b": "qwen3_4b",
    "qwen3-8b": "qwen3_8b",
    "qwen2.5-3b": "qwen2_5_3b",
    "qwen3-1.7b": "qwen3_1_7b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "xlstm-1.3b": "xlstm_1_3b",
    "internvl2-1b": "internvl2_1b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "musicgen-large": "musicgen_large",
}


def smoke_cfg(arch: str):
    mod = importlib.import_module(f"repro.configs.{ARCH_MODULES[arch]}")
    return mod.smoke_config().validate()


def make_batch(cfg, B=2, T=32, key=None):
    key = jax.random.PRNGKey(0) if key is None else key
    batch = {}
    if cfg.num_codebooks > 0:
        batch["tokens"] = jax.random.randint(key, (B, cfg.num_codebooks, T), 0, cfg.vocab)
    else:
        batch["tokens"] = jax.random.randint(key, (B, T), 0, cfg.vocab)
    if cfg.num_prefix_tokens > 0:
        batch["prefix_embeds"] = jax.random.normal(
            key, (B, cfg.num_prefix_tokens, cfg.d_model), jnp.float32
        )
    return batch


def test_all_ten_archs_registered():
    assert set(ARCH_MODULES) == set(list_archs())


@pytest.mark.parametrize("arch", sorted(ARCH_MODULES))
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    cfg = get_config(arch)
    expected = {
        "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
        "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
        "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151936),
        "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102400),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
        "internvl2-1b": (24, 896, 14, 2, 4864, 151655),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab)
    assert got == expected
    assert len(cfg.block_types) == cfg.n_layers


@pytest.mark.parametrize("arch", sorted(ARCH_MODULES))
def test_smoke_forward_and_train_step(arch):
    cfg = smoke_cfg(arch)
    ctx = T.ModelContext()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg)
    logits, aux, mask = T.forward_train(params, batch, cfg, ctx)
    B, Ttok = 2, 32
    total_T = Ttok + (cfg.num_prefix_tokens if cfg.num_prefix_tokens else 0)
    if cfg.num_codebooks > 0:
        assert logits.shape == (B, Ttok, cfg.num_codebooks, cfg.vocab)
    else:
        assert logits.shape == (B, total_T, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    loss, metrics = T.loss_fn(params, batch, cfg, ctx)
    assert np.isfinite(float(loss))
    grads = jax.grad(lambda p: T.loss_fn(p, batch, cfg, ctx)[0])(params)
    gn = sum(
        float(jnp.sum(jnp.square(g.astype(jnp.float32))))
        for g in jax.tree_util.tree_leaves(grads)
    )
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", sorted(ARCH_MODULES))
def test_smoke_group_weighted_loss_matches_uniform(arch):
    """With b ≡ 1 (no stragglers, exact cover) the group-weighted loss equals
    the plain mean — Lemma 3's a ≡ 1 case on gradients' primal."""
    cfg = smoke_cfg(arch)
    ctx = T.ModelContext()
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    batch = make_batch(cfg)
    plain, _ = T.loss_fn(params, batch, cfg, ctx)
    weighted, _ = T.loss_fn(
        params, {**batch, "group_weights": jnp.ones((2,))}, cfg, ctx
    )
    np.testing.assert_allclose(float(plain), float(weighted), rtol=2e-5)


@pytest.mark.parametrize("arch", sorted(ARCH_MODULES))
def test_smoke_decode_step(arch):
    cfg = smoke_cfg(arch)
    ctx = T.ModelContext()
    params = T.init_params(jax.random.PRNGKey(2), cfg)
    B, max_len = 2, 16
    cache = T.init_cache(cfg, B, max_len)
    if cfg.num_codebooks > 0:
        tok = jnp.zeros((B, cfg.num_codebooks, 1), jnp.int32)
    else:
        tok = jnp.zeros((B, 1), jnp.int32)
    logits, cache2 = T.decode_step(params, cache, tok, jnp.asarray(0, jnp.int32), cfg, ctx)
    if cfg.num_codebooks > 0:
        assert logits.shape == (B, 1, cfg.num_codebooks, cfg.vocab)
    else:
        assert logits.shape == (B, 1, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    # Cache must actually change for stateful blocks.
    diff = jax.tree_util.tree_map(
        lambda a, b: float(jnp.sum(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))),
        cache, cache2,
    )
    assert sum(jax.tree_util.tree_leaves(diff)) > 0


DENSE_ARCHS = ["qwen3-4b", "qwen2.5-3b", "musicgen-large"]


@pytest.mark.parametrize("arch", DENSE_ARCHS)
def test_decode_consistency_with_teacher_forcing(arch):
    """Token-by-token decode logits must match the parallel training forward
    (same params, same tokens) — the KV-cache path is exact for attention."""
    cfg = smoke_cfg(arch)
    ctx = T.ModelContext(attn_impl="ref")
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    B, Ttok = 1, 8
    batch = make_batch(cfg, B=B, T=Ttok, key=jax.random.PRNGKey(4))
    full_logits, _, _ = T.forward_train(params, batch, cfg, ctx)
    cache = T.init_cache(cfg, B, Ttok)
    toks = batch["tokens"]
    outs = []
    for t in range(Ttok):
        tok_t = toks[..., t : t + 1]
        lg, cache = T.decode_step(params, cache, tok_t, jnp.asarray(t, jnp.int32), cfg, ctx)
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)  # (B, T, [K,] V)
    np.testing.assert_allclose(
        np.asarray(dec, np.float32), np.asarray(full_logits, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_xlstm_decode_consistency():
    """mLSTM chunkwise-parallel (train) vs recurrent step (decode) must agree
    — validates the stabilized chunkwise cell math end-to-end."""
    cfg = smoke_cfg("xlstm-1.3b")
    ctx = T.ModelContext()
    params = T.init_params(jax.random.PRNGKey(5), cfg)
    B, Ttok = 1, 12
    batch = make_batch(cfg, B=B, T=Ttok, key=jax.random.PRNGKey(6))
    full_logits, _, _ = T.forward_train(params, batch, cfg, ctx)
    cache = T.init_cache(cfg, B, Ttok)
    outs = []
    for t in range(Ttok):
        lg, cache = T.decode_step(
            params, cache, batch["tokens"][:, t : t + 1],
            jnp.asarray(t, jnp.int32), cfg, ctx,
        )
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec, np.float32), np.asarray(full_logits, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_rglru_decode_consistency():
    """RG-LRU associative scan (train) vs per-token step (decode)."""
    cfg = smoke_cfg("recurrentgemma-9b")
    ctx = T.ModelContext(attn_impl="chunked")  # local attn needs window support
    params = T.init_params(jax.random.PRNGKey(7), cfg)
    B, Ttok = 1, 10
    batch = make_batch(cfg, B=B, T=Ttok, key=jax.random.PRNGKey(8))
    full_logits, _, _ = T.forward_train(params, batch, cfg, ctx)
    cache = T.init_cache(cfg, B, Ttok)
    outs = []
    for t in range(Ttok):
        lg, cache = T.decode_step(
            params, cache, batch["tokens"][:, t : t + 1],
            jnp.asarray(t, jnp.int32), cfg, ctx,
        )
        outs.append(lg[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec, np.float32), np.asarray(full_logits, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_moe_capacity_routing_mass():
    """Dropless routing: the full top-k mass reaches the experts.  With every
    expert computing the same FFN, the routed output is each token's top-k
    router mass times that FFN, whatever the load; every pair is computed."""
    import dataclasses

    cfg = smoke_cfg("deepseek-moe-16b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, num_shared=0))
    from repro.models import layers as L
    from repro.models import moe as M

    params = M.moe_init(jax.random.PRNGKey(0), cfg)
    for k in ("w_gate", "w_up", "w_down"):
        params[k] = jnp.broadcast_to(params[k][:1], params[k].shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model), jnp.float32)
    out, stats = M.moe_apply(params, x, cfg)
    assert out.shape == x.shape
    flat = x.reshape(-1, cfg.d_model)
    _, w, _ = M.route(params["router"], None, flat, cfg.moe)
    one = {"gate": params["w_gate"][0], "up": params["w_up"][0], "down": params["w_down"][0]}
    ffn = L.mlp_apply(one, flat, act=cfg.mlp_act, compute_dtype=jnp.float32)
    want = (jnp.sum(w, axis=1, keepdims=True) * ffn).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want), rtol=3e-2, atol=3e-2)
    assert int(jnp.sum(stats["held"])) == flat.shape[0] * cfg.moe.top_k  # none dropped
    assert float(jnp.mean(stats["balance"])) > 0.5  # ≈ 1 at uniform routing


def test_prefill_returns_last_position_logits_and_cache():
    cfg = smoke_cfg("qwen3-4b")
    ctx = T.ModelContext(attn_impl="ref")
    params = T.init_params(jax.random.PRNGKey(9), cfg)
    batch = make_batch(cfg, B=2, T=16)
    logits, cache = T.prefill(params, batch, cfg, ctx)
    assert logits.shape == (2, 1, cfg.vocab)
    k = cache["unit"]["slot0"]["k"]
    assert k.shape == (cfg.scan_repeats, 2, 16, cfg.n_kv_heads, cfg.head_dim)
