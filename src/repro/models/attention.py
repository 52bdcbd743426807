"""GQA attention block (global or sliding-window) and latent attention (MLA)
with train/prefill/decode paths.  The heavy math lives in
repro.kernels.flash_attention (Pallas on TPU, chunked pure-jnp for the
dry-run/CPU)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels.flash_attention import ops as fa
from . import layers as L
from .registry import ModelConfig

__all__ = [
    "attn_init", "attn_apply", "attn_decode_step", "mla_init", "mla_apply", "mla_decode_step",
]


def attn_init(key, cfg: ModelConfig, *, dtype=jnp.float32):
    d, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": L.dense_init(ks[0], d, H * dh, dtype=dtype),
        "wk": L.dense_init(ks[1], d, KV * dh, dtype=dtype),
        "wv": L.dense_init(ks[2], d, KV * dh, dtype=dtype),
        "wo": L.dense_init(ks[3], H * dh, d, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * dh,), dtype)
        p["bk"] = jnp.zeros((KV * dh,), dtype)
        p["bv"] = jnp.zeros((KV * dh,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = L.rmsnorm_init(dh, dtype=dtype)
        p["k_norm"] = L.rmsnorm_init(dh, dtype=dtype)
    return p


def _project_qkv(p, x, cfg: ModelConfig, positions, compute_dtype):
    B, T, _ = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    xc = x.astype(compute_dtype)
    q = xc @ p["wq"].astype(compute_dtype)
    k = xc @ p["wk"].astype(compute_dtype)
    v = xc @ p["wv"].astype(compute_dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(compute_dtype)
        k = k + p["bk"].astype(compute_dtype)
        v = v + p["bv"].astype(compute_dtype)
    q = q.reshape(B, T, H, dh)
    k = k.reshape(B, T, KV, dh)
    v = v.reshape(B, T, KV, dh)
    if cfg.qk_norm:
        q = L.rmsnorm(q, p["q_norm"], eps=cfg.rms_eps)
        k = L.rmsnorm(k, p["k_norm"], eps=cfg.rms_eps)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_apply(p, x, cfg: ModelConfig, *, positions, window=None, impl="auto"):
    """Training / prefill forward.  x: (B, T, d).  Returns (out, (k, v))."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    q, k, v = _project_qkv(p, x, cfg, positions, compute_dtype)
    o = fa.flash_attention(q, k, v, causal=True, window=window, impl=impl)
    B, T = x.shape[:2]
    out = o.reshape(B, T, cfg.n_heads * cfg.head_dim) @ p["wo"].astype(compute_dtype)
    return out.astype(x.dtype), (k, v)


def attn_decode_step(p, x_t, cache_k, cache_v, cur_len, cfg: ModelConfig, *, window=None):
    """One-token decode.  x_t: (B, 1, d); caches (B, S, KV, dh) updated at
    position ``cur_len`` (ring-indexed when a sliding window is active and
    the cache is sized to the window)."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    S = cache_k.shape[1]
    pos = jnp.full((x_t.shape[0],), cur_len, jnp.int32)[:, None]  # (B, 1)
    q, k, v = _project_qkv(p, x_t, cfg, pos, compute_dtype)
    slot = (cur_len % S) if window is not None else cur_len
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), slot, axis=1)
    if window is None:
        o = fa.decode_attention(q, cache_k, cache_v, cur_len + 1)
    else:
        # Ring cache: all S slots are valid once full; mask by recency.
        # Positions in the ring correspond to absolute times
        # (cur_len − S + 1 + offset); attention over the last min(S, cur+1).
        o = fa.decode_attention(q, cache_k, cache_v, jnp.minimum(cur_len + 1, S))
    B = x_t.shape[0]
    out = o.reshape(B, 1, cfg.n_heads * cfg.head_dim) @ p["wo"].astype(compute_dtype)
    return out.astype(x_t.dtype), cache_k, cache_v


# ------------------------------------------------------------------ MLA
#
# DeepSeek-V2 §2.1 (arXiv:2405.04434): keys and values come from one
# compressed latent c_kv = RMSNorm(W_kva h)[:r] per token, up-projected per
# head by W_kvb; a decoupled RoPE key k_r = RoPE(W_kva h)[r:] of
# qk_rope_head_dim is shared by every head.  Queries are W_q h (queries
# are not compressed: q_lora_rank is null), split per head into a
# qk_nope_head_dim part and a RoPE part.  Scores use q = [q_nope, RoPE(q_r)]
# and k = [k_nope, k_r] at (nope + rope)^-0.5; values are v_head_dim wide.
# The cache holds the per-head keys and values (not the latent).


def mla_init(key, cfg: ModelConfig, *, dtype=jnp.float32):
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    ks = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(ks[0], d, H * (dn + dr), dtype=dtype),
        "wkv_a": L.dense_init(ks[1], d, r + dr, dtype=dtype),
        "kv_norm": L.rmsnorm_init(r, dtype=dtype),
        "wkv_b": L.dense_init(ks[2], r, H * (dn + dv), dtype=dtype),
        "wo": L.dense_init(ks[3], H * dv, d, dtype=dtype),
    }


def _mla_qkv(p, x, cfg: ModelConfig, positions, compute_dtype):
    """(q, k, v): q and k (B, T, H, nope + rope), v (B, T, H, v_head_dim)."""
    B, T, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    xc = x.astype(compute_dtype)
    q = (xc @ p["wq"].astype(compute_dtype)).reshape(B, T, H, dn + dr)
    q_r = L.apply_rope_interleaved(q[..., dn:], positions, cfg.rope_theta)
    kv = xc @ p["wkv_a"].astype(compute_dtype)
    c_kv = L.rmsnorm(kv[..., :r], p["kv_norm"], eps=cfg.rms_eps)
    k_r = L.apply_rope_interleaved(kv[..., None, r:], positions, cfg.rope_theta)  # (B,T,1,dr)
    kvb = (c_kv @ p["wkv_b"].astype(compute_dtype)).reshape(B, T, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], q_r], axis=-1)
    k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(k_r, (B, T, H, dr))], axis=-1)
    return q, k, kvb[..., dn:]


def mla_apply(p, x, cfg: ModelConfig, *, positions, impl="auto"):
    """Training / prefill forward.  x: (B, T, d).  Returns (out, (k, v))."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    q, k, v = _mla_qkv(p, x, cfg, positions, compute_dtype)
    o = fa.flash_attention(q, k, v, causal=True, impl=impl)
    B, T = x.shape[:2]
    out = o.reshape(B, T, cfg.n_heads * cfg.v_head_dim) @ p["wo"].astype(compute_dtype)
    return out.astype(x.dtype), (k, v)


def mla_decode_step(p, x_t, cache_k, cache_v, cur_len, cfg: ModelConfig):
    """One-token decode against per-head key/value caches (B, S, H, ·)."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    pos = jnp.full((x_t.shape[0],), cur_len, jnp.int32)[:, None]
    q, k, v = _mla_qkv(p, x_t, cfg, pos, compute_dtype)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), cur_len, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), cur_len, axis=1)
    o = fa.decode_attention(q, cache_k, cache_v, cur_len + 1)
    out = o.reshape(x_t.shape[0], 1, cfg.n_heads * cfg.v_head_dim) @ p["wo"].astype(compute_dtype)
    return out.astype(x_t.dtype), cache_k, cache_v
