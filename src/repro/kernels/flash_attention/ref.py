"""Pure-jnp oracle for flash attention (GQA, causal or full)."""

from __future__ import annotations

import jax.numpy as jnp

__all__ = ["attention_ref"]


def attention_ref(q, k, v, *, causal: bool = True, scale=None):
    """Naive full-materialization attention oracle.

    q: (B, T, H, dh); k: (B, S, KV, dh); v: (B, S, KV, dv) with H % KV == 0
    (GQA).  Returns (B, T, H, dv) in q.dtype; softmax in f32.
    """
    B, T, H, dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    g = H // KV
    scale = (dh ** -0.5) if scale is None else scale
    kr = jnp.repeat(k, g, axis=2)  # (B, S, H, dh)
    vr = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32), kr.astype(jnp.float32))
    s = s * scale
    if causal:
        # Query position t attends to keys ≤ t + (S − T) (decode alignment).
        qpos = jnp.arange(T)[:, None] + (S - T)
        kpos = jnp.arange(S)[None, :]
        s = jnp.where(qpos >= kpos, s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhts,bshd->bthd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)
