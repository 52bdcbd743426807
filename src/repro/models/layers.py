"""Shared neural building blocks (pure functional: init → params, apply)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "dense_init",
    "rmsnorm_init",
    "rmsnorm",
    "rope_freqs",
    "apply_rope",
    "apply_rope_interleaved",
    "mlp_init",
    "mlp_apply",
    "causal_conv1d_init",
    "causal_conv1d",
    "causal_conv1d_step",
]


def dense_init(key, d_in: int, d_out: int, *, dtype=jnp.float32, scale: float | None = None):
    scale = (1.0 / np.sqrt(d_in)) if scale is None else scale
    return (jax.random.normal(key, (d_in, d_out), dtype) * scale).astype(dtype)


def rmsnorm_init(d: int, *, dtype=jnp.float32):
    return jnp.ones((d,), dtype)


def rmsnorm(x, scale, *, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dt)


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (B, T, H, dh); positions: (T,) or (B, T)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # (dh/2,)
    pos = positions.astype(jnp.float32)
    if pos.ndim == 1:
        pos = pos[None, :]  # (1, T)
    ang = pos[..., None] * freqs[None, None, :]  # (B?, T, dh/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rope_interleaved(x, positions, theta: float):
    """RoPE over adjacent pairs (x[2i], x[2i+1]), the pairing of DeepSeek's
    published MLA weights.  x: (B, T, H, dh); positions: (T,) or (B, T)."""
    dh = x.shape[-1]
    pos = positions.astype(jnp.float32)
    if pos.ndim == 1:
        pos = pos[None, :]
    ang = pos[..., None] * rope_freqs(dh, theta)[None, None, :]  # (B?, T, dh/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xp = x.astype(jnp.float32).reshape(x.shape[:-1] + (dh // 2, 2))
    x1, x2 = xp[..., 0], xp[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def mlp_init(key, d: int, d_ff: int, *, gated: bool, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {"down": dense_init(ks[1], d_ff, d, dtype=dtype)}
    if gated:
        p["gate"] = dense_init(ks[0], d, d_ff, dtype=dtype)
        p["up"] = dense_init(ks[2], d, d_ff, dtype=dtype)
    else:
        p["up"] = dense_init(ks[0], d, d_ff, dtype=dtype)
    return p


def mlp_apply(p, x, *, act: str, compute_dtype):
    xc = x.astype(compute_dtype)
    if "gate" in p:
        g = xc @ p["gate"].astype(compute_dtype)
        u = xc @ p["up"].astype(compute_dtype)
        h = (jax.nn.silu(g) if act == "silu_glu" else jax.nn.gelu(g)) * u
    else:
        h = jax.nn.gelu(xc @ p["up"].astype(compute_dtype))
    return h @ p["down"].astype(compute_dtype)


def causal_conv1d_init(key, d: int, width: int, *, dtype=jnp.float32):
    return {
        "w": jax.random.normal(key, (width, d), dtype) / np.sqrt(width),
        "b": jnp.zeros((d,), dtype),
    }


def causal_conv1d(p, x):
    """Depthwise causal conv over time.  x: (B, T, d) → (B, T, d)."""
    w = p["w"].astype(x.dtype)  # (W, d)
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(width):  # width is tiny (4): unrolled adds, no conv op
        out = out + pad[:, i : i + x.shape[1], :] * w[i]
    return out + p["b"].astype(x.dtype)


def causal_conv1d_step(p, state, x_t):
    """Single decode step.  state: (B, W−1, d) past inputs; x_t: (B, d)."""
    w = p["w"].astype(x_t.dtype)
    width = w.shape[0]
    window = jnp.concatenate([state, x_t[:, None, :]], axis=1)  # (B, W, d)
    out = jnp.einsum("bwd,wd->bd", window, w) + p["b"].astype(x_t.dtype)
    return window[:, 1:], out  # new state drops the oldest column
