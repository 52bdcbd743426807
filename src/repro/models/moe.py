"""Mixture-of-Experts layer: dropless routing, held experts as one grouped
matmul over their routed pairs.

Every token is routed over all ``num_experts`` of the router (softmax, or
sigmoid with DeepSeek-V3's bias-corrected ``noaux_tc`` selection); the layer
holds ``experts_held`` of them, numbered from 0 (expert parallelism: the
chip's share of a layer), and computes their part of the result.  The
(token, expert) pairs that fall on the held experts are sorted by expert
and the SwiGLU FFN runs as three grouped matmuls over exactly those pairs
(megablox's ``gmm``, with ``tgmm`` for the weights' gradient: Pallas
kernels that visit only the tiles the groups cover; interpreted off the
TPU); the weighted results are scatter-added back.  No pair is dropped: a
token reaches at most ``min(top_k, held)`` held experts, so
``n · min(top_k, held)`` rows hold every routing.  On a TPU v5e at the
Moonlight cell's shapes megablox's forward and backward took 2.25 ms where
``jax.lax.ragged_dot``'s took 3.31 ms, and both followed the routed pairs
(PERF.md).

Under a mesh the held experts are split once more over the ``model`` axis:
each (data, model) device computes its slice, starting at
``axis_index · e_loc``, for its data shard's tokens, with the same function,
and one ``psum`` over ``model`` recombines routed + shared partial outputs.
Expert weights are additionally FSDP-sharded over ``data`` and explicitly
``all_gather``-ed inside the shard_map (autodiff turns that into the
reduce-scatter of the FSDP backward).

The layer also returns what training needs of the routing: each row's
expert load over all experts (the selection bias's update), the
sequence-wise balance term of DeepSeek-V3 §2.1.2 (arXiv:2412.19437), and the
pairs each held expert computed.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.pallas.ops.tpu.megablox import gmm as _megablox_gmm
from jax.sharding import PartitionSpec as P

from ..kernels import dispatch
from . import layers as L
from .registry import ModelConfig, MoEConfig

__all__ = ["moe_init", "moe_apply", "route", "bias_step"]

HI = jax.lax.Precision.HIGHEST


def moe_init(key, cfg: ModelConfig, *, dtype=jnp.float32):
    m = cfg.moe
    d, f = cfg.d_model, m.d_expert
    ks = jax.random.split(key, 6)

    def experts(k, din, dout):
        return (jax.random.normal(k, (m.held, din, dout), dtype) / np.sqrt(din)).astype(dtype)

    p = {
        "router": L.dense_init(ks[0], d, m.num_experts, dtype=dtype, scale=0.02),
        "w_gate": experts(ks[1], d, f),
        "w_up": experts(ks[2], d, f),
        "w_down": experts(ks[3], f, d),
    }
    if m.bias_update_rate > 0:
        p["router_bias"] = jnp.zeros((m.num_experts,), jnp.float32)
    if m.num_shared > 0:
        p["shared"] = L.mlp_init(ks[4], d, f * m.num_shared, gated=True, dtype=dtype)
    return p


def route(router_w, bias, x_flat, m: MoEConfig):
    """Scores over all experts, the top-k selection and its combine weights.

    Returns ``(idx (n, k) int32, w (n, k) f32, scores (n, E) f32)``.  The
    router runs in float32 at full precision.  The selection bias moves the
    choice only, never the weights, and takes no gradient."""
    logits = jnp.dot(x_flat.astype(jnp.float32), router_w.astype(jnp.float32), precision=HI)
    scores = jax.nn.sigmoid(logits) if m.router_score == "sigmoid" else jax.nn.softmax(logits, -1)
    sel = scores if bias is None else scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, idx = jax.lax.top_k(sel, m.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if m.renorm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * m.routed_scaling_factor, scores


def _route_stats(scores, idx, m: MoEConfig):
    """Per row (B, T, ·): the load over all experts (B, E) and the
    sequence-wise balance term Σ_i f_i P_i (B,), with f_i = E/(k T) × the
    row's picks of expert i and P_i the row's mean normalized score."""
    E, k = m.num_experts, m.top_k
    T = idx.shape[1]
    load = jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=(1, 2))  # (B, E)
    p = scores / jnp.sum(scores, -1, keepdims=True) if m.router_score == "sigmoid" else scores
    f = load * (E / (k * T))
    return load, jnp.sum(f * jnp.mean(p, axis=1), axis=-1)


TILE = 512  # rows, contraction and output tile of the grouped matmul


def _gmm(lhs, rhs, sizes, out_dtype):
    """``lhs[rows of group g] @ rhs[g]`` for each group; rows past the groups
    are left unwritten.  ``lhs``'s rows are a multiple of the row tile."""
    m, k = lhs.shape
    tiling = (min(TILE, m), min(TILE, k), min(TILE, rhs.shape[2]))
    return _megablox_gmm(lhs, rhs, sizes, out_dtype, tiling,
                         interpret=dispatch.backend() != "tpu")


# The backward keeps the matmuls' rows and makes the experts' compute-dtype
# weights again: kept, those copies held ~1.45 GB of a v5e at the Moonlight
# cell's 1 + 5 layers, and the step did not load (PERF.md).
@functools.partial(jax.checkpoint, static_argnums=(5,),
                   policy=jax.checkpoint_policies.save_only_these_names("expert_rows"))
def _ffn_rows(xs, wg, wu, wd, sizes, cd):
    """SwiGLU over the sorted rows, expert by expert: three grouped matmuls."""
    g = checkpoint_name(_gmm(xs, wg.astype(cd), sizes, cd), "expert_rows")
    u = checkpoint_name(_gmm(xs, wu.astype(cd), sizes, cd), "expert_rows")
    return _gmm(jax.nn.silu(g) * u, wd.astype(cd), sizes, jnp.float32)


def _expert_ffn(x_flat, idx, w, wg, wu, wd, first, compute_dtype):
    """Σ over the pairs on experts [first, first + e_loc) of w · FFN_e(x).

    Returns ``(out (n, d) f32, pairs per held expert (e_loc,) int32)``."""
    n, d = x_flat.shape
    k = idx.shape[1]
    e_loc = wg.shape[0]
    local = idx.reshape(-1) - first
    held = (local >= 0) & (local < e_loc)
    key = jnp.where(held, local, e_loc)
    rows = n * min(k, e_loc)
    tile = TILE if rows > TILE else 8
    padded = -(-rows // tile) * tile
    order = jnp.argsort(key, stable=True)[:rows]
    order = jnp.pad(order, (0, padded - rows))
    sizes = jnp.bincount(key, length=e_loc + 1)[:e_loc].astype(jnp.int32)
    total = jnp.sum(sizes)
    valid = jnp.arange(padded) < total
    tok = order // k
    cd = compute_dtype
    # Rows past the routed pairs hold no pair: they read zeros, and what the
    # kernel leaves in them (forward and backward) is selected away.
    xs = jnp.where(valid[:, None], jnp.take(x_flat, tok, axis=0), 0).astype(cd)
    y = _ffn_rows(xs, wg, wu, wd, sizes, cd)
    wt = jnp.where(valid, jnp.take(w.reshape(-1), order), 0.0)
    y = jnp.where(valid[:, None], y, 0.0) * wt[:, None]
    out = jnp.zeros((n, d), jnp.float32).at[tok].add(y)
    return out, sizes


def _moe_body(
    x, router_w, bias, wg, wu, wd, shared, routed,
    *, m: MoEConfig, compute_dtype, act: str,
    model_axis: Optional[str] = None, fsdp_axis: Optional[str] = None,
    batch_axes: tuple = (),
):
    """One device's part: route (unless ``routed`` gives idx, w), compute its
    held experts and the shared experts.  x: (B, T, d)."""
    if fsdp_axis is not None:
        wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, fsdp_axis, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, fsdp_axis, axis=2, tiled=True)
        if shared is not None:
            shared = {
                "gate": jax.lax.all_gather(shared["gate"], fsdp_axis, axis=0, tiled=True),
                "up": jax.lax.all_gather(shared["up"], fsdp_axis, axis=0, tiled=True),
                "down": jax.lax.all_gather(shared["down"], fsdp_axis, axis=1, tiled=True),
            }
    B, T, d = x.shape
    x_flat = x.reshape(B * T, d)
    if routed is None:
        idx, w, scores = route(router_w, bias, x_flat, m)
        load, balance = _route_stats(scores.reshape(B, T, -1), idx.reshape(B, T, -1), m)
    else:
        idx, w = (a.reshape(B * T, -1) for a in routed)
        load = balance = None
    e_loc = wg.shape[0]
    first = 0 if model_axis is None else jax.lax.axis_index(model_axis) * e_loc
    out, sizes = _expert_ffn(x_flat, idx, w, wg, wu, wd, first, compute_dtype)
    if shared is not None:
        # Shared experts: f_shared is sharded over `model`, so this is plain
        # Megatron TP — partial sums recombined by the same psum below.
        out = out + L.mlp_apply(shared, x_flat, act=act, compute_dtype=compute_dtype).astype(
            jnp.float32)
    if model_axis is not None:
        out = jax.lax.psum(out, model_axis)
    if batch_axes:
        sizes = jax.lax.psum(sizes, batch_axes)
    return out.reshape(B, T, d), sizes, load, balance


def moe_apply(
    p, x, cfg: ModelConfig, *, mesh=None, batch_axes=(), model_axis=None,
    fsdp_axis=None, routing: str = "pjit",
):
    """MoE block forward.  x: (B, T, d) → (out (B, T, d), stats) with stats
    ``{"load": (B, E) picks per row over all experts, "balance": (B,) the
    sequence-wise balance term, "held": (held,) pairs each held expert
    computed}``.

    ``routing="pjit"`` (baseline) computes router scores/top-k in pjit-land;
    ``routing="local"`` moves them inside the shard_map so the TopK stays on
    the data shard (no token all-gather — see §Perf)."""
    m = cfg.moe
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    B, T, d = x.shape
    shared = p.get("shared")
    bias = p.get("router_bias")
    body = functools.partial(_moe_body, m=m, compute_dtype=compute_dtype, act=cfg.mlp_act)
    weights = (p["w_gate"], p["w_up"], p["w_down"], shared)

    if mesh is None or model_axis is None or mesh.shape.get(model_axis, 1) == 1:
        out, sizes, load, balance = body(x, p["router"], bias, *weights, None)
        stats = {"load": load, "balance": balance, "held": sizes}
        return out.astype(x.dtype), stats

    fsdp = fsdp_axis if (fsdp_axis and mesh.shape.get(fsdp_axis, 1) > 1) else None
    batch_spec = tuple(batch_axes) if len(batch_axes) > 1 else batch_axes[0]
    weight_specs = (
        P(model_axis, fsdp, None),  # w_gate (E, d, f)
        P(model_axis, fsdp, None),  # w_up
        P(model_axis, None, fsdp),  # w_down (E, f, d)
        {"gate": P(fsdp, model_axis), "up": P(fsdp, model_axis), "down": P(model_axis, fsdp)}
        if shared is not None else None,
    )
    inner = functools.partial(body, model_axis=model_axis, fsdp_axis=fsdp,
                              batch_axes=tuple(batch_axes))
    x_spec = P(batch_spec, None, None)
    held_specs = (x_spec, P(model_axis))
    bias_spec = None if bias is None else P(None)

    if routing == "local":
        def local(x, router_w, bias, *ws):
            return inner(x, router_w, bias, *ws, None)

        out, sizes, load, balance = jax.shard_map(
            local, mesh=mesh,
            in_specs=(x_spec, P(None, None), bias_spec) + weight_specs,
            out_specs=held_specs + (P(batch_spec, None), P(batch_spec)),
            check_vma=False,
        )(x, p["router"], bias, *weights)
    else:
        idx, w, scores = route(p["router"], bias, x.reshape(B * T, d), m)
        idx, w = idx.reshape(B, T, -1), w.reshape(B, T, -1)
        load, balance = _route_stats(scores.reshape(B, T, -1), idx, m)

        def pjit_routed(x, idx, w, *ws):
            out, sizes, _, _ = inner(x, None, None, *ws, (idx, w))
            return out, sizes

        out, sizes = jax.shard_map(
            pjit_routed, mesh=mesh,
            in_specs=(x_spec, x_spec, x_spec) + weight_specs,
            out_specs=held_specs,
            check_vma=False,
        )(x, idx, w, *weights)
    stats = {"load": load, "balance": balance, "held": sizes}
    return out.astype(x.dtype), stats


def bias_step(bias, load, rate: float):
    """DeepSeek-V3's balancing move of the selection bias: γ·sign(mean load
    − load) per expert, the load counted over all experts.  ``load`` has
    ``bias``'s shape (a leading layer axis where the layers are stacked)."""
    mean = jnp.mean(load, axis=-1, keepdims=True)
    return bias + rate * jnp.sign(mean - load)
