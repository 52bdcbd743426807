"""Mixture-of-Experts layer: capacity-bounded expert parallelism.

TPU-native design (DESIGN.md §4.5): tokens stay resident on their data shard;
experts are sharded over the ``model`` mesh axis (E_loc = E/|model| per
shard); each (data, model) device selects the top-C local tokens for each of
its resident experts (``lax.top_k`` over the sparse gate column), gathers
them, runs the expert FFN as an E_loc-batched MXU matmul, scatter-adds back,
and a single ``psum`` over ``model`` recombines routed + shared partial
outputs.  No giant dispatch one-hots, no all-to-all; per-layer collective =
one (N_loc × d) psum — the same as dense tensor parallelism.

Expert weights are additionally FSDP-sharded over ``data`` and explicitly
``all_gather``-ed inside the shard_map (autodiff turns that into the
reduce-scatter of the FSDP backward).

Router scoring/top-k/aux-loss run in the outer pjit land (replicated over
``model``, sharded over batch) — they are O(N·E), negligible.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import layers as L
from .registry import ModelConfig, MoEConfig

__all__ = ["moe_init", "moe_apply"]


def moe_init(key, cfg: ModelConfig, *, dtype=jnp.float32):
    m = cfg.moe
    d, f = cfg.d_model, m.d_expert
    ks = jax.random.split(key, 6)
    import numpy as np

    def experts(k, din, dout):
        return (
            jax.random.normal(k, (m.num_experts, din, dout), dtype) / np.sqrt(din)
        ).astype(dtype)

    p = {
        "router": L.dense_init(ks[0], d, m.num_experts, dtype=dtype, scale=0.02),
        "w_gate": experts(ks[1], d, f),
        "w_up": experts(ks[2], d, f),
        "w_down": experts(ks[3], f, d),
    }
    if m.num_shared > 0:
        f_sh = f * m.num_shared
        p["shared"] = L.mlp_init(ks[4], d, f_sh, gated=True, dtype=dtype)
    return p


def _routing(p, x, m: MoEConfig, compute_dtype):
    """Router scores → (sparse combine weights (N, E) f32, aux loss scalar)."""
    B, T, d = x.shape
    n = B * T
    logits = (x.reshape(n, d).astype(jnp.float32)) @ p["router"].astype(jnp.float32)
    if m.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(scores, m.top_k)  # (n, k)
    if m.renorm_topk:
        vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32)  # (n, k, E)
    w_sparse = jnp.einsum("nk,nke->ne", vals, onehot)
    # Switch-style load-balance aux: E · Σ_e (token fraction)·(prob mass).
    frac = jnp.mean(jnp.sum(onehot, axis=1), axis=0) / m.top_k  # (E,)
    prob = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)  # (E,)
    aux = m.num_experts * jnp.sum(frac * prob)
    return w_sparse, aux


def _expert_compute(x_flat, w_cols, wg, wu, wd, capacity: int, compute_dtype):
    """Top-C dispatch → batched expert FFN → weighted scatter-add.

    x_flat: (N, d); w_cols: (N, E_loc) combine weights for resident experts;
    wg/wu/wd: (E_loc, d, f)/(E_loc, d, f)/(E_loc, f, d).  Returns (N, d).
    """
    n, d = x_flat.shape
    e_loc = w_cols.shape[1]
    c = min(capacity, n)
    vals, idx = jax.lax.top_k(w_cols.T, c)  # (E_loc, C) each
    xe = jnp.take(x_flat, idx.reshape(-1), axis=0).reshape(e_loc, c, d)
    xe = xe.astype(compute_dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg.astype(compute_dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xe, wu.astype(compute_dtype))
    out = jnp.einsum("ecf,efd->ecd", h, wd.astype(compute_dtype))
    out = out * vals[..., None].astype(compute_dtype)  # zero-weight slots are inert
    flat = jnp.zeros((n, d), compute_dtype)
    return flat.at[idx.reshape(-1)].add(out.reshape(-1, d))


def _routing_flat(router_w, x_flat, m: MoEConfig):
    """Router on an (N, d) block — used by the shard-local routing path so the
    TopK never leaves the data shard (GSPMD cannot shard the TopK custom-call;
    pjit-land routing costs a full-token all-gather — §Perf iteration 1)."""
    logits = x_flat.astype(jnp.float32) @ router_w.astype(jnp.float32)
    if m.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(scores, m.top_k)
    if m.renorm_topk:
        vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32)
    w_sparse = jnp.einsum("nk,nke->ne", vals, onehot)
    frac = jnp.mean(jnp.sum(onehot, axis=1), axis=0) / m.top_k
    prob = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)
    aux = m.num_experts * jnp.sum(frac * prob)
    return w_sparse, aux


def _moe_inner_local(
    x_flat, router_w, wg, wu, wd, shared,
    *, mcfg: MoEConfig, capacity: int, compute_dtype,
    model_axis: Optional[str], fsdp_axis: Optional[str], act: str,
    batch_axes: tuple = (),
):
    """Shard-local body: routing AND expert compute inside shard_map."""
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
    w_sparse, aux = _routing_flat(router_w, x_flat, mcfg)
    e_loc = wg.shape[0]
    if model_axis is not None:
        shard = jax.lax.axis_index(model_axis)
        w_cols = jax.lax.dynamic_slice_in_dim(w_sparse, shard * e_loc, e_loc, axis=1)
    else:
        w_cols = w_sparse
    partial = _expert_compute(x_flat, w_cols, wg, wu, wd, capacity, compute_dtype)
    if shared is not None:
        partial = partial + L.mlp_apply(shared, x_flat, act=act, compute_dtype=compute_dtype)
    if model_axis is not None:
        partial = jax.lax.psum(partial, model_axis)
    if batch_axes:
        aux = jax.lax.pmean(aux, batch_axes)
    return partial, aux


def _moe_inner(
    x_flat, w_sparse, wg, wu, wd, shared,
    *, mcfg: MoEConfig, capacity: int, compute_dtype,
    model_axis: Optional[str], fsdp_axis: Optional[str], act: str,
):
    """Per-device body (runs under shard_map when a mesh is active)."""
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
    e_loc = wg.shape[0]
    if model_axis is not None:
        shard = jax.lax.axis_index(model_axis)
        w_cols = jax.lax.dynamic_slice_in_dim(w_sparse, shard * e_loc, e_loc, axis=1)
    else:
        w_cols = w_sparse
    partial = _expert_compute(x_flat, w_cols, wg, wu, wd, capacity, compute_dtype)
    if shared is not None:
        # Shared experts: f_shared is sharded over `model`, so this is plain
        # Megatron TP — partial sums recombined by the same psum below.
        partial = partial + L.mlp_apply(
            shared, x_flat, act=act, compute_dtype=compute_dtype
        )
    if model_axis is not None:
        partial = jax.lax.psum(partial, model_axis)
    return partial


def moe_apply(
    p, x, cfg: ModelConfig, *, mesh=None, batch_axes=(), model_axis=None,
    fsdp_axis=None, routing: str = "pjit",
):
    """MoE block forward.  x: (B, T, d) → (out (B, T, d), aux_loss scalar).

    ``routing="pjit"`` (baseline) computes router scores/top-k in pjit-land;
    ``routing="local"`` moves them inside the shard_map so the TopK stays on
    the data shard (no token all-gather — see §Perf)."""
    m = cfg.moe
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    B, T, d = x.shape
    n = B * T
    x_flat = x.reshape(n, d)
    shared = p.get("shared")

    if mesh is None or model_axis is None or mesh.shape.get(model_axis, 1) == 1:
        w_sparse, aux = _routing(p, x, m, compute_dtype)
        capacity = max(1, int(n * m.top_k * m.capacity_factor / m.num_experts))
        out = _moe_inner(
            x_flat, w_sparse, p["w_gate"], p["w_up"], p["w_down"], shared,
            mcfg=m, capacity=capacity, compute_dtype=compute_dtype,
            model_axis=None, fsdp_axis=None, act=cfg.mlp_act,
        )
        return out.reshape(B, T, d).astype(x.dtype), aux

    n_data = 1
    for ax in batch_axes:
        n_data *= mesh.shape[ax]
    n_loc = max(1, n // n_data)
    capacity = max(1, int(n_loc * m.top_k * m.capacity_factor / m.num_experts))
    fsdp = fsdp_axis if (fsdp_axis and mesh.shape.get(fsdp_axis, 1) > 1) else None
    batch_spec = tuple(batch_axes) if len(batch_axes) > 1 else batch_axes[0]
    weight_specs = (
        P(model_axis, fsdp, None),  # w_gate (E, d, f)
        P(model_axis, fsdp, None),  # w_up
        P(model_axis, None, fsdp),  # w_down (E, f, d)
    )
    shared_specs = (
        {
            "gate": P(fsdp, model_axis),
            "up": P(fsdp, model_axis),
            "down": P(model_axis, fsdp),
        }
        if shared is not None
        else None
    )

    if routing == "local":
        inner = functools.partial(
            _moe_inner_local, mcfg=m, capacity=capacity,
            compute_dtype=compute_dtype, model_axis=model_axis, fsdp_axis=fsdp,
            act=cfg.mlp_act, batch_axes=tuple(batch_axes),
        )
        out, aux = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(P(batch_spec, None), P(None, None)) + weight_specs + (shared_specs,),
            out_specs=(P(batch_spec, None), P()),
            check_vma=False,
        )(x_flat, p["router"], p["w_gate"], p["w_up"], p["w_down"], shared)
        return out.reshape(B, T, d).astype(x.dtype), aux

    w_sparse, aux = _routing(p, x, m, compute_dtype)
    inner = functools.partial(
        _moe_inner, mcfg=m, capacity=capacity, compute_dtype=compute_dtype,
        model_axis=model_axis, fsdp_axis=fsdp, act=cfg.mlp_act,
    )
    out = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(batch_spec, None), P(batch_spec, None)) + weight_specs + (shared_specs,),
        out_specs=P(batch_spec, None),
        check_vma=False,
    )(x_flat, w_sparse, p["w_gate"], p["w_up"], p["w_down"], shared)
    return out.reshape(B, T, d).astype(x.dtype), aux
