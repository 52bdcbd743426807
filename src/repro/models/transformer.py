"""Generic decoder assembly for all ten assigned architectures.

The layer stack is ``cfg.lead + cfg.scan_unit × cfg.scan_repeats +
cfg.tail``; the body runs as one ``lax.scan`` over the repeats with per-slot
stacked parameters (compile time and HLO size O(1) in depth), optionally
rematerialized.

Three entry points:
  * :func:`forward_train`  — (B, T) tokens → logits (+ the expert layers'
    routing statistics)
  * :func:`loss_fn`        — group-weighted CE (+ the sequence-wise balance
    loss); the recovery weights of the paper's Lemma 3 enter *here* (see
    repro.train.resilient)
  * :func:`prefill` / :func:`decode_step` — serving paths with a pytree cache
    (KV for attention, recurrent state for mLSTM/sLSTM/RG-LRU)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import attention as A
from . import layers as L
from . import moe as M
from . import rglru as G
from . import xlstm as X
from .registry import ModelConfig

__all__ = [
    "ModelContext",
    "init_params",
    "forward_train",
    "loss_fn",
    "init_cache",
    "prefill",
    "decode_step",
    "param_count",
    "moe_bias_step",
]

_ATTN_BLOCKS = ("attn_mlp", "attn_moe", "lattn_mlp", "mla_mlp", "mla_moe")
_MOE_BLOCKS = ("attn_moe", "mla_moe")


@dataclasses.dataclass(frozen=True)
class ModelContext:
    """Execution context: mesh topology + implementation switches."""

    mesh: Any = None
    batch_axes: tuple = ()
    model_axis: Optional[str] = None
    fsdp_axis: Optional[str] = None
    attn_impl: str = "auto"
    remat: str = "none"  # none | full | dots
    # §Perf knobs (defaults = paper-faithful baseline behaviour)
    moe_routing: str = "pjit"  # pjit | local (route inside shard_map)
    collective_dtype: str = "default"  # default | bf16 (cast psum partials)

    def constrain(self, x, *spec):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*spec))
        )

    @property
    def batch_spec(self):
        if not self.batch_axes:
            return None
        return tuple(self.batch_axes) if len(self.batch_axes) > 1 else self.batch_axes[0]


# ------------------------------------------------------------------ params


def _block_init(key, bt: str, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 4)
    if bt in _ATTN_BLOCKS:
        attn_init = A.mla_init if bt.startswith("mla_") else A.attn_init
        p = {
            "attn_norm": L.rmsnorm_init(cfg.d_model, dtype=dtype),
            "attn": attn_init(ks[0], cfg, dtype=dtype),
            "mlp_norm": L.rmsnorm_init(cfg.d_model, dtype=dtype),
        }
        if bt in _MOE_BLOCKS:
            p["moe"] = M.moe_init(ks[1], cfg, dtype=dtype)
        else:
            p["mlp"] = L.mlp_init(
                ks[1], cfg.d_model, cfg.d_ff, gated=cfg.mlp_act != "gelu", dtype=dtype
            )
        return p
    if bt == "mlstm":
        return X.mlstm_init(ks[0], cfg, dtype=dtype)
    if bt == "slstm":
        return X.slstm_init(ks[0], cfg, dtype=dtype)
    if bt == "rglru_mlp":
        return G.rglru_init(ks[0], cfg, dtype=dtype)
    raise ValueError(f"unknown block type {bt!r}")


def init_params(key, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    d, V = cfg.d_model, cfg.vocab
    params: dict = {}
    if cfg.num_codebooks > 0:
        params["embed"] = (
            jax.random.normal(keys[0], (cfg.num_codebooks, V, d), dtype) * 0.02
        )
    else:
        params["embed"] = jax.random.normal(keys[0], (V, d), dtype) * 0.02

    if cfg.lead:
        params["lead"] = {
            f"lead{li}": _block_init(jax.random.fold_in(keys[4], li), bt, cfg, dtype)
            for li, bt in enumerate(cfg.lead)
        }
    unit = cfg.scan_unit
    reps = cfg.scan_repeats
    unit_params = {}
    for si, bt in enumerate(unit):
        slot_keys = jax.random.split(jax.random.fold_in(keys[1], si), reps)
        unit_params[f"slot{si}"] = jax.vmap(
            lambda k: _block_init(k, bt, cfg, dtype)
        )(slot_keys)
    params["unit"] = unit_params
    tail_params = {}
    for ti, bt in enumerate(cfg.tail):
        tail_params[f"tail{ti}"] = _block_init(
            jax.random.fold_in(keys[2], ti), bt, cfg, dtype
        )
    if tail_params:
        params["tail"] = tail_params
    params["final_norm"] = L.rmsnorm_init(d, dtype=dtype)
    if not cfg.tie_embeddings:
        head_v = V * max(cfg.num_codebooks, 1)
        params["lm_head"] = L.dense_init(keys[3], d, head_v, dtype=dtype, scale=0.02)
    return params


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


# ------------------------------------------------------------------ blocks


def _block_apply(bt: str, p, x, cfg: ModelConfig, ctx: ModelContext, positions):
    """Training/prefill forward for one block.  Returns (x, moe, cache): moe
    is the expert layer's routing statistics (None for other blocks)."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    moe = None
    cache = None
    if bt in _ATTN_BLOCKS:
        window = cfg.window if bt == "lattn_mlp" else None
        xn = L.rmsnorm(x, p["attn_norm"], eps=cfg.rms_eps)
        if bt.startswith("mla_"):
            a, kv = A.mla_apply(p["attn"], xn, cfg, positions=positions, impl=ctx.attn_impl)
        else:
            a, kv = A.attn_apply(
                p["attn"], xn, cfg, positions=positions, window=window, impl=ctx.attn_impl
            )
        x = x + a
        xn2 = L.rmsnorm(x, p["mlp_norm"], eps=cfg.rms_eps)
        if bt in _MOE_BLOCKS:
            mo, moe = M.moe_apply(
                p["moe"], xn2, cfg, mesh=ctx.mesh, batch_axes=ctx.batch_axes,
                model_axis=ctx.model_axis, fsdp_axis=ctx.fsdp_axis,
                routing=ctx.moe_routing,
            )
            x = x + mo
        else:
            x = x + L.mlp_apply(
                p["mlp"], xn2, act=cfg.mlp_act, compute_dtype=compute_dtype
            ).astype(x.dtype)
        if window is not None:
            k, v = kv
            keep = min(window, k.shape[1])
            kv = (k[:, -keep:], v[:, -keep:])
        cache = {"k": kv[0], "v": kv[1]}
    elif bt == "mlstm":
        x = X.mlstm_apply(p, x, cfg)
    elif bt == "slstm":
        x = X.slstm_apply(p, x, cfg, ctx=ctx)
    elif bt == "rglru_mlp":
        x = G.rglru_apply(p, x, cfg)
    else:
        raise ValueError(bt)
    return x, moe, cache


def _block_decode(bt: str, p, x_t, cache, cur_len, cfg: ModelConfig, ctx: ModelContext):
    """One-token decode for one block.  Returns (x_t, new_cache)."""
    if bt in _ATTN_BLOCKS:
        window = cfg.window if bt == "lattn_mlp" else None
        xn = L.rmsnorm(x_t, p["attn_norm"], eps=cfg.rms_eps)
        if bt.startswith("mla_"):
            a, ck, cv = A.mla_decode_step(p["attn"], xn, cache["k"], cache["v"], cur_len, cfg)
        else:
            a, ck, cv = A.attn_decode_step(
                p["attn"], xn, cache["k"], cache["v"], cur_len, cfg, window=window
            )
        x_t = x_t + a
        xn2 = L.rmsnorm(x_t, p["mlp_norm"], eps=cfg.rms_eps)
        if bt in _MOE_BLOCKS:
            mo, _ = M.moe_apply(
                p["moe"], xn2, cfg, mesh=ctx.mesh, batch_axes=ctx.batch_axes,
                model_axis=ctx.model_axis, fsdp_axis=ctx.fsdp_axis,
                routing=ctx.moe_routing,
            )
            x_t = x_t + mo
        else:
            x_t = x_t + L.mlp_apply(
                p["mlp"], xn2, act=cfg.mlp_act,
                compute_dtype=jnp.dtype(cfg.compute_dtype),
            ).astype(x_t.dtype)
        return x_t, {"k": ck, "v": cv}
    if bt == "mlstm":
        return X.mlstm_decode_step(p, cache, x_t, cfg)
    if bt == "slstm":
        return X.slstm_decode_step(p, cache, x_t, cfg)
    if bt == "rglru_mlp":
        return G.rglru_decode_step(p, cache, x_t, cfg)
    raise ValueError(bt)


# ------------------------------------------------------------------ embed


def _embed(params, batch, cfg: ModelConfig, ctx: ModelContext):
    """Token (+ modality-stub) embedding.  Returns (x (B, T, d), label_mask)."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    tokens = batch["tokens"]
    if cfg.num_codebooks > 0:
        # (B, K, T) EnCodec streams: sum the per-codebook embeddings.
        embs = []
        for kbook in range(cfg.num_codebooks):
            embs.append(jnp.take(params["embed"][kbook], tokens[:, kbook], axis=0))
        x = sum(embs).astype(compute_dtype)
        mask = jnp.ones(tokens.shape[::2], jnp.float32)  # (B, T)
    else:
        x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)
        mask = jnp.ones(tokens.shape, jnp.float32)
    if cfg.num_prefix_tokens > 0 and "prefix_embeds" in batch:
        pre = batch["prefix_embeds"].astype(compute_dtype)  # (B, P, d)
        x = jnp.concatenate([pre, x], axis=1)
        mask = jnp.concatenate(
            [jnp.zeros((x.shape[0], pre.shape[1]), jnp.float32), mask], axis=1
        )
    return ctx.constrain(x, ctx.batch_spec, None, None), mask


# ------------------------------------------------------------------ train


def _stack_forward(params, x, cfg: ModelConfig, ctx: ModelContext, positions):
    """The lead blocks, the scan over the repeating unit, the tail.  Returns
    (x, moe): moe maps each expert block's path ("unit/slot0", "lead/lead0")
    to its routing statistics with a leading layer axis."""
    unit = cfg.scan_unit
    moe = {}

    def single(group, name, bt, x):
        x, st, _ = _block_apply(bt, params[group][name], x, cfg, ctx, positions)
        if st is not None:
            moe[f"{group}/{name}"] = jax.tree_util.tree_map(lambda a: a[None], st)
        return x

    for li, bt in enumerate(cfg.lead):
        x = single("lead", f"lead{li}", bt, x)

    def unit_body(x, unit_p):
        stats = {}
        for si, bt in enumerate(unit):
            x, st, _ = _block_apply(bt, unit_p[f"slot{si}"], x, cfg, ctx, positions)
            if st is not None:
                stats[f"slot{si}"] = st
        x = ctx.constrain(x, ctx.batch_spec, None, None)
        return x, stats

    body = unit_body
    if ctx.remat == "full":
        body = jax.checkpoint(unit_body, prevent_cse=False)
    elif ctx.remat == "dots":
        body = jax.checkpoint(
            unit_body, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    x, unit_stats = jax.lax.scan(body, x, params["unit"])
    moe.update({f"unit/{k}": v for k, v in unit_stats.items()})
    for ti, bt in enumerate(cfg.tail):
        x = single("tail", f"tail{ti}", bt, x)
    return x, moe


def _logits(params, x, cfg: ModelConfig, ctx: ModelContext):
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = L.rmsnorm(x, params["final_norm"], eps=cfg.rms_eps)
    if cfg.tie_embeddings:
        head = params["embed"].T
    else:
        head = params["lm_head"]
    logits = x.astype(compute_dtype) @ head.astype(compute_dtype)
    if cfg.num_codebooks > 0:
        B, T = x.shape[:2]
        logits = logits.reshape(B, T, cfg.num_codebooks, cfg.vocab)
        return ctx.constrain(logits, ctx.batch_spec, None, None, ctx.model_axis)
    return ctx.constrain(logits, ctx.batch_spec, None, ctx.model_axis)


def forward_train(params, batch, cfg: ModelConfig, ctx: ModelContext):
    """Full training forward.  Returns (logits, moe, label_mask), moe the
    expert blocks' routing statistics by path (empty without experts)."""
    x, mask = _embed(params, batch, cfg, ctx)
    T = x.shape[1]
    positions = jnp.arange(T, dtype=jnp.int32)
    x, moe = _stack_forward(params, x, cfg, ctx, positions)
    return _logits(params, x, cfg, ctx), moe, mask


def loss_fn(params, batch, cfg: ModelConfig, ctx: ModelContext):
    """Group-weighted causal-LM cross entropy.

    ``batch["group_weights"]`` (G,) carries the paper's recovery weights b_g
    (zero at straggling groups); the batch's leading dim must be divisible by
    G.  Without the key, plain uniform weighting (b ≡ 1) is used.

    With experts, each group's loss adds ``balance_weight`` × the mean over
    its rows of the sequence-wise balance terms summed over the expert
    layers, and ``metrics["moe"]`` holds per expert block the expert load
    over all experts weighted as the rows are (``load``).
    """
    logits, moe, mask = forward_train(params, batch, cfg, ctx)
    tokens = batch["tokens"]
    if cfg.num_codebooks > 0:
        targets = tokens[:, :, 1:]  # (B, K, T−1)
        lg = logits[:, :-1].astype(jnp.float32)  # (B, T−1, K, V)
        lse = jax.nn.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(
            lg, targets.transpose(0, 2, 1)[..., None], axis=-1
        )[..., 0]
        ce = (lse - tgt).mean(-1)  # (B, T−1) mean over codebooks
        m = mask[:, 1:]
    else:
        prefix = logits.shape[1] - tokens.shape[1]
        lg = logits[:, prefix:, :][:, :-1].astype(jnp.float32)
        lg = lg - jax.nn.logsumexp(lg, axis=-1, keepdims=True)
        tgt = jnp.take_along_axis(lg, tokens[:, 1:][..., None], axis=-1)[..., 0]
        ce = -tgt
        m = mask[:, prefix:][:, 1:]
    B = ce.shape[0]
    gw = batch.get("group_weights")
    G = 1 if gw is None else gw.shape[0]
    row_w = jnp.ones((B,), jnp.float32) if gw is None else jnp.repeat(gw, B // G)

    def weighted(per_group):
        if gw is None:
            return jnp.mean(per_group)
        return jnp.sum(gw * per_group) / jnp.maximum(jnp.sum(gw), 1e-6)

    if gw is None:
        loss = jnp.sum(ce * m) / jnp.maximum(jnp.sum(m), 1.0)
    else:
        ce_g = ce.reshape(G, -1)
        m_g = m.reshape(G, -1)
        loss = weighted(jnp.sum(ce_g * m_g, axis=1) / jnp.maximum(jnp.sum(m_g, axis=1), 1.0))
    metrics = {"ce": loss, "aux": jnp.zeros((), jnp.float32), "tokens": jnp.sum(m)}
    total = loss
    if moe:
        balance = sum(jnp.sum(st["balance"], axis=0) for st in moe.values())  # (B,)
        metrics["aux"] = weighted(jnp.mean(balance.reshape(G, -1), axis=1))
        total = loss + cfg.moe.balance_weight * metrics["aux"]
        metrics["moe"] = {
            path: {"load": jnp.einsum("lbe,b->le", st["load"], row_w)} for path, st in moe.items()
        }
    return total, metrics


def moe_bias_step(params, prev, load: dict, rate: float):
    """``params`` with each expert block's selection bias set to its value in
    ``prev`` moved by the block's load (see :func:`repro.models.moe.bias_step`):
    the bias takes no gradient and no weight decay."""
    params = dict(params)
    for path, ld in load.items():
        group, name = path.split("/")
        blk = params[group][name]
        b = prev[group][name]["moe"]["router_bias"]
        moe = {**blk["moe"], "router_bias": M.bias_step(b, ld.reshape(b.shape), rate)}
        params[group] = {**params[group], name: {**blk, "moe": moe}}
    return params


# ------------------------------------------------------------------ serve


def _block_cache_init(bt: str, cfg: ModelConfig, B: int, max_len: int):
    dt = jnp.dtype(cfg.compute_dtype)
    if bt.startswith("mla_"):
        dk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        return {
            "k": jnp.zeros((B, max_len, cfg.n_heads, dk), dt),
            "v": jnp.zeros((B, max_len, cfg.n_heads, cfg.v_head_dim), dt),
        }
    if bt in ("attn_mlp", "attn_moe"):
        s = max_len
        z = jnp.zeros((B, s, cfg.n_kv_heads, cfg.head_dim), dt)
        return {"k": z, "v": z}
    if bt == "lattn_mlp":
        s = min(cfg.window or max_len, max_len)
        z = jnp.zeros((B, s, cfg.n_kv_heads, cfg.head_dim), dt)
        return {"k": z, "v": z}
    if bt == "mlstm":
        return X.mlstm_init_state(cfg, B)
    if bt == "slstm":
        return X.slstm_init_state(cfg, B)
    if bt == "rglru_mlp":
        return G.rglru_init_state(cfg, B, dtype=dt)
    raise ValueError(bt)


def init_cache(cfg: ModelConfig, B: int, max_len: int):
    reps = cfg.scan_repeats
    unit_cache = {}
    for si, bt in enumerate(cfg.scan_unit):
        one = _block_cache_init(bt, cfg, B, max_len)
        unit_cache[f"slot{si}"] = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l[None], (reps,) + l.shape), one
        )
    cache = {"unit": unit_cache}
    if cfg.lead:
        cache["lead"] = {
            f"lead{li}": _block_cache_init(bt, cfg, B, max_len)
            for li, bt in enumerate(cfg.lead)
        }
    if cfg.tail:
        cache["tail"] = {
            f"tail{ti}": _block_cache_init(bt, cfg, B, max_len)
            for ti, bt in enumerate(cfg.tail)
        }
    return cache


def decode_step(params, cache, tokens_t, cur_len, cfg: ModelConfig, ctx: ModelContext):
    """One decode step.  tokens_t: (B, 1) (or (B, K, 1) for codebooks);
    cur_len: scalar int32 count of tokens already in the cache.
    Returns (logits_t, new_cache)."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    if cfg.num_codebooks > 0:
        x = sum(
            jnp.take(params["embed"][kb], tokens_t[:, kb], axis=0)
            for kb in range(cfg.num_codebooks)
        ).astype(compute_dtype)
    else:
        x = jnp.take(params["embed"], tokens_t, axis=0).astype(compute_dtype)
    x = ctx.constrain(x, ctx.batch_spec, None, None)
    unit = cfg.scan_unit
    lead_c = {}
    for li, bt in enumerate(cfg.lead):
        x, lead_c[f"lead{li}"] = _block_decode(
            bt, params["lead"][f"lead{li}"], x, cache["lead"][f"lead{li}"], cur_len, cfg, ctx
        )

    def unit_body(x, slices):
        unit_p, unit_c = slices
        new_c = {}
        for si, bt in enumerate(unit):
            x, nc = _block_decode(
                bt, unit_p[f"slot{si}"], x, unit_c[f"slot{si}"], cur_len, cfg, ctx
            )
            new_c[f"slot{si}"] = nc
        return x, new_c

    x, new_unit_cache = jax.lax.scan(unit_body, x, (params["unit"], cache["unit"]))
    new_cache = {"unit": new_unit_cache}
    if cfg.lead:
        new_cache["lead"] = lead_c
    if cfg.tail:
        tail_c = {}
        for ti, bt in enumerate(cfg.tail):
            x, nc = _block_decode(
                bt, params["tail"][f"tail{ti}"], x, cache["tail"][f"tail{ti}"],
                cur_len, cfg, ctx,
            )
            tail_c[f"tail{ti}"] = nc
        new_cache["tail"] = tail_c
    logits = _logits(params, x, cfg, ctx)
    return logits, new_cache


def prefill(params, batch, cfg: ModelConfig, ctx: ModelContext):
    """Prefill forward: logits for every position + a filled cache.

    For attention blocks the cache is the computed K/V (window-clipped for
    local attention); recurrent blocks currently re-derive their state at
    decode time from scratch or continue from zeros — for the dry-run cells
    the returned structure is what matters.  Returns (logits, cache).
    """
    x, _ = _embed(params, batch, cfg, ctx)
    T = x.shape[1]
    positions = jnp.arange(T, dtype=jnp.int32)
    unit = cfg.scan_unit
    lead_c = {}
    for li, bt in enumerate(cfg.lead):
        x, _, lead_c[f"lead{li}"] = _block_apply(
            bt, params["lead"][f"lead{li}"], x, cfg, ctx, positions
        )

    def unit_body(carry, unit_p):
        x = carry
        caches = {}
        for si, bt in enumerate(unit):
            x, _, c = _block_apply(bt, unit_p[f"slot{si}"], x, cfg, ctx, positions)
            caches[f"slot{si}"] = c if c is not None else {}
        x = ctx.constrain(x, ctx.batch_spec, None, None)
        return x, caches

    x, unit_caches = jax.lax.scan(unit_body, x, params["unit"])
    cache = {"unit": unit_caches}
    if cfg.lead:
        cache["lead"] = lead_c
    if cfg.tail:
        tail_c = {}
        for ti, bt in enumerate(cfg.tail):
            x, _, c = _block_apply(
                bt, params["tail"][f"tail{ti}"], x, cfg, ctx, positions
            )
            tail_c[f"tail{ti}"] = c if c is not None else {}
        cache["tail"] = tail_c
    # Serving prefill only needs the next-token distribution: slice the last
    # position BEFORE the head matmul (a (B, T, V) logits tensor at 32k·151k
    # would be hundreds of GB; scoring paths use forward_train instead).
    return _logits(params, x[:, -1:], cfg, ctx), cache
