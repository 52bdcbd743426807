"""The jitted training step: loss → grad → (optional compression) → AdamW.

``group_weights`` carries the recovery vector of the step (Lemma 3 applied to
gradients); the gradient all-reduce/reduce-scatter pattern itself is emitted
by GSPMD from the FSDP/TP shardings the launcher installs.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..analysis import compiled_path
from ..models import transformer as T
from ..models.registry import ModelConfig, MoEConfig
from .compression import CompressionConfig, compress_with_error_feedback, init_ef_state
from .optimizer import AdamWConfig, OptState, adamw_update, init_opt_state

__all__ = [
    "TrainState",
    "init_train_state",
    "make_train_step",
    "make_eval_step",
    "make_group_grad_fn",
    "make_recovered_apply_fn",
]


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    ef: Any  # error-feedback buffers (None unless compression is on)


def init_train_state(
    key, cfg: ModelConfig, *, compression: Optional[CompressionConfig] = None
) -> TrainState:
    params = T.init_params(key, cfg)
    ef = init_ef_state(params) if (compression and compression.enabled) else None
    return TrainState(params=params, opt=init_opt_state(params), ef=ef)


def _split_microbatches(batch: dict, accum: int, num_groups: int) -> dict:
    """Group-aligned microbatch split: every array with a leading batch dim
    (G·per_g, …) becomes (A, G·per_g/A, …) with each microbatch containing an
    equal slice of EVERY group — so per-microbatch group-weighted losses
    average exactly to the full-batch weighted loss."""
    out = {}
    for k, v in batch.items():
        if k == "group_weights" or v.ndim == 0:
            out[k] = v
            continue
        b = v.shape[0]
        per_g = b // num_groups
        assert per_g % accum == 0, (k, v.shape, accum, num_groups)
        chunk = per_g // accum
        resh = v.reshape((num_groups, accum, chunk) + v.shape[1:])
        resh = jnp.moveaxis(resh, 1, 0)  # (A, G, chunk, …)
        out[k] = resh.reshape((accum, num_groups * chunk) + v.shape[1:])
    return out


@compiled_path("train.train_step", kind="factory")
def make_train_step(
    cfg: ModelConfig,
    ctx: T.ModelContext,
    opt_cfg: AdamWConfig,
    *,
    compression: Optional[CompressionConfig] = None,
    accum_steps: int = 1,
    num_groups: Optional[int] = None,
    donate: bool = True,
):
    """Returns train_step(state, batch) -> (state, metrics), ready to jit.

    ``accum_steps > 1`` runs gradient-accumulation microbatching (a scan over
    A group-aligned microbatches): activation working set ÷A at identical
    total FLOPs and collective bytes — the standard fit-the-HBM lever
    (§Perf iteration C3)."""

    def grad_of(params, batch):
        return jax.value_and_grad(
            lambda p: T.loss_fn(p, batch, cfg, ctx), has_aux=True
        )(params)

    def train_step(state: TrainState, batch):
        if accum_steps == 1:
            (loss, metrics), grads = grad_of(state.params, batch)
        else:
            G = num_groups or (
                batch["group_weights"].shape[0] if "group_weights" in batch else 1
            )
            micro = _split_microbatches(batch, accum_steps, G)
            gw = batch.get("group_weights")

            def body(gsum, mb):
                if gw is not None:
                    mb = dict(mb, group_weights=gw)
                (loss, metrics), g = grad_of(state.params, mb)
                gsum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(a.dtype), gsum, g
                )
                return gsum, (loss, metrics["ce"])

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            gsum, (losses, ces) = jax.lax.scan(
                body, zeros, {k: v for k, v in micro.items() if k != "group_weights"}
            )
            grads = jax.tree_util.tree_map(lambda g: g / accum_steps, gsum)
            loss = jnp.mean(losses)
            metrics = {"ce": jnp.mean(ces), "aux": jnp.zeros(()), "tokens": jnp.zeros(())}
        ef = state.ef
        if compression is not None and compression.enabled:
            grads, ef = compress_with_error_feedback(compression, grads, ef)
        params, opt, opt_metrics = adamw_update(opt_cfg, state.params, grads, state.opt)
        metrics = dict(metrics)
        moe = metrics.pop("moe", None)
        if moe:
            load = {k: v["load"] for k, v in moe.items()}
            params = T.moe_bias_step(params, state.params, load, cfg.moe.bias_update_rate)
            metrics.update(moe_metrics(load, cfg.moe.held))
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        return TrainState(params=params, opt=opt, ef=ef), metrics

    return train_step


def moe_metrics(load: dict, held: int) -> dict:
    """A step's routing counts from the per-block expert loads: the pairs on
    the held experts (the first ``held``), summed over the layers, and the
    busiest held expert of any layer over the mean held expert."""
    per = jnp.concatenate([v[..., :held].reshape(-1) for v in load.values()])
    pairs = jnp.sum(per)
    return {
        "moe_local_pairs": pairs,
        "moe_load_max_ratio": jnp.max(per) * per.size / jnp.maximum(pairs, 1.0),
    }


@compiled_path("train.group_grad", kind="factory")
def make_group_grad_fn(cfg: ModelConfig, ctx: T.ModelContext):
    """Per-group statistics function for ``Executor.resilient_reduce_masked``
    — the mesh-native resilient train step (Lemma 3 on gradients).

    Returns ``fn(tokens_pool_g, valid_g, params, pool_idx)`` where

    * ``tokens_pool_g`` — ``(P, C·mb, T)`` int32: group ``g``'s resident
      microbatch pool (``P`` step batches, ``C`` shard slots of ``mb``
      sequences each — ``C`` may exceed the group's load to leave headroom
      for elastic patches);
    * ``valid_g`` — ``(C,)`` float32: 1 for slots holding a real shard, 0 for
      padding (padded slots are inert in every statistic);
    * ``params`` — the model parameters (broadcast pytree);
    * ``pool_idx`` — scalar int32: which pool entry this step consumes
      (traced, so cycling the pool never recompiles).

    The function returns the group's **shard-sum** statistics
    ``{"grads", "loss", "ce", "tok"}`` — per-shard token-normalized losses
    summed over the group's valid shard slots, and the gradient of that sum.
    With experts it adds ``moe_load`` (the expert load of the valid slots,
    combined like the gradients, so it counts the tokens the update covers).
    The executor's Lemma-3 combine then yields  Σ_g b_g Σ_{s∈P_g} ∇L̄_s
    = Σ_s a_s ∇L̄_s  with ``a = bᵀA ∈ [1, 1+δ]ⁿ``: for δ = 0 (fractional
    repetition under any coverage-preserving pattern) this is EXACTLY
    ``n·∇(mean shard loss)`` — the full-data gradient, independent of the
    straggler pattern.  :func:`make_recovered_apply_fn` divides by ``n``.
    """

    def group_stats(tokens_pool, valid, params, pool_idx):
        tokens = jax.lax.dynamic_index_in_dim(
            tokens_pool, pool_idx, axis=0, keepdims=False
        )

        def shard_sum_loss(p):
            # loss_fn with group_weights=valid computes the valid-normalized
            # MEAN of per-shard token-normalized losses; rescaling by the
            # number of valid slots turns it into the shard SUM the Lemma-3
            # combine needs (empty groups contribute an exact zero).
            total, metrics = T.loss_fn(
                p, {"tokens": tokens, "group_weights": valid}, cfg, ctx
            )
            n_valid = jnp.sum(valid)
            return total * n_valid, (metrics["ce"] * n_valid, metrics["tokens"],
                                     metrics.get("moe"))

        (loss_sum, (ce_sum, tok, moe)), grads = jax.value_and_grad(
            shard_sum_loss, has_aux=True
        )(params)
        out = {"grads": grads, "loss": loss_sum, "ce": ce_sum, "tok": tok}
        if moe:
            out["moe_load"] = {k: v["load"] for k, v in moe.items()}
        return out

    return group_stats


@compiled_path("train.recovered_apply", kind="factory")
def make_recovered_apply_fn(
    opt_cfg: AdamWConfig,
    num_shards: int,
    *,
    compression: Optional[CompressionConfig] = None,
    moe: Optional[MoEConfig] = None,
):
    """Returns ``apply(state, stats) -> (state, metrics)``, ready to jit.

    ``stats`` is the Lemma-3-combined output of :func:`make_group_grad_fn`
    (shard-sum gradients/losses weighted by the recovery vector); dividing by
    the TOTAL shard count ``n`` — a pattern-independent constant — recovers
    the mean-loss gradient, so straggler and no-straggler steps apply
    numerically identical updates whenever the recovery band is exact.
    With experts (``moe``), the selection biases move against the combined
    load (see :func:`repro.models.moe.bias_step`), and the metrics carry the
    routing counts of the tokens the update covers (:func:`moe_metrics`).
    """
    scale = 1.0 / float(num_shards)

    def apply(state: TrainState, stats):
        grads = jax.tree_util.tree_map(
            lambda g: (g * jnp.asarray(scale, g.dtype)), stats["grads"]
        )
        ef = state.ef
        if compression is not None and compression.enabled:
            grads, ef = compress_with_error_feedback(compression, grads, ef)
        params, opt, opt_metrics = adamw_update(opt_cfg, state.params, grads, state.opt)
        metrics = {
            "loss": stats["loss"] * scale,
            "ce": stats["ce"] * scale,
            "tokens": stats["tok"],
        }
        if "moe_load" in stats:
            # A load counts whole pairs: the combine's weights carry the
            # on-device solve's rounding, which would tip sign(mean − load)
            # for an expert exactly at the mean.
            load = jax.tree_util.tree_map(jnp.round, stats["moe_load"])
            params = T.moe_bias_step(params, state.params, load, moe.bias_update_rate)
            metrics.update(moe_metrics(load, moe.held))
        metrics.update(opt_metrics)
        return TrainState(params=params, opt=opt, ef=ef), metrics

    return apply


@compiled_path("train.eval_step", kind="factory")
def make_eval_step(cfg: ModelConfig, ctx: T.ModelContext):
    def eval_step(params, batch):
        loss, metrics = T.loss_fn(params, batch, cfg, ctx)
        return {"loss": loss, **metrics}

    return eval_step
