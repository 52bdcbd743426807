"""moonlight_16b_a3b: the program's model configuration, weights and tokens
from the seed, and the plain reference of a training step.

The reference is Moonlight-16B-A3B's decoder (DeepSeek-V3 layout) written
from the published descriptions in plain ``jax.numpy``, float32 at
``Precision.HIGHEST``: token embedding; per layer RMSNorm → latent attention
(DeepSeek-V2 §2.1, arXiv:2405.04434, with ``q_lora_rank`` null: queries
W_q h split per head into 128 + 64; [c_kv, k_r] = W_kva h, c_kv RMSNorm-ed,
k_r rotated and shared by the heads; [k_nope, v] = W_kvb c_kv per head;
scores at 192^-0.5 over [q_nope, RoPE(q_r)]·[k_nope, k_r]; RoPE over
adjacent pairs, DeepSeek's published pairing) → residual → RMSNorm → the
dense SwiGLU MLP (layer 0) or the expert layer → residual; final RMSNorm,
the untied LM head over the vocabulary slice; causal-LM cross entropy.

The expert layer (DeepSeek-V3 §2.1.2, arXiv:2412.19437): sigmoid scores s
over all ``router_experts``; the top ``num_experts_per_tok`` by s + b (b the
selection bias); weights s / Σ_selected s × ``routed_scaling_factor``.
Departure: the held experts (the first ``n_routed_experts``) are computed
for every token and masked by their combine weights, where the program
computes only the routed pairs; the absent experts' part is left out in
both, as the configuration's cut says.  The 2 shared experts are one SwiGLU
of twice the expert width.  Each step adds α × the sequence-wise balance
term Σ_i f_i P_i of every expert layer to each sequence's loss, and after
the update moves b by γ·sign(mean load − load) with the load counted over
all experts for the tokens of the covered shards.  b takes no gradient and
no weight decay.

It imports nothing of the program, and reads the parameters by the layout
the trainer takes them in (``embed``, ``lead/lead0/...``, ``unit/slot0/...``
stacked over the expert layers, ``final_norm``, ``lm_head``).  Each layer
and each block of the LM head is recomputed in the backward pass, so that
it fits on the chip beside its own optimizer state once the program's is
freed.  Gradient coding, AdamW, the seeded weights and tokens and the
float8 control are those of ``qwen3_1_7b.py``, shared from there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import harness

_Q = harness.config_module("qwen3_1_7b")
fr_holders = _Q.fr_holders
make_tokens = _Q.make_tokens
leaf_norms = _Q.leaf_norms
host_diff_norm = _Q.host_diff_norm
MATMULS = _Q.MATMULS
HI = _Q.HI
BIAS = "router_bias"


# ------------------------------------------------------------ the program


def model_config(c: dict):
    """The program's ``ModelConfig`` for this configuration: the system
    under test's interface, built from the configuration file alone."""
    from repro.models.registry import ModelConfig, MoEConfig

    lead = c["first_k_dense_replace"]
    t = c["training"]
    return ModelConfig(
        name=c["name"], family="moe", vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        head_dim=c["v_head_dim"], lead=("mla_mlp",) * lead, scan_unit=("mla_moe",),
        kv_lora_rank=c["kv_lora_rank"], q_lora_rank=c["q_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"], qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"], rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]), mlp_act="silu_glu",
        tie_embeddings=bool(c["tie_word_embeddings"]), param_dtype="float32",
        compute_dtype="bfloat16",
        moe=MoEConfig(
            num_experts=c["router_experts"], top_k=c["num_experts_per_tok"],
            d_expert=c["moe_intermediate_size"], num_shared=c["n_shared_experts"],
            router_score=c["scoring_func"], renorm_topk=bool(c["norm_topk_prob"]),
            routed_scaling_factor=float(c["routed_scaling_factor"]),
            experts_held=c["n_routed_experts"], bias_update_rate=t["bias_update_rate"],
            balance_weight=t["balance_weight"],
        ),
    )


# ------------------------------------------------------------ weights


def make_params(seed: int, shapes, *, std: float):
    """Seeded weights in the trainer's layout: ones for every norm scale,
    zeros for the selection bias, normal(0, std) for every matrix."""
    params = _Q.make_params(seed, shapes, std=std)
    return jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.zeros_like(x) if BIAS in jax.tree_util.keystr(p) else x, params)


# ------------------------------------------------------------------- model


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope_pairs(x, theta):
    """x (B, T, H, d): rotate each adjacent pair (x[2i], x[2i+1]) by position."""
    T, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _mla(p, x, c, mm):
    B, T, _ = x.shape
    H, dn, dr, dv, r = (c["num_attention_heads"], c["qk_nope_head_dim"],
                        c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"])
    q = mm(x, p["wq"]).reshape(B, T, H, dn + dr)
    kv = mm(x, p["wkv_a"])
    c_kv = _rmsnorm(kv[..., :r], p["kv_norm"], c["rms_norm_eps"])
    k_r = _rope_pairs(kv[..., None, r:], c["rope_theta"])
    kvb = mm(c_kv, p["wkv_b"]).reshape(B, T, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope_pairs(q[..., dn:], c["rope_theta"])], axis=-1)
    k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(k_r, (B, T, H, dr))], axis=-1)
    qh, kh, vh = (jnp.transpose(t, (0, 2, 1, 3)) for t in (q, k, kvb[..., dn:]))
    s = mm(qh, jnp.swapaxes(kh, -1, -2)) * ((dn + dr) ** -0.5)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    o = mm(jax.nn.softmax(s, axis=-1), vh)  # (B, H, T, dv)
    return mm(jnp.transpose(o, (0, 2, 1, 3)).reshape(B, T, H * dv), p["wo"])


def _swiglu(p, x, mm):
    return mm(jax.nn.silu(mm(x, p["gate"])) * mm(x, p["up"]), p["down"])


def _experts(p, x, c, mm):
    """The expert layer.  Returns (out, load (B, E), balance (B,))."""
    B, T, d = x.shape
    E, k = c["router_experts"], c["num_experts_per_tok"]
    xf = x.reshape(B * T, d)
    s = jax.nn.sigmoid(mm(xf, p["router"]))  # (n, E)
    _, idx = jax.lax.top_k(s + jax.lax.stop_gradient(p[BIAS]), k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * c["routed_scaling_factor"]
    picks = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (n, k, E)
    comb = jnp.einsum("nk,nke->ne", w, picks)
    out = _swiglu(p["shared"], xf, mm)
    for e in range(p["w_gate"].shape[0]):  # the held experts, masked by weight
        ffn = {"gate": p["w_gate"][e], "up": p["w_up"][e], "down": p["w_down"][e]}
        out = out + comb[:, e:e + 1] * _swiglu(ffn, xf, mm)
    load = jnp.sum(picks.reshape(B, T * k, E), axis=1)
    frac = load * (E / (k * T))
    prob = jnp.mean((s / jnp.sum(s, -1, keepdims=True)).reshape(B, T, E), axis=1)
    return out.reshape(B, T, d), load, jnp.sum(frac * prob, axis=-1)


def _layer(p, x, c, mm, *, moe: bool):
    eps = c["rms_norm_eps"]
    x = x + _mla(p["attn"], _rmsnorm(x, p["attn_norm"], eps), c, mm)
    h = _rmsnorm(x, p["mlp_norm"], eps)
    if not moe:
        B = x.shape[0]
        return x + _swiglu(p["mlp"], h, mm), jnp.zeros((B, c["router_experts"])), jnp.zeros((B,))
    out, load, bal = _experts(p["moe"], h, c, mm)
    return x + out, load, bal


HEAD_BLOCK = 256  # positions of the LM head and its softmax computed at once


def row_losses(params, tokens, c, mm):
    """Per row of ``tokens`` (B, T): token-mean cross entropy, α × the
    balance terms summed over the expert layers, and each expert layer's
    load (L, B, E)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    bal = jnp.zeros((tokens.shape[0],))
    for name in sorted(params["lead"]):
        x, _, _ = jax.checkpoint(functools.partial(_layer, c=c, mm=mm, moe=False))(
            params["lead"][name], x)

    def layer(carry, p):
        x, bal = carry
        x, load, b = jax.checkpoint(lambda p, x: _layer(p, x, c, mm, moe=True))(p, x)
        return (x, bal + b), load

    (x, bal), loads = jax.lax.scan(layer, (x, bal), params["unit"]["slot0"])
    x = _rmsnorm(x, params["final_norm"], c["rms_norm_eps"])

    @jax.checkpoint
    def head(x, tgt, w):
        logp = jax.nn.log_softmax(mm(x, w), axis=-1)
        return jnp.sum(jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0], axis=1)

    T = tokens.shape[1] - 1
    total = 0.0
    for lo in range(0, T, HEAD_BLOCK):
        hi = min(lo + HEAD_BLOCK, T)
        total = total + head(x[:, lo:hi], tokens[:, lo + 1:hi + 1], params["lm_head"])
    return -total / T, c["training"]["balance_weight"] * bal, loads


@functools.partial(jax.jit, static_argnames=("c_items", "mode", "shards"))
def _loss_and_grad(params, tokens, weights, *, c_items, mode, shards):
    """Loss (1/S) Σ_j w_j (L_j + α B_j) over the rows of each shard, its
    gradient, and the expert load of the rows of the weighted shards."""
    c = dict(c_items)
    c["training"] = dict(c["training"])
    mm = MATMULS[mode]

    def loss(p):
        ce, bal, loads = row_losses(p, tokens, c, mm)
        per_shard = (ce + bal).reshape(shards, -1).mean(axis=1)
        row_w = jnp.repeat(weights, tokens.shape[0] // shards)
        return jnp.sum(weights * per_shard) / shards, jnp.einsum("lbe,b->le", loads, row_w)

    (value, load), grads = jax.value_and_grad(loss, has_aux=True)(params)
    return value, grads, load


def _items(c: dict) -> tuple:
    """The numbers of the configuration, hashable, for the jitted step."""
    keep = lambda v: isinstance(v, (int, float)) and not isinstance(v, bool)  # noqa: E731
    top = tuple(sorted((k, v) for k, v in c.items() if keep(v)))
    return top + (("training", tuple(sorted((k, v) for k, v in c["training"].items()
                                            if keep(v)))),)


def ref_steps(params0, tokens, masks, c: dict, *, mode: str = "f32", keep_shards=None) -> dict:
    """The first ``len(masks)`` training steps from ``params0``.  Returns each
    step's loss (None where skipped), the per-leaf norms of the first update's
    clipped gradient and of the parameters' change after the last step.

    ``mode="fp8"`` computes every matmul in float8 (the control);
    ``keep_shards`` leaves the other shards out of every step and takes the
    mean over the rest (a fault)."""
    t = c["training"]
    o = t["optimizer"]
    S = t["shards"]
    holders = fr_holders(t["groups"], S, t["redundancy"])
    params = jax.tree_util.tree_map(jnp.asarray, params0)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad, n_updates = [], None, 0
    with jax.default_matmul_precision("highest"):
        for step, alive in enumerate(masks):
            alive = np.asarray(alive, bool)
            if not alive.any():
                losses.append(None)
                continue
            w = np.array([float(alive[h].any()) for h in holders], np.float32)
            if keep_shards is not None:
                w = np.array([w[j] if j in keep_shards else 0.0 for j in range(S)], np.float32)
                w *= S / max(len(keep_shards), 1)
            pool = tokens[step % tokens.shape[0]]  # (S, rows, T)
            loss, grads, load = _loss_and_grad(
                params, jnp.asarray(pool.reshape(-1, pool.shape[-1])), jnp.asarray(w),
                c_items=_items(c), mode=mode, shards=S)
            losses.append(float(loss))
            n_updates += 1
            bias = jnp.array(params["unit"]["slot0"]["moe"][BIAS])  # a copy: the update donates
            params, m, v, g_norms = _Q._adamw(
                params, grads, m, v, jnp.float32(_Q._schedule(o, n_updates)),
                jnp.float32(1 - o["b1"] ** n_updates), jnp.float32(1 - o["b2"] ** n_updates),
                b1=o["b1"], b2=o["b2"], eps=o["eps"], wd=o["weight_decay"], clip=o["grad_clip"])
            mean = jnp.mean(load, axis=-1, keepdims=True)
            params["unit"]["slot0"]["moe"][BIAS] = bias + t["bias_update_rate"] * jnp.sign(mean - load)
            if first_grad is None:
                first_grad = jax.device_get(g_norms)
            del grads
    del m, v
    change = jax.tree_util.tree_map(
        lambda a, b: host_diff_norm(jax.device_get(a), b), params, params0)
    return {"losses": losses, "first_grad": first_grad, "change": change}
