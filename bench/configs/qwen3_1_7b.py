"""qwen3_1_7b: the program's model configuration, weights and tokens from
the seed, and the plain reference of a training step.

The reference is Qwen3's decoder layer written from the published
description in plain ``jax.numpy``: token embedding, per layer RMSNorm →
grouped-query attention with RMSNorm on each head's queries and keys and
rotary embedding (half-split, θ = rope_theta) → residual → RMSNorm → SwiGLU
MLP → residual, a final RMSNorm and the LM head tied to the embedding;
causal-LM cross entropy.  It imports nothing of the program.  It reads the
parameters by the layout the trainer takes them in (``embed``,
``unit/slot0/...`` stacked over layers, ``final_norm``), which is the
program's interface, not its output.  It recomputes each layer and each
block of the LM head in the backward pass, so that it fits on the chip
beside its own optimizer state once the program's is freed.

Gradient coding: each step's update is the gradient of the mean over all
shards of each covered shard's token-mean loss (a shard is covered when one
of the groups holding it is alive); a step with no group alive is skipped.
AdamW as the configuration states (global-norm clipping, warmup-cosine
schedule, decoupled weight decay).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# ------------------------------------------------------------ the program


def model_config(c: dict):
    """The program's ``ModelConfig`` for this configuration: the system
    under test's interface, built from the configuration file alone."""
    from repro.models.registry import ModelConfig

    return ModelConfig(
        name=c["name"], family="dense", vocab=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        head_dim=c["head_dim"], scan_unit=("attn_mlp",), qk_norm=True,
        qkv_bias=bool(c["attention_bias"]), rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]), mlp_act="silu_glu",
        tie_embeddings=bool(c["tie_word_embeddings"]), param_dtype="float32",
        compute_dtype="bfloat16",
    )


# ------------------------------------------------------------ weights, data


def make_params(seed: int, shapes, *, std: float):
    """Seeded weights in the trainer's layout, made on the device in one
    call: ones for every norm scale, normal(0, std) for every matrix."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [jax.tree_util.keystr(p) for p, _ in leaves]
    structs = [s for _, s in leaves]

    @jax.jit
    def build(key):
        out = []
        for i, (path, s) in enumerate(zip(paths, structs)):
            if "norm" in path:
                out.append(jnp.ones(s.shape, s.dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, s.shape, jnp.float32) * std).astype(s.dtype))
        return out

    return jax.tree_util.tree_unflatten(treedef, build(jax.random.PRNGKey(seed)))


def make_tokens(seed: int, *, pool: int, shards: int, rows: int, seq_len: int,
                vocab: int) -> np.ndarray:
    """(pool, shards, rows, seq_len) int32 token ids, uniform over the
    vocabulary."""
    r = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    return r.integers(0, vocab, size=(pool, shards, rows, seq_len), dtype=np.int32)


def fr_holders(groups: int, shards: int, redundancy: int) -> list:
    """Groups holding each shard under fractional repetition: the groups
    form ``redundancy`` replica sets, each splitting the shards in order."""
    per = groups // redundancy
    return [[rep * per + (j * per) // shards for rep in range(redundancy)]
            for j in range(shards)]


# ---------------------------------------------------------------- matmuls


def mm_f32(a, b):
    return jnp.matmul(a, b, precision=HI)


def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(F8), s


def _qmm(a, b):
    qa, sa = _q8(a)
    qb, sb = _q8(b)
    return jnp.matmul(qa, qb, preferred_element_type=jnp.float32) * (sa * sb)


@jax.custom_vjp
def mm_fp8(a, b):
    """Matmul with both operands scaled to float8 (e4m3) per tensor, forward
    and backward: the control's precision, one step below bfloat16."""
    return _qmm(a, b)


def _mm_fp8_fwd(a, b):
    return _qmm(a, b), (a, b)


def _mm_fp8_bwd(res, g):
    a, b = res
    ga = _qmm(g, jnp.swapaxes(b, -1, -2))
    if a.ndim == b.ndim:
        gb = _qmm(jnp.swapaxes(a, -1, -2), g)
    else:  # a (..., K) against a shared (K, N): sum over a's leading axes
        a2 = a.reshape(-1, a.shape[-1])
        gb = _qmm(a2.T, g.reshape(-1, g.shape[-1]))
    return ga, gb


mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)

MATMULS = {"f32": mm_f32, "fp8": mm_fp8}


# ------------------------------------------------------------------- model


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, T, H, dh): rotate the two halves of each head by position."""
    T, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(p, x, c, mm):
    B, T, d = x.shape
    H, KV, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    eps = c["rms_norm_eps"]
    h = _rmsnorm(x, p["attn_norm"], eps)
    q = mm(h, p["attn"]["wq"]).reshape(B, T, H, dh)
    k = mm(h, p["attn"]["wk"]).reshape(B, T, KV, dh)
    v = mm(h, p["attn"]["wv"]).reshape(B, T, KV, dh)
    q = _rope(_rmsnorm(q, p["attn"]["q_norm"], eps), c["rope_theta"])
    k = _rope(_rmsnorm(k, p["attn"]["k_norm"], eps), c["rope_theta"])
    rep = H // KV
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    qh, kh, vh = (jnp.transpose(t, (0, 2, 1, 3)) for t in (q, k, v))  # (B, H, T, dh)
    s = mm(qh, jnp.swapaxes(kh, -1, -2)) * (dh ** -0.5)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = mm(jax.nn.softmax(s, axis=-1), vh)  # (B, H, T, dh)
    o = jnp.transpose(o, (0, 2, 1, 3)).reshape(B, T, H * dh)
    x = x + mm(o, p["attn"]["wo"])
    h = _rmsnorm(x, p["mlp_norm"], eps)
    g = mm(h, p["mlp"]["gate"])
    u = mm(h, p["mlp"]["up"])
    return x + mm(jax.nn.silu(g) * u, p["mlp"]["down"])


HEAD_BLOCK = 256  # positions of the LM head and its softmax computed at once


def shard_losses(params, tokens, c, mm):
    """Token-mean cross entropy of each row of ``tokens`` (B, T)."""
    x = jnp.take(params["embed"], tokens, axis=0)

    def layer(x, p):
        return jax.checkpoint(lambda x, p: _layer(p, x, c, mm))(x, p), None

    x, _ = jax.lax.scan(layer, x, params["unit"]["slot0"])
    x = _rmsnorm(x, params["final_norm"], c["rms_norm_eps"])

    @jax.checkpoint
    def head(x, tgt, emb):
        logp = jax.nn.log_softmax(mm(x, emb.T), axis=-1)
        return jnp.sum(jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0], axis=1)

    T = tokens.shape[1] - 1
    total = 0.0
    for lo in range(0, T, HEAD_BLOCK):
        hi = min(lo + HEAD_BLOCK, T)
        total = total + head(x[:, lo:hi], tokens[:, lo + 1:hi + 1], params["embed"])
    return -total / T


@functools.partial(jax.jit, static_argnames=("c_items", "mode", "shards"))
def _loss_and_grad(params, tokens, weights, *, c_items, mode, shards):
    """Loss (1/S) Σ_j w_j L_j over the rows of each shard, and its gradient."""
    c = dict(c_items)
    mm = MATMULS[mode]

    def loss(p):
        per_row = shard_losses(p, tokens, c, mm)
        per_shard = per_row.reshape(shards, -1).mean(axis=1)
        return jnp.sum(weights * per_shard) / shards

    return jax.value_and_grad(loss)(params)


def _schedule(o, step):
    warm = min(step / max(o["warmup_steps"], 1), 1.0)
    prog = min(max((step - o["warmup_steps"]) / max(o["total_steps"] - o["warmup_steps"], 1), 0.0), 1.0)
    return o["lr"] * warm * (o["min_lr_ratio"] + (1.0 - o["min_lr_ratio"]) * 0.5 * (1.0 + np.cos(np.pi * prog)))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "clip"),
                   donate_argnums=(0, 2, 3))
def _adamw(params, grads, m, v, lr, b1t, b2t, *, b1, b2, eps, wd, clip):
    """One AdamW update; returns the new state and the per-leaf norms of
    the clipped gradient."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
    g = jax.tree_util.tree_map(lambda x: x * scale, grads)
    m = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    params = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * ((a / b1t) / (jnp.sqrt(b / b2t) + eps) + wd * p), params, m, v)
    return params, m, v, leaf_norms(g)


@jax.jit
def leaf_norms(tree):
    return jax.tree_util.tree_map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def host_diff_norm(a, b) -> float:
    """‖a − b‖ of two host arrays, the difference in float32 and its squares
    summed in float64 by blocks: a float32 sum over the hundreds of millions
    of elements of one leaf reads low by up to a few tenths of a per cent."""
    d = (np.asarray(a, np.float32) - np.asarray(b, np.float32)).ravel()
    total = 0.0
    for lo in range(0, d.size, 1 << 24):
        blk = d[lo:lo + (1 << 24)].astype(np.float64)
        total += float(np.dot(blk, blk))
    return float(np.sqrt(total))


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
    c_items = tuple(sorted((k, v) for k, v in c.items() if isinstance(v, (int, float)) and not isinstance(v, bool)))
    params = jax.tree_util.tree_map(jnp.asarray, params0)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad, n_updates = [], None, 0
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
        loss, grads = _loss_and_grad(
            params, jnp.asarray(pool.reshape(-1, pool.shape[-1])), jnp.asarray(w),
            c_items=c_items, mode=mode, shards=S)
        losses.append(float(loss))
        n_updates += 1
        lr = _schedule(o, n_updates)
        params, m, v, g_norms = _adamw(
            params, grads, m, v, jnp.float32(lr), jnp.float32(1 - o["b1"] ** n_updates),
            jnp.float32(1 - o["b2"] ** n_updates), b1=o["b1"], b2=o["b2"], eps=o["eps"],
            wd=o["weight_decay"], clip=o["grad_clip"])
        if first_grad is None:
            first_grad = jax.device_get(g_norms)
        del grads
    del m, v
    change = jax.tree_util.tree_map(
        lambda a, b: host_diff_norm(jax.device_get(a), b), params, params0)
    return {"losses": losses, "first_grad": first_grad, "change": change}
