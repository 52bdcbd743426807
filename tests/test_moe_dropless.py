"""The dropless expert layer against a dense masked reference, its shares
under expert parallelism, and the selection bias's balancing move."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import moe as M
from repro.models.registry import ModelConfig, MoEConfig


def _cfg(score="sigmoid"):
    moe = MoEConfig(
        num_experts=8, top_k=3, d_expert=24, num_shared=1, router_score=score,
        renorm_topk=score == "sigmoid", routed_scaling_factor=2.446 if score == "sigmoid" else 1.0,
        bias_update_rate=0.001 if score == "sigmoid" else 0.0, balance_weight=0.0001,
    )
    return ModelConfig(name="moe-tiny", family="moe", vocab=64, d_model=32, n_layers=1,
                       n_heads=4, n_kv_heads=4, d_ff=48, scan_unit=("attn_moe",), moe=moe,
                       compute_dtype="float32").validate()


def _swiglu(x, g, u, d):
    return (jax.nn.silu(x @ g) * (x @ u)) @ d


def _dense_reference(p, x, c):
    """Every held expert on every token, masked by its combine weight:
    s = σ(x W_r), the top-k of s + b, weights s / Σ s × scaling."""
    m = c.moe
    xf = x.reshape(-1, x.shape[-1])
    logits = xf @ p["router"]
    s = jax.nn.sigmoid(logits) if m.router_score == "sigmoid" else jax.nn.softmax(logits, -1)
    sel = s + p["router_bias"] if "router_bias" in p else s
    _, idx = jax.lax.top_k(sel, m.top_k)
    w = jnp.take_along_axis(s, idx, -1)
    if m.renorm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    comb = jnp.einsum("nk,nke->ne", w * m.routed_scaling_factor,
                      jax.nn.one_hot(idx, m.num_experts))
    out = jnp.zeros_like(xf)
    for e in range(p["w_gate"].shape[0]):
        out = out + comb[:, e:e + 1] * _swiglu(
            xf, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    if "shared" in p:
        sh = p["shared"]
        out = out + _swiglu(xf, sh["gate"], sh["up"], sh["down"])
    return out.reshape(x.shape)


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_dropless_layer_matches_dense_masked_reference(score):
    c = _cfg(score=score)
    p = M.moe_init(jax.random.PRNGKey(0), c)
    if "router_bias" in p:
        p["router_bias"] = jax.random.normal(jax.random.PRNGKey(5), (8,)) * 0.05
    # An uneven router: most tokens pick the same experts, which a capacity
    # bound would have dropped.
    p["router"] = p["router"].at[:, :2].add(0.5)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, c.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, stats = M.moe_apply(p, x, c)
        want = _dense_reference(p, x, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert int(jnp.sum(stats["held"])) == 32 * c.moe.top_k  # every pair computed
    np.testing.assert_array_equal(np.asarray(jnp.sum(stats["load"], 0)), np.asarray(stats["held"]))


def test_held_shares_add_up_to_the_uncut_layer():
    """Expert parallelism: 8 experts in shares of 2, each share's layer
    computing only its own experts (numbered from 0 in its weights, routed
    over all 8).  The routed parts of the four shares, with the shared
    expert counted once, add up to the uncut layer."""
    full = _cfg()
    p = M.moe_init(jax.random.PRNGKey(2), full)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, full.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, _ = M.moe_apply(p, x, full)
        xf = x.reshape(-1, x.shape[-1])
        idx, w, _ = M.route(p["router"], p["router_bias"], xf, full.moe)
        total, pairs = jnp.zeros_like(xf), 0
        for first in range(0, 8, 2):
            part = [p[k][first:first + 2] for k in ("w_gate", "w_up", "w_down")]
            out, sizes = M._expert_ffn(xf, idx, w, *part, first, jnp.float32)
            total, pairs = total + out, pairs + int(jnp.sum(sizes))
        sh = p["shared"]
        total = (total + _swiglu(xf, sh["gate"], sh["up"], sh["down"])).reshape(x.shape)
        # The layer holding experts 0-1 is the first share plus the shared expert.
        share = dataclasses.replace(full, moe=dataclasses.replace(full.moe, experts_held=2))
        first_share = {**p, **{k: p[k][:2] for k in ("w_gate", "w_up", "w_down")}}
        got, st = M.moe_apply(first_share, x, share)
        out0, _ = M._expert_ffn(xf, idx, w, *(first_share[k] for k in ("w_gate", "w_up", "w_down")),
                                   0, jnp.float32)
        want0 = out0 + _swiglu(xf, sh["gate"], sh["up"], sh["down"])
    np.testing.assert_allclose(np.asarray(got).reshape(want0.shape), np.asarray(want0),
                               rtol=1e-5, atol=1e-5)
    assert st["held"].shape == (2,)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=1e-5, atol=1e-5)
    assert pairs == 32 * full.moe.top_k


def test_bias_step_moves_against_the_load():
    bias = jnp.zeros((2, 4))
    load = jnp.array([[3.0, 1.0, 2.0, 2.0], [0.0, 4.0, 4.0, 0.0]])
    got = M.bias_step(bias, load, 0.001)
    np.testing.assert_allclose(np.asarray(got), [[-0.001, 0.001, 0, 0], [0.001, -0.001, -0.001, 0.001]])


def test_selection_bias_takes_no_gradient():
    c = _cfg()
    p = M.moe_init(jax.random.PRNGKey(6), c)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 8, c.d_model), jnp.float32)

    def loss(p):
        out, st = M.moe_apply(p, x, c)
        return jnp.sum(out ** 2) + jnp.sum(st["balance"])

    g = jax.grad(loss)(p)
    assert float(jnp.max(jnp.abs(g["router_bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(g["router"]))) > 0.0


def test_balance_term_is_one_at_uniform_routing():
    """Σ_i f_i P_i with every expert picked equally often and equal scores
    reads 1, DeepSeek-V3's normalisation."""
    m = _cfg().moe
    T_ = 8
    idx = jnp.array([[[(t * m.top_k + j) % 8 for j in range(m.top_k)] for t in range(T_)]])
    scores = jnp.full((1, T_, 8), 0.5)
    load, bal = M._route_stats(scores, idx, m)
    np.testing.assert_allclose(np.asarray(load), [[3.0] * 8])
    np.testing.assert_allclose(np.asarray(bal), [1.0], rtol=1e-6)
