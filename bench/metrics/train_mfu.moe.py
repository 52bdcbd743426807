"""The whole train step's share of the chip's bf16 peak for a latent
attention + routed expert model: the FLOPs training requires per unique
token, times ``train_tokens_per_s`` of the same run, over the peak of the
chips used.

Per token: 6 times the parameters a token passes in matmuls (each layer's
latent attention projections, the dense MLP of the leading layers, the
shared experts, the router, top_k × held / router_experts of the held
experts' FFN, and the LM head over the vocabulary slice; the embedding
lookup is no matmul), plus causal latent attention, 3 H (2 dk + 2 dv)
(T + 1) / 2 per layer (scores over dk and output over dv, 2 FLOPs a
multiply-add, forward and backward, over the (T + 1) / 2 pairs a token has
on average).  With the data computed ell = 2 times by the redundant
groups, the share cannot pass 1 / ell = 50%."""


def flops_per_token(c: dict) -> float:
    d, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    lead = c["first_k_dense_replace"]
    f, E = c["moe_intermediate_size"], c["router_experts"]
    T = c["training"]["seq_len"]
    mla = d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d
    dense = 3 * d * c["intermediate_size"]
    expert = 3 * d * f
    moe = (c["n_shared_experts"] * expert + d * E
           + c["num_experts_per_tok"] * c["n_routed_experts"] / E * expert)
    matmul_params = L * mla + lead * dense + (L - lead) * moe + d * V
    attention = L * 3.0 * H * (2 * (dn + dr) + 2 * dv) * (T + 1) / 2
    return 6.0 * matmul_params + attention


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps"):
        return None
    chips = ctx["cell"].chips
    return (100.0 * flops_per_token(c["config"]) * c["train_tokens_per_s"]
            / (chips * ctx["peaks"]["bf16_flops_per_s"]))
