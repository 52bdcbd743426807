"""The whole train step's share of the chip's bf16 peak: the FLOPs training
requires per unique token, times ``train_tokens_per_s`` of the same run,
over the peak of the chips used.

Per token: 6 times the parameters in matmuls (every projection, the MLP
and the LM head; the embedding lookup is no matmul), plus causal attention,
6 H dh (T + 1) per layer (scores and output, 2 dh FLOPs a pair each,
forward and backward, over the (T + 1) / 2 pairs a token has on average).  With the data computed ell = 2
times by the redundant groups, the share cannot pass 1 / ell = 50%."""


def flops_per_token(c: dict) -> float:
    d, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    H, KV, dh, ff = (c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
                     c["intermediate_size"])
    T = c["training"]["seq_len"]
    per_layer = d * (H + 2 * KV) * dh + H * dh * d + 3 * d * ff
    matmul_params = L * per_layer + d * V
    attention = L * 6.0 * H * dh * (T + 1)
    return 6.0 * matmul_params + attention


def read(ctx):
    c = ctx["counters"]
    if not c.get("steps"):
        return None
    chips = ctx["cell"].chips
    return (100.0 * flops_per_token(c["config"]) * c["train_tokens_per_s"]
            / (chips * ctx["peaks"]["bf16_flops_per_s"]))
