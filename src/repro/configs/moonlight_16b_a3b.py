"""moonlight-16b-a3b [moe]: 27L d_model=2048, MLA 16 heads (kv_lora 512,
qk 128 + 64 rope, v 128), layer 0 dense (d_ff 11264), then 26 expert layers
of 64 routed experts (width 1408, top-6, sigmoid scores with bias-corrected
``noaux_tc`` selection, renormalized, × 2.446) and 2 shared experts;
vocab 163840, untied.  [hf:moonshotai/Moonlight-16B-A3B config.json,
model_type deepseek_v3]

``n_group`` = ``topk_group`` = 1, so group-limited routing selects from every
expert and is not modelled.  The bias update rate γ = 0.001 and the
sequence-wise balance weight α = 0.0001 are DeepSeek-V3's (arXiv:2412.19437
§2.1.2); the config does not state them.
"""

import dataclasses

from ..models.registry import ModelConfig, MoEConfig, register


@register("moonlight-16b-a3b")
def config() -> ModelConfig:
    return ModelConfig(
        name="moonlight-16b-a3b",
        family="moe",
        vocab=163840,
        d_model=2048,
        n_layers=27,
        n_heads=16,
        n_kv_heads=16,
        d_ff=11264,
        head_dim=128,
        lead=("mla_mlp",),
        scan_unit=("mla_moe",),
        kv_lora_rank=512,
        q_lora_rank=None,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=50000.0,
        rms_eps=1e-5,
        mlp_act="silu_glu",
        tie_embeddings=False,
        moe=MoEConfig(
            num_experts=64, top_k=6, d_expert=1408, num_shared=2,
            router_score="sigmoid", renorm_topk=True, routed_scaling_factor=2.446,
            bias_update_rate=0.001, balance_weight=0.0001,
        ),
    )


def smoke_config() -> ModelConfig:
    """Every mechanism of the published model at toy widths: a dense lead
    layer, MLA with dk ≠ dv, and all 8 experts held."""
    return dataclasses.replace(
        config(), vocab=256, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_ff=96, head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        moe=dataclasses.replace(config().moe, num_experts=8, top_k=2, d_expert=32,
                                num_shared=1),
    )
