"""Assigned-architecture configs.  Importing this package registers all
architectures with repro.models.registry."""

from . import (  # noqa: F401
    deepseek_moe_16b,
    internvl2_1b,
    moonlight_16b_a3b,
    musicgen_large,
    qwen2_5_3b,
    qwen3_1_7b,
    qwen3_4b,
    qwen3_8b,
    recurrentgemma_9b,
    xlstm_1_3b,
)

ARCHS = [
    "qwen3-4b",
    "qwen3-8b",
    "qwen2.5-3b",
    "qwen3-1.7b",
    "moonlight-16b-a3b",
    "deepseek-moe-16b",
    "xlstm-1.3b",
    "internvl2-1b",
    "recurrentgemma-9b",
    "musicgen-large",
]
