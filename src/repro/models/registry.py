"""Model configuration schema + architecture registry.

Every assigned architecture is a :class:`ModelConfig`; block heterogeneity
(hybrids like RecurrentGemma and xLSTM) is expressed as a repeating
``scan_unit`` of block types between an optional ``lead`` and ``tail`` —
the layer stack is ``lead  +  scan_unit × scan_repeats  +  tail`` and the
unit is executed as a ``lax.scan`` over the repeats (compile time O(1) in
depth).

Block types:
  attn_mlp   — GQA attention + gated/plain MLP        (dense transformers)
  attn_moe   — GQA attention + routed MoE (+ shared)  (MoE transformers)
  mla_mlp    — latent attention (MLA) + gated MLP     (DeepSeek-V2/V3 dense layers)
  mla_moe    — latent attention (MLA) + routed MoE    (DeepSeek-V2/V3 expert layers)
  mlstm      — xLSTM matrix-memory block (chunkwise-parallel / recurrent)
  slstm      — xLSTM scalar-memory block (sequential scan)
  rglru_mlp  — RG-LRU recurrent block + MLP           (Griffin/RecurrentGemma)
  lattn_mlp  — local sliding-window attention + MLP   (RecurrentGemma)
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

__all__ = ["MoEConfig", "ModelConfig", "register", "get_config", "list_archs"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 64  # the router's width: experts a token is routed over
    top_k: int = 6
    d_expert: int = 1408
    num_shared: int = 2
    router_score: str = "softmax"  # or "sigmoid" (DeepSeek-V3/Moonlight style)
    renorm_topk: bool = False
    routed_scaling_factor: float = 1.0
    # Experts whose weights this layer holds, numbered from 0 (expert
    # parallelism: the chip's share of a layer).  None holds all of them.
    experts_held: Optional[int] = None
    # γ of DeepSeek-V3's auxiliary-loss-free balancing (noaux_tc): > 0 adds a
    # per-expert selection bias, moved by γ·sign(mean load − load) each step.
    bias_update_rate: float = 0.0
    # α of the sequence-wise balance loss, summed over the expert layers.
    balance_weight: float = 0.001

    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None else self.experts_held


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | vlm | hybrid | audio
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    head_dim: int = 128
    scan_unit: tuple = ("attn_mlp",)
    tail: tuple = ()
    lead: tuple = ()  # block types run once before the scanned unit
    qk_norm: bool = False
    qkv_bias: bool = False
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    mlp_act: str = "silu_glu"  # silu_glu | gelu_glu | gelu
    moe: Optional[MoEConfig] = None
    window: Optional[int] = None  # sliding-window size for lattn blocks
    # Latent attention (MLA, DeepSeek-V2 §2.1) for the mla_* blocks
    kv_lora_rank: Optional[int] = None
    q_lora_rank: Optional[int] = None  # compressed queries: not implemented
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # xLSTM specifics
    mlstm_proj_factor: float = 2.0
    slstm_proj_factor: float = 4.0 / 3.0
    conv_width: int = 4
    # RG-LRU specifics
    d_rnn: Optional[int] = None
    rglru_c: float = 8.0
    # modality frontends (STUBS: precomputed embeddings / codebook tokens)
    num_codebooks: int = 0  # musicgen: EnCodec streams
    num_prefix_tokens: int = 0  # internvl2: vision patch embeddings
    tie_embeddings: bool = False
    # Numerics
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # Sub-quadratic decode? (gates the long_500k shape)
    subquadratic: bool = False

    @property
    def scan_repeats(self) -> int:
        body = self.n_layers - len(self.lead) - len(self.tail)
        assert body % len(self.scan_unit) == 0, (
            f"{self.name}: {body} body layers not divisible by unit "
            f"{self.scan_unit}"
        )
        return body // len(self.scan_unit)

    @property
    def block_types(self) -> tuple:
        return self.lead + self.scan_unit * self.scan_repeats + self.tail

    def validate(self) -> "ModelConfig":
        assert self.n_layers == len(self.block_types)
        assert self.n_heads % self.n_kv_heads == 0
        if any(bt.startswith("mla_") for bt in self.block_types):
            assert self.kv_lora_rank and self.qk_rope_head_dim and self.v_head_dim
            assert self.q_lora_rank is None, "MLA with compressed queries is not implemented"
        if self.moe is not None:
            assert 0 < self.moe.held <= self.moe.num_experts
        return self


_REGISTRY: dict[str, Callable[[], ModelConfig]] = {}


def register(name: str):
    def deco(fn: Callable[[], ModelConfig]):
        _REGISTRY[name] = fn
        return fn

    return deco


def get_config(name: str, **overrides) -> ModelConfig:
    """Instantiate a registered architecture (importing repro.configs lazily)."""
    if name not in _REGISTRY:
        import importlib

        importlib.import_module("repro.configs")
        if name not in _REGISTRY:
            raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    cfg = _REGISTRY[name]()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg.validate()


def list_archs() -> list[str]:
    import importlib

    importlib.import_module("repro.configs")
    return sorted(_REGISTRY)
