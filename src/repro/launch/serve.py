"""Serving launcher: batched prefill + decode for any registered arch.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --scale smoke \
        --batch 4 --prompt-len 16 --gen 32
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from ..models import transformer as T
from ..models.registry import get_config
from ..serve.decode import greedy_generate
from .train import _SCALES


def main() -> None:
    from ..caches import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--scale", default="smoke", choices=list(_SCALES))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if _SCALES[args.scale] is not None:
        over = dict(_SCALES[args.scale])
        if cfg.moe is not None:
            over.pop("d_ff")
            over["moe"] = dataclasses.replace(
                cfg.moe, num_experts=8, top_k=2, d_expert=64, num_shared=1
            )
            over["n_kv_heads"] = over["n_heads"]
        if cfg.family in ("ssm", "hybrid"):
            over.pop("d_ff", None)
            over.pop("n_kv_heads", None)
        scan_len = len(cfg.scan_unit)
        body = over.get("n_layers", cfg.n_layers) - len(cfg.tail)
        over["n_layers"] = max(scan_len, body - body % scan_len) + len(cfg.tail)
        cfg = dataclasses.replace(cfg, **over)
    cfg = cfg.validate()

    params = T.init_params(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)
    if cfg.num_codebooks > 0:
        prompt = jax.random.randint(
            key, (args.batch, cfg.num_codebooks, args.prompt_len), 0, cfg.vocab
        )
    else:
        prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0, cfg.vocab)
    t0 = time.perf_counter()
    out = greedy_generate(
        params, cfg, prompt, steps=args.gen, temperature=args.temperature
    )
    dt = time.perf_counter() - t0
    print(
        f"{cfg.name} [{args.scale}]  batch={args.batch} prompt={args.prompt_len} "
        f"gen={args.gen}  {args.batch * args.gen / dt:.1f} tok/s (incl. compile)"
    )
    print("row 0:", out[0].tolist())


if __name__ == "__main__":
    main()
