"""Production training launcher.

Maps any registered architecture onto the redundant-assignment trainer at a
chosen scale.  On the CPU container this runs reduced widths (--scale smoke);
on a real pod the same entry point runs the full config under the production
mesh (the per-host data plane consumes the same RedundantShardPlan the
dry-run validates).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --scale smoke \
        --steps 100 --redundancy 2 --scheme cyclic --ckpt /tmp/ck
"""

from __future__ import annotations

import argparse
import dataclasses

import numpy as np

from ..models.registry import get_config
from ..train.compression import CompressionConfig
from ..train.optimizer import AdamWConfig
from ..train.trainer import Trainer, TrainerConfig

_SCALES = {
    # (d_model, n_layers, heads, kv, d_ff, vocab, head_dim)
    "smoke": dict(d_model=128, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=384,
                  vocab=512, head_dim=32),
    "100m": dict(d_model=768, n_layers=12, n_heads=12, n_kv_heads=4, d_ff=3072,
                 vocab=32768, head_dim=64),
    "full": None,  # exact assigned config (pod-scale hardware required)
}


def main() -> None:
    from ..caches import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--scale", default="smoke", choices=list(_SCALES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--groups", type=int, default=4)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--redundancy", type=int, default=2)
    ap.add_argument("--scheme", default="cyclic", choices=("cyclic", "fr", "singleton"))
    ap.add_argument("--microbatch", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--no-stragglers", action="store_true")
    ap.add_argument("--compress", action="store_true")
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
            # keep the family's block pattern, shrink dims only
            over.pop("d_ff", None)
            over.pop("n_kv_heads", None)
        scan_len = len(cfg.scan_unit)
        body = over.get("n_layers", cfg.n_layers) - len(cfg.tail)
        over["n_layers"] = max(scan_len, body - body % scan_len) + len(cfg.tail)
        cfg = dataclasses.replace(cfg, **over)
    cfg = cfg.validate()

    tcfg = TrainerConfig(
        num_groups=args.groups, num_shards=args.shards,
        redundancy=args.redundancy, scheme=args.scheme,
        microbatch=args.microbatch, seq_len=args.seq_len, steps=args.steps,
        ckpt_dir=args.ckpt, ckpt_every=max(args.steps // 4, 1),
        simulate_stragglers=not args.no_stragglers,
        compression=CompressionConfig() if args.compress else None,
    )
    ocfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                       total_steps=args.steps)
    trainer = Trainer(cfg, tcfg, ocfg)
    print(
        f"arch={cfg.name} scale={args.scale} params≈? | groups={args.groups} "
        f"ell={args.redundancy} scheme={args.scheme} steps={args.steps}"
    )

    def on_step(step, rec):
        if step % 10 == 0 or rec["stragglers"]:
            print(
                f"step {step:4d} loss={rec['loss']:.4f} "
                f"stragglers={rec['stragglers']} covered={rec['covered']:.2f}"
            )

    trainer.run(on_step=on_step)
    losses = [h["loss"] for h in trainer.history if "loss" in h]
    print(f"final: {losses[0]:.4f} -> {losses[-1]:.4f} ({len(losses)} steps)")


if __name__ == "__main__":
    main()
