"""Latent attention's forward calls of the flash attention kernel
(``kernels/flash_attention``), whose keys are wider than its values:

    bf16[BH,T,dv] custom-call(bf16[BH,T,dk] q, bf16[BKV,S,dk] k, bf16[BKV,S,dv] v)

with dk = qk_nope_head_dim + qk_rope_head_dim (192) and dv = v_head_dim
(128), read from v's operand.  Causal attention at S = T needs T (T + 1) / 2
of the T x S scores, each a dk-long dot product for the score and a dv-long
one for the output: 2 (dk + dv) FLOPs apiece.  Its least traffic is
reading q, k and v once and writing the output once.
"""

from __future__ import annotations

import re

NAME = "_flash_attention"
_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_BYTES = {"f32": 4, "bf16": 2, "f16": 2}


def shapes(event_name: str) -> dict:
    args = event_name.split("custom-call(", 1)[1]
    ops = [(t, [int(v) for v in dims.split(",") if v]) for t, dims in _SHAPE.findall(args)]
    (tq, q), (_, k), (_, v) = ops[0], ops[1], ops[2]
    return {"bh": q[0], "t": q[1], "dk": q[2], "bkv": k[0], "s": k[1], "dv": v[2],
            "itemsize": _BYTES[tq]}


def matches(event_name: str) -> bool:
    if not (event_name.lstrip("%").startswith(NAME) and "custom-call(" in event_name):
        return False
    sh = shapes(event_name)
    return sh["dk"] != sh["dv"]


def flops(bh: int, t: int, dk: int, dv: int, s: int, **_) -> float:
    pairs = t * (t + 1) / 2 if s == t else t * s
    return 2.0 * bh * pairs * (dk + dv)


def bytes_moved(bh: int, t: int, dk: int, dv: int, bkv: int, s: int, itemsize: int = 2) -> float:
    return float(itemsize * (bh * t * (dk + dv) + bkv * s * (dk + dv)))


def least_seconds(event_name: str, peaks: dict) -> tuple:
    sh = shapes(event_name)
    t_flops = flops(**sh) / peaks["bf16_flops_per_s"]
    t_bytes = bytes_moved(**sh) / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
