"""Flash attention forward (``kernels/flash_attention``).  Counts from the
call's shapes, read off the HLO text of the trace event:

    bf16[BH,T,dh] custom-call(bf16[BH,T,dh] q, bf16[BKV,S,dh] k, bf16[BKV,S,dh] v)

Causal attention needs, of the T x S scores, those at or below the
diagonal: for S = T that is T (T + 1) / 2 of them, each a dh-long dot
product for the scores and another for the output, so 4 dh FLOPs apiece.
Its least traffic is reading q, k and v once and writing the output once.
"""

from __future__ import annotations

import re

NAME = "_flash_attention"
_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_BYTES = {"f32": 4, "bf16": 2, "f16": 2}


def matches(event_name: str) -> bool:
    return event_name.lstrip("%").startswith(NAME) and "custom-call(" in event_name


def shapes(event_name: str) -> dict:
    args = event_name.split("custom-call(", 1)[1]
    ops = [(t, [int(v) for v in dims.split(",") if v]) for t, dims in _SHAPE.findall(args)]
    (tq, q), (_, k) = ops[0], ops[1]
    return {"bh": q[0], "t": q[1], "dh": q[2], "bkv": k[0], "s": k[1], "itemsize": _BYTES[tq]}


def flops(bh: int, t: int, dh: int, s: int, causal: bool = True, **_) -> float:
    pairs = t * (t + 1) / 2 if (causal and s == t) else t * s
    return 4.0 * bh * pairs * dh


def bytes_moved(bh: int, t: int, dh: int, bkv: int, s: int, itemsize: int = 2, **_) -> float:
    return float(itemsize * dh * (2 * bh * t + 2 * bkv * s))


def least_seconds(event_name: str, peaks: dict) -> tuple:
    sh = shapes(event_name)
    t_flops = flops(**sh) / peaks["bf16_flops_per_s"]
    t_bytes = bytes_moved(**sh) / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
