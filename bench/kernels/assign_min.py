"""``assign_min``: each row's nearest center and its squared distance
(``kernels/pairwise_dist``).  Counts from the call's shapes, read off the
HLO text of the trace event:

    (s32[B,1,n], f32[B,1,n]) custom-call(f32[B,n,d] x, f32[B,1,n] x2, f32[B,k,d] c)

with the leading B absent outside ``vmap``.  The work the call needs is the
n x k dot products, 2 n k d FLOPs; its least traffic is reading x, the row
norms and the centers once and writing an index and a distance per row.
"""

from __future__ import annotations

import math
import re

NAME = "_assign_min"
_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_BYTES = {"f32": 4, "s32": 4, "bf16": 2, "f16": 2, "s8": 1, "u32": 4}


def matches(event_name: str) -> bool:
    return event_name.lstrip("%").startswith(NAME) and "custom-call(" in event_name


def _operand_shapes(event_name: str) -> list:
    args = event_name.split("custom-call(", 1)[1]
    return [(t, [int(v) for v in dims.split(",") if v]) for t, dims in _SHAPE.findall(args)]


def shapes(event_name: str) -> dict:
    ops = _operand_shapes(event_name)
    (tx, x), (tc, c) = ops[0], ops[-1]
    batch = math.prod(x[:-2]) if len(x) > 2 else 1
    return {"batch": batch, "n": x[-2], "d": x[-1], "k": c[-2], "itemsize": _BYTES[tx]}


def flops(batch: int, n: int, k: int, d: int, **_) -> float:
    return 2.0 * batch * n * k * d


def bytes_moved(batch: int, n: int, k: int, d: int, itemsize: int = 4, **_) -> float:
    return float(batch * (itemsize * (n * d + n + k * d) + 8 * n))


def least_seconds(event_name: str, peaks: dict) -> tuple:
    """(least time on this chip, the bound that sets it)."""
    sh = shapes(event_name)
    t_flops = flops(**sh) / peaks["bf16_flops_per_s"]
    t_bytes = bytes_moved(**sh) / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
