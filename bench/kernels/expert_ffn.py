"""The grouped expert FFN (``models/moe.py``): megablox's ``gmm`` calls over
the routed (token, expert) pairs, three a layer forward (gate, up, down),
and for each in the backward a ``gmm`` for the rows' gradient and a
``tgmm`` for the weights'.  Each call's HLO text names its shapes:

    f32[R,N] custom-call(s32[] ..., bf16[R,K] rows, bf16[E,K,N] weights)

and the weights' gradient is the 3-d array on the output side.  R is a
static bound, n · min(top_k, held); only the routed pairs are work, so the
rows a call computes come from the program's own counters: the pairs on the
held experts of the tokens each update covers (``moe_local_pairs``) over the
steps that counted them (``moe_steps``).  Each group computes its shards'
tokens, so the groups compute every covered token ``redundancy`` times; a
call of one group's layer gets that work over the groups and the layers.
A step that leaves a shard uncovered computes it all the same, and the
count leaves it out: the share then reads a little low.  A call over p pairs
needs 2 p K N FLOPs; its least traffic is its expert weights once
(E K N) and its p rows in and out (p (K + N)), in bfloat16.
"""

from __future__ import annotations

import re

KERNELS = ("gmm", "tgmm")
_SHAPE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
_INSTR = re.compile(r"^%?([^\s=]+)\s*=")


def matches(event_name: str) -> bool:
    m = _INSTR.match(event_name)
    name = m.group(1) if m else ""
    return name.split(".")[0] in KERNELS and "custom-call(" in event_name


def weights_shape(event_name: str) -> list:
    """(E, K, N) of the call: the one 3-d floating array, output or operand."""
    for dtype, dims in _SHAPE.findall(event_name):
        shape = [int(v) for v in dims.split(",") if v]
        if dtype in ("bf16", "f32") and len(shape) == 3:
            return shape
    raise ValueError(f"no 3-d array in {event_name[:120]!r}")


def flops(pairs: float, k: int, n: int, **_) -> float:
    return 2.0 * pairs * k * n


def bytes_moved(pairs: float, e: int, k: int, n: int, itemsize: int = 2) -> float:
    return float(itemsize * (e * k * n + pairs * (k + n)))


def pairs_per_call(config: dict) -> float | None:
    """Routed pairs one call of one group's layer computes, from the
    program's counters; None where the program keeps none."""
    from program import obs

    reg = obs().default_registry()
    steps = reg.value("moe_steps")
    if not steps:
        return None
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    t = config["training"]
    return reg.value("moe_local_pairs") / steps * t["redundancy"] / (layers * t["groups"])


def least_seconds(event_name: str, pairs: float, peaks: dict) -> float:
    e, k, n = weights_shape(event_name)
    return max(flops(pairs, k, n) / peaks["bf16_flops_per_s"],
               bytes_moved(pairs, e, k, n) / peaks["hbm_bytes_per_s"])
