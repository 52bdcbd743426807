"""Jit'd public wrapper for the weighted segment-sum kernel.

Implementations (see :mod:`repro.kernels.dispatch`):

* ``pallas_tpu``       — one-hot-matmul Pallas kernel (TPU only)
* ``pallas_interpret`` — debug only, never auto-selected
* ``xla_ref``          — compiled one-hot matmul oracle (materializes (n, k))
* ``xla_segment``      — compiled ``segment_sum`` scatter-add; streaming, no
  (n, k) intermediate — the off-TPU choice for large n·k

Legacy ``impl`` strings: ``"ref"`` → ``xla_ref``; ``"pallas"`` →
``pallas_tpu`` on TPU, ``pallas_interpret`` elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel as _kernel
from . import ref as _ref
from .. import dispatch

__all__ = ["weighted_segsum"]


def _segsum_pallas(x, w, idx, k: int, *, interpret: bool):
    n, d = x.shape
    # Same VMEM model as the pairwise kernels: working set is the (bn, d)
    # x-tile, the (k, bn) one-hot and the (k, d) accumulator — exactly
    # pick_blocks' footprint with bk pinned to the whole k.
    bn = dispatch.pick_blocks(n, k, d, bk_cap=dispatch.shape_bucket(k)).bn
    rem = (-n) % bn
    if rem:
        x = jnp.pad(x, ((0, rem), (0, 0)))
        w = jnp.pad(w, (0, rem))  # zero weight ⇒ padded rows are inert
        idx = jnp.pad(idx, (0, rem))
    return _kernel.weighted_segsum_kernel_call(x, w, idx, k, bn=bn, interpret=interpret)


def _segsum_xla_segment(x, w, idx, k: int):
    wf = w.astype(jnp.float32)
    xw = x.astype(jnp.float32) * wf[:, None]
    sums = jax.ops.segment_sum(xw, idx, num_segments=k)
    tot = jax.ops.segment_sum(wf, idx, num_segments=k)
    return sums, tot


dispatch.register_impl("weighted_segsum", "xla_ref", _ref.weighted_segsum_ref)
dispatch.register_impl("weighted_segsum", "xla_segment", _segsum_xla_segment)
dispatch.register_impl(
    "weighted_segsum", "pallas_tpu",
    functools.partial(_segsum_pallas, interpret=False), backends=("tpu",),
)
dispatch.register_impl(
    "weighted_segsum", "pallas_interpret",
    functools.partial(_segsum_pallas, interpret=True), debug_only=True,
)
dispatch.register_alias("weighted_segsum", "ref", "xla_ref")
dispatch.register_alias(
    "weighted_segsum", "pallas",
    lambda b: "pallas_tpu" if b == "tpu" else "pallas_interpret",
)


# Below ~1 MiB of one-hot the dense matmul beats scatter-add on CPU (measured
# crossover n·k ≈ 2.5e5 f32; see BENCH_kernels.json) — far below the generic
# materialization budget, because the matmul also pays O(n·k·d) flops.
_ONEHOT_BUDGET = 1 << 20


def _select_segsum(b, x, w, idx, k):
    if b == "tpu":
        # The kernel keeps the whole (k, d) accumulator and a (k, bn) one-hot
        # in VMEM; past the budget even at the smallest row block, the
        # scatter-add streams instead.
        d = x.shape[1]
        fits = dispatch.block_footprint(
            dispatch.LANE, dispatch.shape_bucket(k), d
        ) <= dispatch.VMEM_BUDGET
        return "pallas_tpu" if fits else "xla_segment"
    return (
        "xla_segment"
        if dispatch.should_stream(x.shape[0], k, budget=_ONEHOT_BUDGET)
        else "xla_ref"
    )


dispatch.register_selector("weighted_segsum", _select_segsum)


@functools.partial(jax.jit, static_argnames=("k", "impl"))
def _weighted_segsum_jit(x, w, idx, k: int, *, impl: str):
    return dispatch.resolve("weighted_segsum", impl, x, w, idx, k).fn(x, w, idx, k)


def weighted_segsum(x, w, idx, k: int, *, impl: str = "auto"):
    """Per-cluster weighted sums and totals.  See ref.weighted_segsum_ref.

    Resolution runs eagerly per call (env toggles honored); the compiled
    path is keyed on the resolved canonical impl name.
    """
    name = dispatch.resolve("weighted_segsum", impl, x, w, idx, k).name
    return _weighted_segsum_jit(x, w, idx, k, impl=name)
