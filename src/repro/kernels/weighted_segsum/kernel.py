"""Pallas TPU kernel: weighted segment-sum (Lloyd centroid update).

GPU implementations scatter-add into per-cluster accumulators through shared
memory atomics.  TPU has no fast scatter — instead each x-tile builds a
transposed (k, bn) one-hot dispatch in VMEM and accumulates

    sums   += onehot @ (w · x)        (MXU matmul)
    totals += w @ onehotᵀ

into the (k, d)/(1, k) output refs, which are revisited across the sequential
n grid dimension.  k·d and the (k, bn) one-hot must fit VMEM; the selector in
``ops.py`` routes larger k to the XLA scatter-add.

The sums are exact to f32: the one-hot is 0/1, exact in bf16, and the f32
operand ``w · x`` (formed by XLA before the kernel) is split into three bf16
terms that carry its 24 significand bits, so three native bf16 passes with
f32 accumulation give every product exactly.  Asking Mosaic for f32
precision instead also splits the one-hot and needs several times the VMEM.

The per-point inputs (w, idx) are lane-dense ``(1, n)`` rows with ``(1, bn)``
blocks, for the same reason as in the pairwise kernels: rank-1 blocks are
refused by the TPU compiler, plain and under ``jax.vmap``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["weighted_segsum_kernel_call"]


def _bf16_terms(v):
    """Three bf16 terms whose f32 sum is ``v``: 8 + 8 + 8 significand bits
    cover f32's 24."""
    hi = v.astype(jnp.bfloat16)
    r = v - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _segsum_kernel(wx_ref, w_ref, idx_ref, sums_ref, tot_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        tot_ref[...] = jnp.zeros_like(tot_ref)

    idx = idx_ref[...]  # (1, bn)
    k = sums_ref.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (k, idx.shape[1]), 0)
    oh = (idx == row).astype(jnp.bfloat16)  # (k, bn)
    for part in _bf16_terms(wx_ref[...]):  # (bn, d)
        sums_ref[...] += jax.lax.dot_general(
            oh, part, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
    # Totals as NT matmuls of the weight row: they land lane-dense in (1, k).
    for part in _bf16_terms(w_ref[...]):  # (1, bn)
        tot_ref[...] += jax.lax.dot_general(
            part, oh, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )


def weighted_segsum_kernel_call(x, w, idx, k: int, *, bn: int, interpret: bool):
    """Inputs pre-padded so n % bn == 0; padded rows must carry w = 0.
    ``bn`` must be a multiple of 128 (the lane width of the (1, bn) rows)."""
    n, d = x.shape
    assert n % bn == 0, (n, bn)
    w = w.astype(jnp.float32)
    wx = x.astype(jnp.float32) * w[:, None]
    grid = (n // bn,)
    row_spec = pl.BlockSpec((1, bn), lambda i: (0, i))
    sums, tot = pl.pallas_call(
        _segsum_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)), row_spec, row_spec],
        out_specs=[
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
        ],
        interpret=interpret,
    )(wx, w[None, :], idx.astype(jnp.int32)[None, :])
    return sums, tot[0]
