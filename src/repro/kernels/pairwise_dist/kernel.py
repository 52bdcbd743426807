"""Pallas TPU kernels for blocked pairwise distances + fused nearest-center.

The Lloyd assignment step is the compute hot spot of every algorithm in the
paper (local k-median/k-means at each worker, coordinator re-clustering, and
sensitivity-sampling coresets all spend their FLOPs here).  GPU
implementations scatter through shared memory; on TPU we phrase everything as
MXU matmuls over VMEM tiles:

    ‖x − c‖² = ‖x‖² + ‖c‖² − 2·x·cᵀ

The grid is (n_blocks, k_blocks); the k axis is the minor (sequential) grid
dimension so the running min/argmin for a given x-block is carried in the
output refs across k-steps (TPU grid order guarantees sequential revisits;
interpret mode preserves the order).

Tiles: x-block (bn, d) and c-block (bk, d) live in VMEM; d is kept whole
(clustering dimensionality ≤ a few thousand → ≤ a few MB per tile).  The
block sizes come from :func:`repro.kernels.dispatch.pick_blocks`, which knows
the chip's tiling rules.

Layout of the per-point vectors: the fused assignment computes its tile
transposed, ``(bk, bn) = c·xᵀ``, so the min/argmin over centers reduces the
sublane axis and leaves one lane-dense ``(1, bn)`` row per x-block.  The
per-point inputs and outputs (‖x‖², idx, dist) are therefore ``(1, n)``
arrays with ``(1, bn)`` blocks: a rank-1 ``(bn,)`` block is refused by the
TPU compiler (its HBM tiling differs from the kernel's), and so is any
rank-1 block once ``jax.vmap`` adds the node axis in front of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..dispatch import MATMUL_PRECISION

__all__ = ["pairwise_sqdist_kernel_call", "assign_min_kernel_call", "PAD_DIST"]

# Positive, finite "+inf"-like distance: initializes running minima and masks
# padded center columns.  Kept finite (< f32 max) so no inf − inf can occur.
PAD_DIST = 3.4e38


def _sqdist_block(x, c):
    """(bn, d), (bk, d) → (bn, bk) f32 squared distances via MXU dot."""
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    c2 = jnp.sum(c * c, axis=1)[None, :]
    xc = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        precision=MATMUL_PRECISION, preferred_element_type=jnp.float32,
    )
    return jnp.maximum(x2 + c2 - 2.0 * xc, 0.0)


def _sqdist_kernel(x_ref, c_ref, o_ref):
    o_ref[...] = _sqdist_block(x_ref[...], c_ref[...])


def pairwise_sqdist_kernel_call(x, c, *, bn: int, bk: int, interpret: bool):
    """Full (n, k) distance matrix.  Inputs must be pre-padded to block multiples."""
    n, d = x.shape
    k, _ = c.shape
    assert n % bn == 0 and k % bk == 0, (n, k, bn, bk)
    grid = (n // bn, k // bk)
    return pl.pallas_call(
        _sqdist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, k), jnp.float32),
        interpret=interpret,
    )(x, c)


def _assign_kernel(x_ref, x2_ref, c_ref, idx_ref, dist_ref, *, bk, k_valid):
    """Fused argmin over k-blocks; running state carried in the output refs.

    The tile is laid out (bk, bn): centers on sublanes, points on lanes, so
    every per-point quantity is a lane-dense (1, bn) row.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        idx_ref[...] = jnp.zeros_like(idx_ref)
        dist_ref[...] = jnp.full_like(dist_ref, PAD_DIST)

    x = x_ref[...].astype(jnp.float32)  # (bn, d)
    c = c_ref[...].astype(jnp.float32)  # (bk, d)
    cx = jax.lax.dot_general(
        c, x, (((1,), (1,)), ((), ())),
        precision=MATMUL_PRECISION, preferred_element_type=jnp.float32,
    )  # (bk, bn)
    c2 = jnp.sum(c * c, axis=1, keepdims=True)  # (bk, 1)
    d2 = jnp.maximum(x2_ref[...] + c2 - 2.0 * cx, 0.0)
    # Mask padded center rows by index (centers are zero-padded; masking by
    # huge pad coordinates would overflow ‖c‖² to inf and poison the block
    # with inf − inf = NaN).
    row = j * bk + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    d2 = jnp.where(row < k_valid, d2, PAD_DIST)
    loc_min = jnp.min(d2, axis=0, keepdims=True)  # (1, bn)
    # First-occurrence argmin as a second min: the smallest center index
    # attaining the minimum (xla_ref's tie rule).
    loc_idx = jnp.min(
        jnp.where(d2 == loc_min, row, jnp.iinfo(jnp.int32).max), axis=0, keepdims=True
    )
    prev_min = dist_ref[...]
    better = loc_min < prev_min
    dist_ref[...] = jnp.where(better, loc_min, prev_min)
    idx_ref[...] = jnp.where(better, loc_idx, idx_ref[...])


def assign_min_kernel_call(
    x, c, *, bn: int, bk: int, k_valid: int | None = None, interpret: bool,
):
    """Fused nearest-center assignment: (idx (n,) i32, sqdist (n,) f32).

    Never materializes the (n, k) matrix in HBM — each (bk, bn) tile lives
    only in VMEM with the running (min, argmin) carried across the sequential
    k grid dimension.  ``k_valid`` (default: all) marks how many leading
    center rows are real; zero-padded rows beyond it are masked to PAD_DIST.
    ``bn`` must be a multiple of 128 (the lane width of the (1, bn) rows).
    """
    n, d = x.shape
    k, _ = c.shape
    assert n % bn == 0 and k % bk == 0, (n, k, bn, bk)
    grid = (n // bn, k // bk)
    xf = x.astype(jnp.float32)
    x2 = jnp.sum(xf * xf, axis=1)[None, :]  # (1, n)
    kern = functools.partial(_assign_kernel, bk=bk, k_valid=k if k_valid is None else k_valid)
    row_spec = pl.BlockSpec((1, bn), lambda i, j: (0, i))
    idx, dist = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            row_spec,
            pl.BlockSpec((bk, d), lambda i, j: (j, 0)),
        ],
        out_specs=[row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=interpret,
    )(x, x2, c)
    return idx[0], dist[0]
