"""Pallas TPU flash-attention kernel (GQA-aware, causal block skipping).

TPU-native layout: grid = (B·H, n_q_blocks, n_kv_blocks) with the kv axis as
the minor sequential dimension; the online-softmax state (m, l, acc) lives in
VMEM scratch and persists across kv steps of one (bh, qi) cell.  Causal
skipping is structural — blocks strictly above the diagonal never execute
(`pl.when`), so FLOPs match the ~T²/2 causal optimum instead of T².

GQA is expressed through the k/v BlockSpec index maps (head h reads kv-head
h // group) — no materialized head repetition in HBM.

VMEM budget per step (f32): bq·dh (q) + 2·bk·dh (k,v) + bq·bk (s) + bq·dh
(acc) ≈ 1.3 MB at bq=bk=512, dh=128 — comfortably under the ~16 MB/core v5e
budget, MXU-aligned (multiples of 128 on the matmul dims).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel_call"]


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, causal, bq, bk, kv_len
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Offset of query positions relative to key positions (decode alignment):
    # query block rows are global positions qi·bq + r + (kv_len − q_len)… the
    # wrapper pads q and kv to the same timeline, so q row r in block qi sits
    # at absolute position qi·bq + r.
    q_start = qi * bq
    k_start = ki * bk

    run = jnp.logical_or(not causal, k_start <= q_start + bq - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, dh)
        k = k_ref[0].astype(jnp.float32)  # (bk, dh)
        v = v_ref[0].astype(jnp.float32)  # (bk, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, -jnp.inf)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        # exp(−inf − −inf) guard: rows with no valid key yet keep m = −inf.
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_new), 0.0)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def flash_attention_kernel_call(
    q, k, v, *, group: int, causal: bool, scale: float,
    bq: int = 512, bk: int = 512, interpret: bool,
):
    """q: (BH, T, dh); k: (BKV, S, dh); v: (BKV, S, dv) with BH = BKV · group
    (dv may differ from dh, as in latent attention).

    T % bq == 0 and S % bk == 0 (wrapper pads).  Returns (BH, T, dv).
    """
    BH, T, dh = q.shape
    BKV, S, _ = k.shape
    dv = v.shape[-1]
    assert BH == BKV * group, (BH, BKV, group)
    assert T % bq == 0 and S % bk == 0, (T, S, bq, bk)
    grid = (BH, T // bq, S // bk)
    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, bq=bq, bk=bk, kv_len=S
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
