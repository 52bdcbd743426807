"""Public attention entry point, routed through :mod:`repro.kernels.dispatch`.

* ``pallas_tpu``       — the TPU-target kernel (kernel.py)
* ``pallas_interpret`` — the same kernel interpreted (debug only; never
  auto-selected off-TPU)
* ``xla_chunked``      — compiled jnp flash (online softmax, Python loop over
  query chunks with a `lax.scan` over each chunk's *own* causal KV range).
  No T×T materialization, FLOPs within ~cq/T of the causal optimum, compact
  HLO.  Supports GQA and sliding windows (RecurrentGemma local attention).
* ``xla_ref``          — naive oracle (ref.py).

``impl="auto"`` picks pallas_tpu on TPU (xla_chunked for windowed attention),
xla_chunked elsewhere.  Legacy strings ``"pallas"``/``"chunked"``/``"ref"``
keep working.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import kernel as _kernel
from . import ref as _ref
from .. import dispatch

__all__ = ["flash_attention", "decode_attention"]


def _pick_chunks(T: int, S: int, window) -> tuple[int, int]:
    # Cap the Python-level q-chunk count at ~32 to bound HLO size; keep the
    # diagonal-block waste ≤ ~3% of causal FLOPs.
    cq = max(512, T // 32)
    cq = min(cq, T)
    while T % cq != 0:  # T is a power-of-two multiple in all our shapes
        cq //= 2
    ck = min(1024, S)
    while S % ck != 0:
        ck //= 2
    return max(cq, 1), max(ck, 1)


def chunked_attention(q, k, v, *, causal=True, window=None, scale=None):
    """Flash attention in pure jnp.  q, k: (B,T|S,H|KV,dh); v: (B,S,KV,dv)."""
    B, T, H, dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = H // KV
    scale = (dh ** -0.5) if scale is None else scale
    cq, ck = _pick_chunks(T, S, window)
    nq = T // cq
    off = S - T  # decode-style alignment: q row t ↔ absolute position t + off
    qg = q.reshape(B, T, KV, g, dh).astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    outs = []
    for i in range(nq):
        q_blk = jax.lax.dynamic_slice_in_dim(qg, i * cq, cq, axis=1)  # (B,cq,KV,g,dh)
        row = off + i * cq + jnp.arange(cq)  # absolute positions of this block
        hi = off + (i + 1) * cq if causal else S  # keys strictly before hi
        lo = 0 if window is None else max(0, off + i * cq - int(window) + 1)
        j0, j1 = lo // ck, math.ceil(min(hi, S) / ck)
        n_blocks = max(1, j1 - j0)

        def body(carry, j):
            m, l, acc = carry
            k_blk = jax.lax.dynamic_slice_in_dim(kf, j * ck, ck, axis=1)  # (B,ck,KV,dh)
            v_blk = jax.lax.dynamic_slice_in_dim(vf, j * ck, ck, axis=1)
            col = j * ck + jnp.arange(ck)
            s = jnp.einsum("bqkgd,bskd->bkgqs", q_blk, k_blk)  # (B,KV,g,cq,ck)
            mask = jnp.ones((cq, ck), dtype=bool)
            if causal:
                mask &= row[:, None] >= col[None, :]
            if window is not None:
                mask &= col[None, :] > row[:, None] - int(window)
            s = jnp.where(mask, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_new), 0.0)
            p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_new[..., None]), 0.0)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p, v_blk)
            return (m_new, l, acc), ()

        init = (
            jnp.full((B, KV, g, cq), -jnp.inf, jnp.float32),
            jnp.zeros((B, KV, g, cq), jnp.float32),
            jnp.zeros((B, KV, g, cq, dv), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(body, init, j0 + jnp.arange(n_blocks))
        safe = jnp.where(l > 0.0, l, 1.0)
        o = (acc / safe[..., None]).transpose(0, 3, 1, 2, 4)  # (B,cq,KV,g,dv)
        outs.append(o.reshape(B, cq, H, dv))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def _pallas_attention(q, k, v, *, causal, window, scale, interpret):
    if window is not None:
        # Windowed attention falls through to chunked (structural skipping
        # already yields the T·W cost there).
        return chunked_attention(q, k, v, causal=causal, window=window, scale=scale)
    return _pallas_forward(q, k, v, causal, scale, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _pallas_forward(q, k, v, causal, scale, interpret):
    """The kernel's forward pass.  The kernel has no backward of its own
    (pallas_call cannot differentiate a kernel with scratch state), so the
    gradient comes from :func:`chunked_attention`'s — the same function,
    recomputed in XLA — which is what lets a train step use the kernel."""
    B, T, H, dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = H // KV
    # Shared VMEM tile model seeds the caps; shrink to exact divisors.
    cfg = dispatch.pick_blocks(T, S, max(dh, dv), bn_cap=512, bk_cap=512)
    bq = min(cfg.bn, T)
    while T % bq:
        bq //= 2
    bk = min(cfg.bk, S)
    while S % bk:
        bk //= 2
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, S, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, S, dv)
    out = _kernel.flash_attention_kernel_call(
        qf, kf, vf, group=g, causal=causal, scale=scale,
        bq=bq, bk=bk, interpret=interpret,
    )
    return out.reshape(B, H, T, dv).transpose(0, 2, 1, 3)


def _pallas_forward_fwd(q, k, v, causal, scale, interpret):
    return _pallas_forward(q, k, v, causal, scale, interpret), (q, k, v)


def _pallas_forward_bwd(causal, scale, interpret, res, g):
    _, vjp = jax.vjp(
        lambda q, k, v: chunked_attention(q, k, v, causal=causal, scale=scale), *res
    )
    return vjp(g)


_pallas_forward.defvjp(_pallas_forward_fwd, _pallas_forward_bwd)


def _ref_attention(q, k, v, *, causal, window, scale):
    assert window is None, "ref oracle does not model sliding windows"
    return _ref.attention_ref(q, k, v, causal=causal, scale=scale)


dispatch.register_impl("flash_attention", "xla_chunked", chunked_attention)
dispatch.register_impl("flash_attention", "xla_ref", _ref_attention)
dispatch.register_impl(
    "flash_attention", "pallas_tpu",
    functools.partial(_pallas_attention, interpret=False), backends=("tpu",),
)
dispatch.register_impl(
    "flash_attention", "pallas_interpret",
    functools.partial(_pallas_attention, interpret=True), debug_only=True,
)
dispatch.register_alias("flash_attention", "ref", "xla_ref")
dispatch.register_alias("flash_attention", "chunked", "xla_chunked")
dispatch.register_alias(
    "flash_attention", "pallas",
    lambda b: "pallas_tpu" if b == "tpu" else "pallas_interpret",
)
def _select_attention(b, q, k, v, causal, window, scale):
    """Measured-first attention impl selection.

    On TPU the Pallas kernel is the pick (chunked for windowed attention).
    Elsewhere the analytic prior is: ``xla_ref`` while the (B,H,T,S) score
    tile fits the materialization budget — one fused softmax beats the
    chunk bookkeeping at small sizes, which is exactly where the old
    always-chunked policy showed no measured win — and ``xla_chunked`` past
    it.  Worth-measuring buckets then time both once, with ref as the
    baseline: chunked must beat ref past the noise floor to keep the pick.
    """
    if b == "tpu":
        return "pallas_tpu" if window is None else "xla_chunked"
    if window is not None:
        return "xla_chunked"  # ref does not model sliding windows
    B, T, H, dh = q.shape
    S = k.shape[1]
    score_bytes = B * H * T * S * 4
    prior = "xla_ref" if score_bytes <= dispatch.MATERIALIZE_BUDGET else "xla_chunked"
    if not (dispatch.autotune_enabled() and dispatch.worth_measuring(score_bytes)):
        return prior
    ref_feasible = score_bytes <= 4 * dispatch.MATERIALIZE_BUDGET
    if not ref_feasible:
        return prior

    KV = k.shape[2]
    Tb, Sb = dispatch.shape_bucket(T), dispatch.shape_bucket(S)

    def bench(name):
        qs = jnp.zeros((B, Tb, H, dh), q.dtype)
        ks = jnp.zeros((B, Sb, KV, dh), q.dtype)
        fn = _ref_attention if name == "xla_ref" else chunked_attention
        return (
            lambda qq, kk, vv: fn(qq, kk, vv, causal=causal, window=None, scale=scale),
            (qs, ks, ks),
        )

    return dispatch.tuned_strategy(
        "flash_attention_strategy", (B, T, H, S, KV, dh), q.dtype,
        default=prior, candidates=("xla_ref", "xla_chunked"), bench=bench,
        baseline="xla_ref", inputs=(q, k, v),
    )


dispatch.register_selector("flash_attention", _select_attention)


# scale is static here: it reaches the Pallas kernel as a Python constant (a
# traced scalar would be a captured tracer inside pallas_call).
@functools.partial(jax.jit, static_argnames=("causal", "window", "scale", "impl"))
def _flash_attention_jit(q, k, v, *, causal, window, scale, impl):
    return dispatch.resolve(
        "flash_attention", impl, q, k, v, causal=causal, window=window, scale=scale
    ).fn(q, k, v, causal=causal, window=window, scale=scale)


# Variant with a traced scale, for the XLA impls (e.g. a learned temperature
# flowing through an outer jit) — only the Pallas kernel needs staticness.
@functools.partial(jax.jit, static_argnames=("causal", "window", "impl"))
def _flash_attention_jit_dynscale(q, k, v, scale, *, causal, window, impl):
    return dispatch.resolve(
        "flash_attention", impl, q, k, v, causal=causal, window=window, scale=scale
    ).fn(q, k, v, causal=causal, window=window, scale=scale)


def flash_attention(q, k, v, *, causal=True, window=None, scale=None, impl="auto"):
    """Dispatching attention.  Shapes: q (B,T,H,dh); k (B,S,KV,dh); v
    (B,S,KV,dv), where dv may differ from dh (latent attention).

    Resolution runs eagerly per call (env toggles honored); the compiled
    path is keyed on the resolved canonical impl name.
    """
    dh = q.shape[-1]
    scale = (dh ** -0.5) if scale is None else scale
    name = dispatch.resolve(
        "flash_attention", impl, q, k, v, causal=causal, window=window, scale=scale
    ).name
    if isinstance(scale, jax.core.Tracer):
        if name.startswith("pallas"):
            raise TypeError(
                f"flash_attention impl {name!r} needs a concrete scale "
                "(it is baked into the Pallas kernel); pass a Python float "
                "or use an xla_* impl"
            )
        return _flash_attention_jit_dynscale(
            q, k, v, scale, causal=causal, window=window, impl=name
        )
    # float() also accepts 0-d arrays / numpy scalars; the Tracer case was
    # routed to the dynamic-scale impl above, so this cast never syncs.
    return _flash_attention_jit(
        q, k, v, causal=causal, window=window, scale=float(scale), impl=name  # repro-lint: disable=JS101
    )


def decode_attention(q, k_cache, v_cache, cur_len, *, window=None, scale=None):
    """Single-token decode attention against a (possibly ring) KV cache.

    q: (B, 1, H, dh); caches: (B, S, KV, dh); ``cur_len``: (B,) or scalar —
    number of valid cache positions.  Positions ≥ cur_len are masked.
    """
    B, _, H, dh = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    dv = v_cache.shape[-1]
    g = H // KV
    scale = (dh ** -0.5) if scale is None else scale
    # Read the cache in ITS OWN dtype and accumulate in f32 via the MXU
    # (preferred_element_type) — upcasting the cache materializes (and, in a
    # scanned decode, carries) a full f32 copy of it: §Perf decode iteration.
    qg = (q.reshape(B, KV, g, dh) * scale).astype(k_cache.dtype)
    s = jnp.einsum(
        "bkgd,bskd->bkgs", qg, k_cache, preferred_element_type=jnp.float32
    )
    pos = jnp.arange(S)[None, :]  # (1, S)
    cur = jnp.asarray(cur_len).reshape(-1, 1)  # (B, 1) or (1, 1)
    valid = pos < cur
    if window is not None:
        valid &= pos >= cur - int(window)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, 1, H, dv).astype(q.dtype)
