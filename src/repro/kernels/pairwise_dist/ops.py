"""Jit'd public wrappers around the pairwise-distance kernels.

All backend/strategy choice is delegated to :mod:`repro.kernels.dispatch`:

* ``pallas_tpu``      — the compiled Pallas kernel (TPU only)
* ``pallas_interpret``— the same kernel in interpret mode (debug only; never
  auto-selected — force with ``impl="pallas_interpret"`` or
  ``REPRO_PALLAS_INTERPRET=1``)
* ``xla_ref``         — compiled XLA oracle (materializes the (n, k) matrix)
* ``xla_chunked``     — streaming assign_min: a ``lax.scan`` over center
  chunks so the (n, k) matrix is never materialized on any backend

Legacy ``impl`` strings keep working: ``"ref"`` → ``xla_ref``; ``"pallas"``
→ ``pallas_tpu`` on TPU, ``pallas_interpret`` elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel as _kernel
from . import ref as _ref
from .. import dispatch

__all__ = ["pairwise_sqdist", "assign_min"]

_PAD_DIST = jnp.float32(_kernel.PAD_DIST)


def _pad_to(x, m, axis, value=0.0):
    n = x.shape[axis]
    rem = (-n) % m
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, rem)
    return jnp.pad(x, widths, constant_values=value)


# ------------------------------------------------------------ pallas paths


def _tuned_cfg(op, x, c, interpret, run_with_cfg):
    """Shared-model block config, refined by the measured-autotune cache."""
    n, d = x.shape
    k = c.shape[0]
    dtype = x.dtype
    default = dispatch.pick_blocks(n, k, d)
    if interpret:  # debug path — measuring the interpreter is meaningless
        return default
    if not dispatch.worth_measuring(n * k * 4):
        return default  # below the floor the model is within noise of optimal
    cands = {default}
    if default.bn > dispatch.LANE:
        cands.add(dispatch.BlockConfig(default.bn // 2, default.bk))
    if default.bk > dispatch.LANE:
        cands.add(dispatch.BlockConfig(default.bn, default.bk // 2))

    def bench(cfg):
        # Synthetic inputs are BENCH ARGUMENTS (not closure constants) so
        # the timed program cannot be constant-folded away.
        xs = jnp.zeros((dispatch.shape_bucket(n), d), dtype)
        cs = jnp.zeros((dispatch.shape_bucket(k), d), dtype)
        return (lambda a, b: run_with_cfg(a, b, cfg), (xs, cs))

    return dispatch.tuned_block_config(
        op, (n, k, d), dtype, default=default, candidates=sorted(
            cands, key=lambda c: (c.bn, c.bk)
        ), bench=bench, inputs=(x, c),
    )


def _sqdist_pallas_cfg(x, c, cfg, interpret):
    n, k = x.shape[0], c.shape[0]
    xp = _pad_to(x, cfg.bn, 0)
    cp = _pad_to(c, cfg.bk, 0)
    out = _kernel.pairwise_sqdist_kernel_call(
        xp, cp, bn=cfg.bn, bk=cfg.bk, interpret=interpret
    )
    return out[:n, :k]


def _sqdist_pallas(x, c, *, interpret: bool):
    cfg = _tuned_cfg(
        "pairwise_sqdist", x, c, interpret,
        lambda xs, cs, cf: _sqdist_pallas_cfg(xs, cs, cf, False),
    )
    return _sqdist_pallas_cfg(x, c, cfg, interpret)


def _assign_pallas_cfg(x, c, cfg, interpret):
    n, k = x.shape[0], c.shape[0]
    xp = _pad_to(x, cfg.bn, 0)
    # Zero-pad centers; the kernel masks columns ≥ k by index (padding with
    # huge coordinates overflows ‖c‖² to inf → NaN via inf − inf).
    cp = _pad_to(c, cfg.bk, 0)
    idx, dist = _kernel.assign_min_kernel_call(
        xp, cp, bn=cfg.bn, bk=cfg.bk, k_valid=k, interpret=interpret
    )
    return idx[:n], dist[:n]


def _assign_pallas(x, c, *, interpret: bool):
    cfg = _tuned_cfg(
        "assign_min", x, c, interpret,
        lambda xs, cs, cf: _assign_pallas_cfg(xs, cs, cf, False),
    )
    return _assign_pallas_cfg(x, c, cfg, interpret)


# ------------------------------------------------- streaming XLA assign_min


def _chunk_bk(n: int, k: int) -> int:
    """Center-chunk width for the streaming path, calibrated against measured
    CPU behavior rather than the materialization budget alone.

    Two findings drove the recalibration (the old ``bk=1024``-down policy ran
    3.8× slower than ref at bench shape): (1) the per-step cost of the scan
    body grows superlinearly in ``bk`` past ~256 on CPU — the (n, bk) score
    tile spills cache and ``argmin``'s per-element index bookkeeping dominates
    — while a smaller ``bk`` merely adds cheap scan iterations, so measured
    curves are flat-to-falling all the way down to 128 even at n=65536; and
    (2) ``bk`` must never exceed ``shape_bucket(k)`` — a 1024-wide chunk over
    k=512 centers pads HALF the tile with masked columns that still get
    scored.  The measured-autotune pass refines this default per shape bucket.
    """
    return max(64, min(128, dispatch.shape_bucket(k)))


def _assign_min_chunked_bk(x, c, bk: int):
    n, d = x.shape
    k = c.shape[0]
    kp = -(-k // bk) * bk
    cp = jnp.pad(c.astype(jnp.float32), ((0, kp - k), (0, 0)))
    xf = x.astype(jnp.float32)
    x2 = jnp.sum(xf * xf, axis=1)  # (n,)

    def body(carry, j):
        best_d, best_i = carry
        cb = jax.lax.dynamic_slice_in_dim(cp, j * bk, bk, axis=0)  # (bk, d)
        c2 = jnp.sum(cb * cb, axis=1)
        xc = jnp.matmul(xf, cb.T, precision=dispatch.MATMUL_PRECISION)
        d2 = jnp.maximum(x2[:, None] + c2[None, :] - 2.0 * xc, 0.0)
        col = j * bk + jnp.arange(bk)
        d2 = jnp.where(col[None, :] < k, d2, _PAD_DIST)
        loc_i = jnp.argmin(d2, axis=1).astype(jnp.int32)
        loc_d = jnp.min(d2, axis=1)
        better = loc_d < best_d  # strict < keeps the earlier index on ties
        return (
            jnp.where(better, loc_d, best_d),
            jnp.where(better, j * bk + loc_i, best_i),
        ), None

    init = (jnp.full((n,), _PAD_DIST, jnp.float32), jnp.zeros((n,), jnp.int32))
    (dist, idx), _ = jax.lax.scan(body, init, jnp.arange(kp // bk))
    return idx, dist


def _broadcast_blocks(n: int, k: int, *, itemsize: int = 4) -> dispatch.BlockConfig:
    """(bn, kb) for the row-chunked broadcast rung: ``bn`` rows per scan step
    so the (bn, k) score tile respects the materialization budget; ``kb`` the
    inner block of the two-stage argmin reduction."""
    bn = 4096
    while bn > 8 and bn * max(k, 1) * itemsize > dispatch.MATERIALIZE_BUDGET:
        bn //= 2
    bn = max(8, min(bn, dispatch.shape_bucket(n)))
    kb = min(128, dispatch.shape_bucket(k))
    return dispatch.BlockConfig(bn=bn, bk=kb)


def _assign_min_broadcast_cfg(x, c, cfg):
    """BroadcastUDF-style nearest-center: ALL centers stay resident, the rows
    stream through in ``bn``-sized chunks.  Each scan step makes one
    well-shaped (bn, d) @ (d, k) matmul and reduces the score tile with a
    two-stage blocked argmin — min over kb-wide blocks, argmin over block
    minima, then argmin inside the single winning block — which is markedly
    cheaper than one flat argmin over (bn, k) (XLA's argmin pays index
    bookkeeping per element; min does not).  First-occurrence tie semantics
    are preserved: equal block minima resolve to the earlier block, equal
    scores inside a block to the earlier column — exactly ``xla_ref``'s rule.
    """
    bn, kb = cfg.bn, cfg.bk
    n, d = x.shape
    k = c.shape[0]
    nb = -(-n // bn) * bn
    kp = -(-k // kb) * kb
    xf = x.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    xp = jnp.pad(xf, ((0, nb - n), (0, 0)))
    cp = jnp.pad(cf, ((0, kp - k), (0, 0)))
    # Score s_j = ‖c_j‖² − 2·x·c_j orders exactly like the squared distance
    # (the ‖x‖² term is constant per row), so the full d² tile is never
    # formed.  Padded center columns carry a PAD_DIST ‖c‖² (their dot term
    # is 0 against the zero-padded cp rows), so they never win the argmin.
    c2 = jnp.pad(
        jnp.sum(cf * cf, axis=1), (0, kp - k), constant_values=_kernel.PAD_DIST
    )

    def body(carry, xb):
        xc = jnp.matmul(xb, cp.T, precision=dispatch.MATMUL_PRECISION)
        s = (c2[None, :] - 2.0 * xc).reshape(bn, kp // kb, kb)
        bm = jnp.min(s, axis=2)                                   # (bn, kp/kb)
        wb = jnp.argmin(bm, axis=1).astype(jnp.int32)             # winning block
        win = jnp.take_along_axis(s, wb[:, None, None], axis=1)[:, 0, :]
        wi = jnp.argmin(win, axis=1).astype(jnp.int32)            # col in block
        smin = jnp.take_along_axis(win, wi[:, None], axis=1)[:, 0]
        x2 = jnp.sum(xb * xb, axis=1)
        return carry, (wb * kb + wi, jnp.maximum(x2 + smin, 0.0))

    # The scalar carry is a stand-in for None: an empty-pytree carry hits
    # the 'empty' primitive, which has no eval rule when the autotune
    # measurement pass evaluates this rung eagerly.
    _, (idx, dist) = jax.lax.scan(
        body, jnp.int32(0), xp.reshape(nb // bn, bn, d)
    )
    return idx.reshape(-1)[:n], dist.reshape(-1)[:n]


def _assign_min_broadcast(x, c):
    n, d = x.shape
    k = c.shape[0]
    default = _broadcast_blocks(n, k)
    if not dispatch.worth_measuring(n * k * 4):
        return _assign_min_broadcast_cfg(x, c, default)
    cands = {default}
    if default.bn > 8:
        cands.add(dispatch.BlockConfig(default.bn // 2, default.bk))
    if default.bk > 8:
        cands.add(dispatch.BlockConfig(default.bn, default.bk // 2))

    def bench(cfg):
        xs = jnp.zeros((dispatch.shape_bucket(n), d), jnp.float32)
        cs = jnp.zeros((dispatch.shape_bucket(k), d), jnp.float32)
        return (lambda a, b: _assign_min_broadcast_cfg(a, b, cfg), (xs, cs))

    cfg = dispatch.tuned_block_config(
        "assign_min_broadcast", (n, k, d), x.dtype, default=default,
        candidates=sorted(cands, key=lambda c_: (c_.bn, c_.bk)), bench=bench,
        inputs=(x, c),
    )
    return _assign_min_broadcast_cfg(x, c, cfg)


def _assign_min_chunked(x, c):
    """ChunkedBroadcast-style nearest-center: scans center chunks carrying the
    running (min, argmin), so the (n, k) matrix is never materialized."""
    n, d = x.shape
    k = c.shape[0]
    default_bk = _chunk_bk(n, k)
    if not dispatch.worth_measuring(n * k * 4):
        return _assign_min_chunked_bk(x, c, default_bk)
    # Widened search space around the calibrated default — never wider than
    # the (padded) center count, where extra width is pure masked waste.
    cands = sorted(b for b in (64, 128, 256, 512) if b <= dispatch.shape_bucket(k))
    cands = cands or [default_bk]

    def bench(cfg):
        xs = jnp.zeros((dispatch.shape_bucket(n), d), jnp.float32)
        cs = jnp.zeros((dispatch.shape_bucket(k), d), jnp.float32)
        return (lambda a, b: _assign_min_chunked_bk(a, b, cfg.bk), (xs, cs))

    cfg = dispatch.tuned_block_config(
        "assign_min_chunked", (n, k, d), x.dtype,
        default=dispatch.BlockConfig(0, default_bk),
        candidates=[dispatch.BlockConfig(0, b) for b in cands],
        bench=bench, inputs=(x, c),
    )
    return _assign_min_chunked_bk(x, c, cfg.bk)


# ------------------------------------------------------------ registration


dispatch.register_impl("pairwise_sqdist", "xla_ref", _ref.pairwise_sqdist_ref)
dispatch.register_impl(
    "pairwise_sqdist", "pallas_tpu",
    functools.partial(_sqdist_pallas, interpret=False), backends=("tpu",),
)
dispatch.register_impl(
    "pairwise_sqdist", "pallas_interpret",
    functools.partial(_sqdist_pallas, interpret=True), debug_only=True,
)
dispatch.register_alias("pairwise_sqdist", "ref", "xla_ref")
dispatch.register_alias(
    "pairwise_sqdist", "pallas",
    lambda b: "pallas_tpu" if b == "tpu" else "pallas_interpret",
)
dispatch.register_selector(
    "pairwise_sqdist",
    # The output IS the (n, k) matrix, so off-TPU the compiled oracle is
    # optimal at every size.
    lambda b, x, c: "pallas_tpu" if b == "tpu" else "xla_ref",
)

dispatch.register_impl("assign_min", "xla_ref", _ref.assign_min_ref)
dispatch.register_impl("assign_min", "xla_broadcast", _assign_min_broadcast)
dispatch.register_impl("assign_min", "xla_chunked", _assign_min_chunked)
dispatch.register_impl(
    "assign_min", "pallas_tpu",
    functools.partial(_assign_pallas, interpret=False), backends=("tpu",),
)
dispatch.register_impl(
    "assign_min", "pallas_interpret",
    functools.partial(_assign_pallas, interpret=True), debug_only=True,
)
dispatch.register_alias("assign_min", "ref", "xla_ref")
dispatch.register_alias("assign_min", "broadcast", "xla_broadcast")
dispatch.register_alias(
    "assign_min", "pallas",
    lambda b: "pallas_tpu" if b == "tpu" else "pallas_interpret",
)

_LADDER_IMPLS = {
    "ref": "xla_ref",
    "broadcast": "xla_broadcast",
    "chunked": "xla_chunked",
}


# Ref stays in the measured candidate set only while its (n, k) matrix is
# merely *over budget*, not absurd — measuring a candidate that has to
# materialize gigabytes would blow the measurement budget on a known loser.
_REF_CANDIDATE_BUDGET = 4 * dispatch.MATERIALIZE_BUDGET


def _select_assign(b, x, c):
    """Measured-first rung selection for ``assign_min``.

    The SNIPPETS-1 analytic ladder (ref/broadcast/chunked by n·k and k·d) is
    the *prior*; by default every worth-measuring shape bucket times the
    plausible rungs once (winners cached in-process and on disk) and the
    measured pick wins.  ``xla_ref`` is the baseline: any rung that does not
    beat it past the noise floor loses back to ref, so the auto path can
    never pick a rung measured slower than ref.  ``REPRO_AUTOTUNE=0`` opts
    out to the bare ladder.
    """
    if b == "tpu":
        return "pallas_tpu"
    n, d = x.shape
    k = c.shape[0]
    impl = _LADDER_IMPLS[dispatch.ladder_strategy(n, k, d)]
    if not (dispatch.autotune_enabled() and dispatch.worth_measuring(n * k * 4)):
        return impl

    ref_feasible = n * k * 4 <= _REF_CANDIDATE_BUDGET
    cands = ["xla_broadcast", "xla_chunked"]
    if ref_feasible:
        cands.insert(0, "xla_ref")

    def bench(name):
        xs = jnp.zeros((dispatch.shape_bucket(n), d), jnp.float32)
        cs = jnp.zeros((dispatch.shape_bucket(k), d), jnp.float32)
        fn = {
            "xla_ref": _ref.assign_min_ref,
            "xla_broadcast": _assign_min_broadcast,
            "xla_chunked": _assign_min_chunked,
        }[name]
        return (fn, (xs, cs))

    return dispatch.tuned_strategy(
        "assign_min_strategy", (n, k, d), x.dtype, default=impl,
        candidates=tuple(cands), bench=bench,
        baseline="xla_ref" if ref_feasible else None, inputs=(x, c),
    )


dispatch.register_selector("assign_min", _select_assign)


# ---------------------------------------------------------- public wrappers
#
# Resolution (env vars, shape policy, aliases) runs EAGERLY on every call so
# REPRO_PALLAS_INTERPRET toggles are honored even after a shape has been
# compiled; only the resolved canonical name is a jit cache key.  (Inside an
# outer jit — e.g. lloyd's loop — resolution is captured at that trace.)


@functools.partial(jax.jit, static_argnames=("impl",))
def _pairwise_sqdist_jit(x, c, *, impl: str):
    return dispatch.resolve("pairwise_sqdist", impl, x, c).fn(x, c)


def pairwise_sqdist(x, c, *, impl: str = "auto"):
    """Squared Euclidean distance matrix (n, k) f32."""
    name = dispatch.resolve("pairwise_sqdist", impl, x, c).name
    return _pairwise_sqdist_jit(x, c, impl=name)


@functools.partial(jax.jit, static_argnames=("impl",))
def _assign_min_jit(x, c, *, impl: str):
    return dispatch.resolve("assign_min", impl, x, c).fn(x, c)


def assign_min(x, c, *, impl: str = "auto"):
    """Nearest-center assignment: (idx (n,) i32, sqdist (n,) f32)."""
    name = dispatch.resolve("assign_min", impl, x, c).name
    return _assign_min_jit(x, c, impl=name)
