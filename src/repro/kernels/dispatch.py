"""Backend-aware kernel dispatch — shared by every kernel family.

The three kernel families (``pairwise_dist``, ``weighted_segsum``,
``flash_attention``) register *named implementations* here instead of each
carrying its own ``interpret=not _on_tpu()`` logic and ad-hoc size cutoffs.

Resolution rules (``resolve(op, impl, ...)``):

* An explicit canonical name (``"xla_ref"``, ``"xla_chunked"``,
  ``"pallas_tpu"``, ``"pallas_interpret"``, ...) selects that registered
  implementation directly.
* ``"auto"`` asks the op's *selector* (a shape/backend-aware callback) for
  the best implementation.  Off-TPU this is always a **compiled** XLA path —
  interpret-mode Pallas is never auto-selected; it survives only behind an
  explicit ``impl="pallas_interpret"`` or the ``REPRO_PALLAS_INTERPRET=1``
  debug env var.
* Legacy per-op aliases (``"pallas"``, ``"ref"``, ``"chunked"``) map onto
  canonical names so existing call sites keep working.

The module also owns the *analytic* cross-op sizing policies:

* :func:`pick_blocks` — one VMEM-aware block-size model: choose ``(bn, bk)``
  so the f32 working set ``(bn·d + bk·d + bn·bk)·itemsize`` fits a VMEM
  budget, in multiples of the TPU lane width (128) that the chip's compiler
  accepts.
* :func:`should_stream` — whether an op should take a chunked/streaming path
  instead of materializing an ``(n, k)`` intermediate.
* :func:`ladder_strategy` — the ref/broadcast/chunked assignment ladder.

These analytic models are **priors, not verdicts**: selection is
measured-first by default.  The measurement machinery — shape-bucketed
timing, the budgeted candidate pass, the versioned persistent cache, and
the ``warmup(plan)`` API — lives in :mod:`repro.kernels.autotune` and is
re-exported here for backward compatibility (``dispatch.tuned_strategy``,
``dispatch.autotune_cache_info``, ... keep working).  Opt out with
``REPRO_AUTOTUNE=0`` to fall back to the pure analytic models.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, Sequence, Tuple

# Back-compat re-exports: the measured-autotune subsystem grew out of this
# module and its public names remain reachable from ``dispatch``.  The cache
# dicts are shared objects (not copies), so introspection/monkeypatching of
# ``dispatch._AUTOTUNE_CACHE`` et al. still observes the live state.
from .autotune import (  # noqa: F401
    AUTOTUNE_CACHE_ENV,
    AUTOTUNE_ENV,
    BlockConfig,
    WarmupReport,
    _AUTOTUNE_CACHE,
    _AUTOTUNE_STATS,
    _PERSIST_VERSION,
    _STRATEGY_CACHE,
    _pow2_ceil,
    _time_once,
    autotune_cache_dir,
    autotune_cache_file,
    autotune_cache_info,
    autotune_enabled,
    backend,
    clear_autotune_cache,
    device_kind,
    shape_bucket,
    tuned_block_config,
    tuned_strategy,
    warm_start_enabled,
    warmup,
    worth_measuring,
)

__all__ = [
    "BlockConfig",
    "WarmupReport",
    "autotune_cache_dir",
    "autotune_cache_file",
    "autotune_cache_info",
    "autotune_enabled",
    "backend",
    "block_footprint",
    "clear_autotune_cache",
    "device_kind",
    "dispatch",
    "impl_names",
    "interpret_enabled",
    "ladder_strategy",
    "pick_blocks",
    "register_alias",
    "register_impl",
    "register_selector",
    "resolve",
    "shape_bucket",
    "should_stream",
    "tuned_block_config",
    "tuned_strategy",
    "warm_start_enabled",
    "warmup",
    "worth_measuring",
]

# Debug env var — read at resolution time.  The public ops resolve eagerly on
# every call, so toggling mid-process works there; code that bakes a
# resolution into its own jit trace (e.g. core.kmeans.lloyd) keeps the value
# seen when its shape was first traced.
INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"

# Default budgets of the shared sizing model.  VMEM_BUDGET bounds the per-tile
# working set of the Pallas kernels, as block_footprint counts it, at three
# quarters of the 16 MiB that Mosaic lets one kernel use by default on a v5e
# (the rest is the compiler's own scratch); MATERIALIZE_BUDGET bounds how
# large an (n, k) intermediate an op may materialize before auto-dispatch
# switches to a streaming path.
VMEM_BUDGET = 12 * 1024 * 1024
MATERIALIZE_BUDGET = 32 * 1024 * 1024

# TPU vector lane width.  Every block size pick_blocks returns is a multiple
# of it: bn is the lane axis of the kernels' lane-dense (1, bn) per-point
# rows, bk the lane axis of pairwise_sqdist's (bn, bk) output tile, and both
# are MXU-aligned.  A multiple of 128 is also a multiple of the sublane
# count (8), so every block meets the chip's (8, 128) tiling rule.
LANE = 128

# Precision of every matmul in the clustering kernels and their XLA paths:
# full f32.  At the default precision a TPU rounds f32 operands to bf16,
# which moves a squared distance ‖x‖² + ‖c‖² − 2·x·c by about 2⁻⁸·‖x‖² —
# more than the distance from a point to its own center in typical data —
# so the nearest center would be picked from rounding noise.
MATMUL_PRECISION = "highest"


def interpret_enabled() -> bool:
    """Debug override: force interpret-mode Pallas everywhere."""
    return os.environ.get(INTERPRET_ENV, "").lower() in ("1", "true", "yes")


# --------------------------------------------------------------- registry


@dataclasses.dataclass(frozen=True)
class ImplInfo:
    op: str
    name: str
    fn: Callable
    backends: Tuple[str, ...]  # backends where auto-selection may pick it
    debug_only: bool = False  # never auto-selected (e.g. interpret mode)


_REGISTRY: Dict[str, Dict[str, ImplInfo]] = {}
_ALIASES: Dict[str, Dict[str, Callable[[str], str]]] = {}
_SELECTORS: Dict[str, Callable[..., str]] = {}


def register_impl(
    op: str,
    name: str,
    fn: Callable,
    *,
    backends: Sequence[str] = ("cpu", "gpu", "tpu"),
    debug_only: bool = False,
) -> Callable:
    """Register implementation ``name`` for ``op``.  Returns ``fn``."""
    _REGISTRY.setdefault(op, {})[name] = ImplInfo(
        op=op, name=name, fn=fn, backends=tuple(backends), debug_only=debug_only
    )
    return fn


def register_alias(op: str, alias: str, to: Callable[[str], str] | str) -> None:
    """Map a legacy ``impl`` string onto a canonical name (may depend on the
    backend, e.g. ``"pallas"`` → ``pallas_tpu`` on TPU / ``pallas_interpret``
    elsewhere)."""
    fn = (lambda _b, _to=to: _to) if isinstance(to, str) else to
    _ALIASES.setdefault(op, {})[alias] = fn


def register_selector(op: str, fn: Callable[..., str]) -> None:
    """Install the ``"auto"`` selector for ``op``: ``fn(backend, *args,
    **kwargs) -> canonical impl name``.  Called at trace time with the op's
    actual arguments, so it can inspect static shapes."""
    _SELECTORS[op] = fn


def impl_names(op: str) -> Tuple[str, ...]:
    return tuple(_REGISTRY.get(op, {}))


def resolve(op: str, impl: str = "auto", *args: Any, **kwargs: Any) -> ImplInfo:
    """Resolve ``impl`` to a registered implementation for ``op``.

    ``*args``/``**kwargs`` are the op's call arguments — forwarded to the
    selector so ``"auto"`` can be shape-aware.
    """
    if op not in _REGISTRY:
        raise KeyError(f"unknown op {op!r}; registered: {sorted(_REGISTRY)}")
    impls = _REGISTRY[op]
    b = backend()
    name = impl
    if name == "auto":
        if interpret_enabled() and "pallas_interpret" in impls:
            name = "pallas_interpret"
        else:
            sel = _SELECTORS.get(op)
            if sel is not None:
                name = sel(b, *args, **kwargs)
            else:  # first registered impl eligible on this backend
                name = next(
                    (
                        n
                        for n, info in impls.items()
                        if b in info.backends and not info.debug_only
                    ),
                    "xla_ref",
                )
    elif name in _ALIASES.get(op, {}):
        name = _ALIASES[op][name](b)
    if name not in impls:
        raise KeyError(
            f"op {op!r} has no impl {name!r}; available: {sorted(impls)}"
        )
    info = impls[name]
    # Explicitly named impls still honor the backend gate (a clear error here
    # beats an opaque Mosaic lowering failure); debug impls run anywhere.
    if not info.debug_only and b not in info.backends:
        raise KeyError(
            f"impl {name!r} of op {op!r} is not available on backend {b!r} "
            f"(supported: {info.backends})"
        )
    return info


def dispatch(op: str, impl: str, *args: Any, **kwargs: Any) -> Any:
    """Resolve and call."""
    return resolve(op, impl, *args, **kwargs).fn(*args, **kwargs)


# ------------------------------------------------------- block-size model


def block_footprint(bn: int, bk: int, d: int, *, itemsize: int = 4) -> int:
    """VMEM bytes of one grid step as the chip holds it.

    The x-tile (bn, d) and the c-tile (bk, d) are double-buffered by the
    pipeline and split into three bf16 terms for the f32 contraction
    (``2·itemsize + 6`` bytes an element); the (bn, bk) product tile is held
    with one masked copy.  For weighted_segsum bk is the whole k: the (k, d)
    accumulator and the (k, bn) one-hot.  Calibrated against compiles for a
    described v5e: at d=2048 a (512, 256) assign_min tile needs 16.4 MiB.
    """
    return (bn + bk) * d * (2 * itemsize + 6) + 2 * bn * bk * itemsize


def pick_blocks(
    n: int,
    k: int,
    d: int,
    *,
    itemsize: int = 4,
    vmem_budget: int = VMEM_BUDGET,
    bn_cap: int = 512,
    bk_cap: int = 2 * LANE,
) -> BlockConfig:
    """The one VMEM-aware tile model shared by every blocked op.

    Start from the caps (clamped to the padded problem size, never below one
    lane) and halve — bn first, it has the bigger footprint — until
    :func:`block_footprint` fits the budget or both sizes reach 128.  Both
    sizes stay multiples of :data:`LANE`, the only block shapes the TPU
    compiler accepts for these kernels, plain and under ``jax.vmap``.
    Callers pad their operands up to the returned multiples.
    """
    bn = max(LANE, min(bn_cap, _pow2_ceil(n)))
    bk = max(LANE, min(bk_cap, _pow2_ceil(k)))
    while bn > LANE and block_footprint(bn, bk, d, itemsize=itemsize) > vmem_budget:
        bn //= 2
    while bk > LANE and block_footprint(bn, bk, d, itemsize=itemsize) > vmem_budget:
        bk //= 2
    return BlockConfig(bn=bn, bk=bk)


def should_stream(n: int, k: int, *, itemsize: int = 4, budget: int = MATERIALIZE_BUDGET) -> bool:
    """True when an (n, k) intermediate is too large to materialize and the
    op should take its chunked/streaming implementation instead."""
    return n * k * itemsize > budget


# Centers working set (k·d elements, ≈4 MB f32 at the default) above which
# even a "broadcast all centers, chunk the rows" pass holds too much resident
# state and the center-chunked streaming rung takes over.  The analogue of
# the SECrossJoin / BroadcastUDF / ChunkedBroadcast broadcastThresholdElems
# cutoff (SNIPPETS.md Snippet 1), sized for one core's L2/L3 reuse here.
BROADCAST_ELEMS = 1 << 20


def ladder_strategy(
    n: int,
    k: int,
    d: int,
    *,
    itemsize: int = 4,
    materialize_budget: int = MATERIALIZE_BUDGET,
    broadcast_elems: int = BROADCAST_ELEMS,
) -> str:
    """The cross-op assignment-strategy ladder, selected by n·k and k·d.

    * ``"ref"``        — materialize the full (n, k) matrix: optimal while it
      fits the budget (one fused pass, best matmul shape).
    * ``"broadcast"``  — broadcast ALL centers, chunk the *rows*: each scan
      step computes a budget-sized (bn, k) score tile with one well-shaped
      matmul and reduces it immediately.  Right whenever the centers
      themselves are small (k·d under ``broadcast_elems``).
    * ``"chunked"``    — chunk the *centers*, carry a running (min, argmin)
      over the whole n: the only rung whose resident state is O(n) no matter
      how large k·d grows.

    Pure shape *prior* — by default callers refine the choice per measured
    shape bucket via :func:`repro.kernels.autotune.tuned_strategy`
    (measured-first; ``REPRO_AUTOTUNE=0`` opts out to this ladder alone).
    """
    if n * k * itemsize <= materialize_budget:
        return "ref"
    if k * d <= broadcast_elems:
        return "broadcast"
    return "chunked"
