"""Measured-first autotune: calibrated, budgeted, persistent, warm-startable.

The dispatch layer's analytic models (VMEM block model, strategy ladder)
are *priors*, not verdicts — off-TPU especially they are mis-calibrated and
pick losing implementations (a chunked ``assign_min`` 3.8× slower than ref
at bench shape was the motivating case).  This module flips selection to
**measured-first as the opt-out default**:

* **Shape buckets.**  Incoming shapes quantize to the same power-of-two
  buckets the serving tier uses (:func:`shape_bucket`), so one measurement
  serves every ragged shape in an octave.
* **Bounded measurement.**  On the first sighting of a bucket, the ladder /
  block-config candidates are timed compiled (``REPRO_AUTOTUNE_TRIALS``
  reps each, median) under a per-bucket wall-clock budget
  (``REPRO_AUTOTUNE_BUDGET_MS``) — the analytic default is measured FIRST,
  so when the budget stops the pass early the prior has already been
  calibrated against at least one alternative or wins by default.
* **The analytic model is demoted to prior/tiebreaker.**  A candidate must
  beat the measured default by more than the noise floor
  (``REPRO_AUTOTUNE_NOISE``, relative) to displace it, and a designated
  *baseline* (``xla_ref`` where feasible) wins back any pick that is not
  measurably faster than it — "no measured win" resolves to ref, never to
  a fashionable streaming rung.
* **Versioned, self-healing persistence.**  Winners persist to one JSON
  file per ``(backend, device kind)`` in ``.autotune_cache/`` inside the
  checkout (``REPRO_AUTOTUNE_CACHE`` overrides; ``0``/``off`` disables).
  Writes are atomic (tmp file + rename) and merge entries a concurrent
  process saved between our load and our save; corrupt, stale-version, or
  foreign-device files are ignored and overwritten by the next measurement.
* **Warm-start.**  :func:`warmup` runs a tier-declared plan of callables
  (pre-measuring buckets and pre-compiling programs) off the hot path —
  the serving frontend, streaming session, and trainer each declare their
  bucket set and re-warm on model/generation bumps.

Opting out: ``REPRO_AUTOTUNE=0`` (or ``off``/``model``) falls back to the
pure analytic models — deterministic, zero measurement, zero disk IO.
Small shapes never measure regardless (:func:`worth_measuring`): below
``REPRO_AUTOTUNE_MIN_BYTES`` the analytic answer is within noise of optimal
and the measurement pass would cost more than it could ever save.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import re
import tempfile
import time
from typing import Any, Callable, Dict, Iterable, Optional, Sequence, Tuple

import jax

_log = logging.getLogger(__name__)

__all__ = [
    "BlockConfig",
    "WarmupReport",
    "autotune_cache_dir",
    "autotune_cache_file",
    "autotune_cache_info",
    "autotune_enabled",
    "backend",
    "clear_autotune_cache",
    "device_kind",
    "measure_budget_s",
    "measure_trials",
    "noise_rel",
    "shape_bucket",
    "tuned_block_config",
    "tuned_strategy",
    "warm_start_enabled",
    "warmup",
    "worth_measuring",
]

# Env knobs — read at resolution time, so toggling mid-process works for the
# eagerly-resolved public ops (code that bakes a resolution into its own jit
# trace keeps the value seen when that shape was first traced).
AUTOTUNE_ENV = "REPRO_AUTOTUNE"                # opt-OUT: 0/off/model disables
AUTOTUNE_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"    # persistence dir (0/off: none)
TRIALS_ENV = "REPRO_AUTOTUNE_TRIALS"           # timed reps per candidate
BUDGET_ENV = "REPRO_AUTOTUNE_BUDGET_MS"        # per-bucket measuring budget
NOISE_ENV = "REPRO_AUTOTUNE_NOISE"             # relative noise floor
MIN_BYTES_ENV = "REPRO_AUTOTUNE_MIN_BYTES"     # smallest bucket worth measuring
WARM_START_ENV = "REPRO_WARM_START"            # opt-OUT: tier warm-up plans

_OFF_VALUES = ("0", "off", "false", "no", "none", "model", "analytic")

DEFAULT_TRIALS = 3
DEFAULT_BUDGET_MS = 10_000.0
DEFAULT_NOISE_REL = 0.10
# 1 MB of intermediate: below this the analytic prior is within noise of
# optimal on every backend we measure, and a measurement pass (2-3 compiles)
# costs orders of magnitude more than the op itself.
DEFAULT_MIN_BYTES = 1 << 20


def autotune_enabled() -> bool:
    """Whether measured autotuning is on.  Measured-first is the DEFAULT —
    unset means on; ``REPRO_AUTOTUNE=0`` / ``off`` / ``model`` opts out to
    the pure analytic models."""
    return os.environ.get(AUTOTUNE_ENV, "1").strip().lower() not in _OFF_VALUES


def warm_start_enabled() -> bool:
    """Whether the tiers auto-run their warm-up plans (serving frontend on
    generation bumps, streaming solve, trainer setup).  On by default;
    ``REPRO_WARM_START=0`` opts out."""
    return os.environ.get(WARM_START_ENV, "1").strip().lower() not in _OFF_VALUES


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, str(default)))
    except ValueError:
        return default


def measure_trials() -> int:
    """Timed reps per candidate (median taken; +1 warmup/compile rep)."""
    return max(1, int(_env_float(TRIALS_ENV, DEFAULT_TRIALS)))


def measure_budget_s() -> float:
    """Per-bucket measurement budget in seconds (compile time included)."""
    return max(0.0, _env_float(BUDGET_ENV, DEFAULT_BUDGET_MS)) / 1e3


def noise_rel() -> float:
    """Relative noise floor: a measured delta below this is a tie."""
    return max(0.0, _env_float(NOISE_ENV, DEFAULT_NOISE_REL))


def worth_measuring(nbytes: int) -> bool:
    """Whether a bucket moving ``nbytes`` of intermediate justifies a
    measurement pass at all (tiny shapes stay on the analytic prior)."""
    return nbytes >= max(0.0, _env_float(MIN_BYTES_ENV, DEFAULT_MIN_BYTES))


# ----------------------------------------------------------- device identity


def backend() -> str:
    """The JAX default backend ("cpu" | "gpu" | "tpu")."""
    return jax.default_backend()


def device_kind() -> str:
    """Filesystem-safe kind of device 0 (e.g. "cpu", "TPU-v4", "NVIDIA-A100").

    Finer-grained than :func:`backend`: measured winners transfer between
    processes only within the same hardware generation, so the persistent
    cache is keyed on (backend, device kind).
    """
    kind = jax.devices()[0].device_kind  # no device is an error, not a kind
    return re.sub(r"[^A-Za-z0-9_.-]+", "-", str(kind)).strip("-")


# ------------------------------------------------------------ shape buckets


def _pow2_ceil(x: int) -> int:
    return 1 << max(int(x) - 1, 1).bit_length()


def shape_bucket(v: int) -> int:
    """Next power of two — ragged shapes share one cache entry per octave
    (the same quantization the serving tier's micro-batcher pads to)."""
    return _pow2_ceil(v)


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    bn: int
    bk: int


# -------------------------------------------------------------- cache state


_AUTOTUNE_CACHE: Dict[tuple, BlockConfig] = {}
# Measured *strategy* winners (ladder rung per shape bucket) — same keying as
# the block-config cache, but the cached value is a canonical impl name.
_STRATEGY_CACHE: Dict[tuple, str] = {}
class _RegistryStats:
    """Dict-like view over the ``autotune_*`` counters in the process-wide
    metrics registry — same ``stats["hits"] += 1`` call sites as the old
    plain dict, but the numbers surface in obs-report too."""

    FIELDS = (
        "hits", "misses", "measured", "errors", "warmup_errors",
        "budget_stops", "deferred", "disk_loaded", "disk_errors",
    )

    def __init__(self):
        from ..obs import default_registry

        self._c = {
            f: default_registry().counter(
                "autotune_" + f, help=f"autotune {f.replace('_', ' ')}"
            )
            for f in self.FIELDS
        }

    def __getitem__(self, k: str) -> int:
        return int(self._c[k].value)

    def __setitem__(self, k: str, v) -> None:
        self._c[k].set(v)

    def __iter__(self):
        return iter(self._c)

    def keys(self):
        return self._c.keys()


_AUTOTUNE_STATS = _RegistryStats()
# Which persistent file the in-memory cache has been hydrated from (None =
# not yet).  Re-checked per lookup so a monkeypatched env var / device kind
# (tests) or a cleared cache triggers a fresh load.
_PERSIST_LOADED_FROM: Optional[str] = None
# v2: measured-first era — winners may carry their measured time (``us``)
# and strategy entries a baseline; v1 files predate the calibration fixes
# (mis-calibrated winners) and are invalidated wholesale.
_PERSIST_VERSION = 2


def clear_autotune_cache() -> None:
    """Forget all in-memory winners and stats (the on-disk cache survives;
    delete :func:`autotune_cache_file` to force re-measurement on disk too)."""
    global _PERSIST_LOADED_FROM
    _AUTOTUNE_CACHE.clear()
    _STRATEGY_CACHE.clear()
    _PERSIST_LOADED_FROM = None
    for k in _AUTOTUNE_STATS:
        _AUTOTUNE_STATS[k] = 0


def autotune_cache_info() -> dict:
    return {
        "entries": dict(_AUTOTUNE_CACHE),
        "strategies": dict(_STRATEGY_CACHE),
        **_AUTOTUNE_STATS,
    }


def _bucket_key(op: str, shapes: Sequence[int], dtype: Any) -> tuple:
    return (
        op, backend(), device_kind(),
        tuple(shape_bucket(s) for s in shapes), str(dtype),
    )


# ------------------------------------------------- persistent autotune cache


def autotune_cache_dir() -> Optional[str]:
    """Directory for persisted winners; None disables persistence.

    ``REPRO_AUTOTUNE_CACHE`` overrides (``0``/``off``/``none`` to disable);
    the default is a fixed directory inside the checkout
    (:data:`repro.caches.AUTOTUNE_CACHE_DIR`), so two checkouts measured on
    one machine never share winners.
    """
    v = os.environ.get(AUTOTUNE_CACHE_ENV)
    if v is not None:
        if v.strip().lower() in ("", "0", "off", "none", "false"):
            return None
        return os.path.expanduser(v)
    from ..caches import AUTOTUNE_CACHE_DIR

    return str(AUTOTUNE_CACHE_DIR)


def autotune_cache_file() -> Optional[str]:
    """Path of the persistent cache for the CURRENT (backend, device kind).

    One file per hardware flavour keeps winners measured on one machine from
    leaking onto different silicon: a TPU-v4 pod and the CPU smoke-test
    runner never read each other's tables.
    """
    d = autotune_cache_dir()
    if d is None:
        return None
    return os.path.join(d, f"autotune-{backend()}-{device_kind()}.json")


def _persist_load() -> None:
    """Hydrate the in-memory cache from disk (idempotent per file path).

    Any malformed, unreadable, stale-version, or foreign (backend /
    device-kind mismatch) file is ignored — the caller falls through to
    re-measurement and the next save overwrites the bad file.
    """
    global _PERSIST_LOADED_FROM
    path = autotune_cache_file()
    if path is None or path == _PERSIST_LOADED_FROM:
        return
    _PERSIST_LOADED_FROM = path
    try:
        with open(path) as f:
            payload = json.load(f)
        if (
            payload.get("version") != _PERSIST_VERSION
            or payload.get("backend") != backend()
            or payload.get("device_kind") != device_kind()
        ):
            raise ValueError("cache file is for a different build or device")
        loaded = 0
        for e in payload["entries"]:
            key = _bucket_key(str(e["op"]), [int(s) for s in e["shapes"]], e["dtype"])
            cfg = BlockConfig(bn=int(e["bn"]), bk=int(e["bk"]))
            if key not in _AUTOTUNE_CACHE:  # in-process winners take priority
                _AUTOTUNE_CACHE[key] = cfg
                loaded += 1
        for e in payload.get("strategies", []):
            key = _bucket_key(str(e["op"]), [int(s) for s in e["shapes"]], e["dtype"])
            if key not in _STRATEGY_CACHE:
                _STRATEGY_CACHE[key] = str(e["choice"])
                loaded += 1
        _AUTOTUNE_STATS["disk_loaded"] += loaded
    except FileNotFoundError:
        pass
    except Exception:
        _AUTOTUNE_STATS["disk_errors"] += 1


def _persist_save() -> None:
    """Write all in-memory winners for the current (backend, device kind)
    atomically (tmp file + rename); persistence failures never fail the op.

    Disk entries this process has not seen (a concurrent process measured a
    different shape bucket between our load and this save) are merged back
    in rather than clobbered; in-memory winners take priority on conflicts.
    """
    path = autotune_cache_file()
    if path is None:
        return
    b, kind = backend(), device_kind()
    merged = {
        (op, tuple(shapes), dtype): cfg
        for (op, kb, kk, shapes, dtype), cfg in _AUTOTUNE_CACHE.items()
        if kb == b and kk == kind
    }
    merged_strat = {
        (op, tuple(shapes), dtype): choice
        for (op, kb, kk, shapes, dtype), choice in _STRATEGY_CACHE.items()
        if kb == b and kk == kind
    }
    try:
        with open(path) as f:
            payload = json.load(f)
        # Same gate as _persist_load: never launder entries from a corrupt,
        # stale-version, or foreign-device file back in under a valid header.
        if (
            payload.get("version") == _PERSIST_VERSION
            and payload.get("backend") == b
            and payload.get("device_kind") == kind
        ):
            for e in payload["entries"]:
                k = (str(e["op"]), tuple(int(s) for s in e["shapes"]), str(e["dtype"]))
                merged.setdefault(k, BlockConfig(bn=int(e["bn"]), bk=int(e["bk"])))
            for e in payload.get("strategies", []):
                k = (str(e["op"]), tuple(int(s) for s in e["shapes"]), str(e["dtype"]))
                merged_strat.setdefault(k, str(e["choice"]))
    except Exception:
        pass  # unreadable/corrupt file: overwritten below
    entries = [
        {"op": op, "shapes": list(shapes), "dtype": dtype, "bn": cfg.bn, "bk": cfg.bk}
        for (op, shapes, dtype), cfg in sorted(merged.items())
    ]
    strategies = [
        {"op": op, "shapes": list(shapes), "dtype": dtype, "choice": choice}
        for (op, shapes, dtype), choice in sorted(merged_strat.items())
    ]
    payload = {
        "version": _PERSIST_VERSION, "backend": b, "device_kind": kind,
        "entries": entries, "strategies": strategies,
    }
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(path), prefix=".autotune-", suffix=".tmp"
        )
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, path)
    except OSError:
        _AUTOTUNE_STATS["disk_errors"] += 1


# ---------------------------------------------------------------- measuring


def _time_once(item, *, reps: Optional[int] = None) -> float:
    """Median wall time of compiled executions of one bench item.

    ``item`` is either ``(fn, args)`` — the preferred form: ``fn`` is jitted
    and timed on the concrete ``args`` — or a legacy zero-arg callable.  The
    two-tuple form matters for measurement fidelity: synthetic inputs must
    enter as jit *arguments*, because inputs captured as closure constants
    make the entire computation constant-foldable — XLA folds it at compile
    time and the "measurement" times an empty program.
    """
    fn, args = item if isinstance(item, tuple) else (item, ())
    reps = measure_trials() if reps is None else reps
    # Benchmarking jit: one-shot by design, eager-context only.
    run = jax.jit(fn)  # repro-lint: disable=JS201
    times = []
    for _ in range(reps + 1):  # first rep warms up / compiles
        t0 = time.perf_counter()
        out = run(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times = sorted(times[1:])
    return times[len(times) // 2]


def _deferred(inputs: Sequence[Any]) -> bool:
    """Measurement is eager-only: when the op's own ``inputs`` are tracers
    (it is being staged into an outer ``jit``/``vmap``) the bench inputs
    would be staged too and nothing could execute.  The public ops resolve
    eagerly and the warm-up plans run eagerly, which is where buckets get
    measured; traced code then reads the caches (or gets the analytic
    default, uncached, so a later eager call still measures)."""
    leaves = jax.tree_util.tree_leaves(inputs)
    if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
        _AUTOTUNE_STATS["deferred"] += 1
        return True
    return False


def _measure_pass(ordered: Sequence, bench: Callable) -> Dict:
    """Time each candidate (first-to-last) under the per-bucket budget.

    The caller puts the analytic default FIRST: if the budget truncates the
    pass, the prior has been measured and later candidates simply never get
    the chance to displace it.  Candidates that fail to compile never win.
    """
    times: Dict = {}
    from ..obs import trace_span

    budget = measure_budget_s()
    with trace_span("autotune.measure", candidates=len(ordered)) as sp:
        t_start = time.perf_counter()
        for cand in ordered:
            if times and (time.perf_counter() - t_start) > budget:
                _AUTOTUNE_STATS["budget_stops"] += 1
                break
            try:
                t = _time_once(bench(cand))
            except Exception:
                _log.warning("autotune candidate %r failed", cand, exc_info=True)
                _AUTOTUNE_STATS["errors"] += 1
                continue
            _AUTOTUNE_STATS["measured"] += 1
            times[cand] = t
        sp.set_attr(measured=len(times))
    return times


def _pick(times: Dict, default, baseline=None):
    """Measured-first winner with the analytic model demoted to tiebreaker.

    Fastest measured candidate wins — unless the ``default`` (the analytic
    prior) or the ``baseline`` (e.g. ``xla_ref``) is within the noise floor
    of it, in which case stability beats a delta the measurement cannot
    distinguish from zero: the prior keeps its seat, and a baseline that is
    not measurably *beaten* takes the pick back (never pick a fashionable
    rung over ref without a measured win).
    """
    if not times:
        return default
    noise = noise_rel()
    best = min(times, key=times.get)
    pick = best
    if default in times and times[default] <= times[best] * (1.0 + noise):
        pick = default
    if (
        baseline is not None
        and baseline in times
        and baseline != pick
        and times[baseline] <= times[pick] * (1.0 + noise)
    ):
        pick = baseline
    return pick


def tuned_block_config(
    op: str,
    shapes: Sequence[int],
    dtype: Any,
    *,
    default: BlockConfig,
    candidates: Sequence[BlockConfig] = (),
    bench: Optional[Callable[[BlockConfig], Callable[[], Any]]] = None,
    inputs: Sequence[Any] = (),
) -> BlockConfig:
    """Block config for ``op`` at the given shape bucket.

    Measured-first (the default): each candidate is timed once per
    ``(op, backend, device-kind, shape-bucket, dtype)`` key — the analytic
    ``default`` first, displaced only by a candidate that beats it past the
    noise floor — and the winner is cached for the life of the process AND
    persisted to disk (see :func:`autotune_cache_file`), so later processes
    on the same hardware skip the measurement entirely.  With autotune
    opted out (``REPRO_AUTOTUNE=0``) or no ``bench`` factory, the analytic
    ``default`` comes back untouched and uncached.

    ``bench(cfg)`` must return ``(fn, args)`` — ``fn`` jitted and timed on
    the synthetic ``args`` — or a legacy zero-arg callable (which risks
    constant folding; see :func:`_time_once`).  ``inputs`` are the op's own
    arguments: when they are tracers the measurement defers (see
    :func:`_deferred`).
    """
    if autotune_enabled():
        # Hydrate measured winners from previous processes on this hardware
        # before deciding whether to measure.  Gated on the opt-out so
        # analytic runs keep zero disk IO.
        _persist_load()
    key = _bucket_key(op, shapes, dtype)
    cached = _AUTOTUNE_CACHE.get(key)
    if cached is not None:
        _AUTOTUNE_STATS["hits"] += 1
        return cached
    if not (autotune_enabled() and bench is not None and len(candidates) > 1):
        # Analytic model only — deterministic and cheap, so do NOT cache it:
        # a cached default would mask autotune being enabled later in the
        # same process for this shape bucket.
        return default
    _AUTOTUNE_STATS["misses"] += 1
    ordered = [default] + [c for c in candidates if c != default]
    if _deferred(inputs):
        return default
    times = _measure_pass(ordered, bench)
    if not times:
        # Every candidate errored — stay on the analytic default WITHOUT
        # caching it, so a later call still gets its chance to measure.
        return default
    best = _pick(times, default)
    _AUTOTUNE_CACHE[key] = best
    _persist_save()
    return best


def tuned_strategy(
    op: str,
    shapes: Sequence[int],
    dtype: Any,
    *,
    default: str,
    candidates: Sequence[str] = (),
    bench: Optional[Callable[[str], Callable[[], Any]]] = None,
    baseline: Optional[str] = None,
    inputs: Sequence[Any] = (),
) -> str:
    """Strategy (ladder-rung) choice for ``op`` at the given shape bucket.

    The measured-first refinement of the analytic ladder: candidate
    *strategy names* are timed once per ``(op, backend, device-kind,
    shape-bucket, dtype)`` key and the winner cached in-process and on disk
    alongside the block-config winners.  The analytic ``default`` is the
    prior (measured first, displaced only past the noise floor) and
    ``baseline`` — when given and among the candidates — wins back any pick
    without a measured win over it: "within noise of ref" resolves to ref.
    With autotune opted out or no ``bench``, the analytic ``default`` comes
    back untouched and uncached.
    """
    if autotune_enabled():
        _persist_load()
    key = _bucket_key(op, shapes, dtype)
    cached = _STRATEGY_CACHE.get(key)
    if cached is not None and (not candidates or cached in candidates):
        _AUTOTUNE_STATS["hits"] += 1
        return cached
    if not (autotune_enabled() and bench is not None and len(candidates) > 1):
        return default
    _AUTOTUNE_STATS["misses"] += 1
    ordered = [default] + [c for c in candidates if c != default]
    if _deferred(inputs):
        return default
    times = _measure_pass(ordered, bench)
    if not times:
        return default  # all-errored: uncached, retry eagerly later
    best = _pick(times, default, baseline=baseline)
    _STRATEGY_CACHE[key] = best
    _persist_save()
    return best


# ------------------------------------------------------------------ warm-up


@dataclasses.dataclass
class WarmupReport:
    """What one warm-up pass did (and how long it took, off the hot path)."""

    warmed: int = 0                 # plan entries completed
    errors: int = 0                 # entries that raised (never fatal)
    seconds: float = 0.0            # wall clock of the whole pass
    measured: int = 0               # autotune measurements the pass triggered
    labels: Tuple[str, ...] = ()    # completed entry labels, in order

    def merge(self, other: "WarmupReport") -> "WarmupReport":
        return WarmupReport(
            warmed=self.warmed + other.warmed,
            errors=self.errors + other.errors,
            seconds=self.seconds + other.seconds,
            measured=self.measured + other.measured,
            labels=self.labels + other.labels,
        )


def warmup(plan: Iterable) -> WarmupReport:
    """Run a warm-up ``plan`` — pre-measure and pre-compile a declared
    bucket set off the hot path.

    ``plan`` is an iterable of zero-arg callables, or ``(label, callable)``
    pairs.  Each callable should exercise one compiled bucket the caller
    expects to serve (e.g. dispatch one padded batch through its jitted
    entry point): running it triggers any pending autotune measurement for
    the bucket, lowers/compiles the program, and leaves every process-wide
    cache hot.  Exceptions are counted, not raised — a failed warm-up must
    never take down the tier it was warming.
    """
    from ..obs import trace_span

    report = WarmupReport()
    measured_before = _AUTOTUNE_STATS["measured"]
    t0 = time.perf_counter()
    labels = []
    with trace_span("autotune.warmup") as sp:
        for entry in plan:
            label, fn = entry if isinstance(entry, tuple) else (None, entry)
            if label is None:
                label = getattr(fn, "__name__", "warmup")
            try:
                out = fn()
                jax.block_until_ready(out)
                report.warmed += 1
                labels.append(str(label))
            except Exception:
                _log.warning("warm-up entry %r failed", label, exc_info=True)
                _AUTOTUNE_STATS["warmup_errors"] += 1
                report.errors += 1
        sp.set_attr(warmed=report.warmed, errors=report.errors)
    report.seconds = time.perf_counter() - t0
    report.measured = _AUTOTUNE_STATS["measured"] - measured_before
    report.labels = tuple(labels)
    return report
