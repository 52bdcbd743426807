"""Host pauses as spans: collections, XLA compiles, persistent-cache loads.

A stall on the host that no ``with`` block wraps still has to be put down
to something.  :func:`install_pause_hooks` (run once per process by the
first ``ResilienceSession`` or ``ServingFrontend``; idempotent) records:

* ``process.gc`` — one span per garbage collection (``generation`` attr),
  from a ``gc.callbacks`` hook.  The callback may run while this thread
  holds the ring's or a histogram's lock, so it only queues the span
  (:func:`repro.obs.trace.defer_span`); the next span exit records it.
* ``jax.compile`` — one span per ``/jax/core/compile/backend_compile_duration``
  event (``fun_name`` attr), from the event's own start and end, which JAX
  takes on the wall clock and this module moves onto the span clock.
* ``jax.cache_load`` — one span per persistent-cache retrieval
  (``/jax/compilation_cache/cache_retrieval_time_sec``), ending when JAX
  reports it.  The retrieval runs inside a backend compile, so it is the
  child of that ``jax.compile`` span, not a pause of its own.

With ``REPRO_OBS_PROFILER=1`` a collection and a compile are also bracketed
by a ``jax.profiler.TraceAnnotation`` while they run (the compile from JAX's
start-of-compile scalar event), so a traced run puts a stall down to them by
name.  Like the rest of :mod:`repro.obs`, nothing here imports JAX at module
scope; ``REPRO_OBS=0`` turns every hook into a no-op at fire time.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Optional

from . import trace as _trace

__all__ = ["install_pause_hooks"]

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

_install_lock = threading.Lock()
_installed = False


def _parent_id() -> Optional[int]:
    span = _trace._current.get()
    return span.span_id if span is not None else None


# ------------------------------------------------------------- collections

# At most one collection runs at a time in a process, so one slot holds the
# running one: (start on the span clock, parent id, profiler annotation).
_gc_running: Optional[tuple] = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_running
    if phase == "start":
        if not _trace.obs_enabled():
            _gc_running = None
            return
        ann = _trace._profiler_annotation("process.gc") if _trace.profiler_enabled() else None
        if ann is not None:
            ann.__enter__()
        _gc_running = (_trace._clock(), _parent_id(), ann)
        return
    running, _gc_running = _gc_running, None
    if running is None:
        return
    t_start, parent, ann = running
    if ann is not None:
        ann.__exit__(None, None, None)
    _trace.defer_span(
        "process.gc", t_start, _trace._clock(),
        {"generation": info.get("generation")}, parent,
    )


# ---------------------------------------------------------------- compiles

# Per thread, the compiles that have started and not ended, innermost last:
# (span id, parent id, profiler annotation).
_compiles = threading.local()


def _open_compiles() -> list:
    stack = getattr(_compiles, "stack", None)
    if stack is None:
        stack = _compiles.stack = []
    return stack


def _on_compile_start(event: str, value, **kwargs) -> None:
    if event != COMPILE_EVENT or not _trace.obs_enabled():
        return
    ann = None
    if _trace.profiler_enabled():
        ann = _trace._profiler_annotation("jax.compile")
        if ann is not None:
            ann.__enter__()
    _open_compiles().append((next(_trace._span_ids), _parent_id(), ann))


def _wall_to_span_clock(t_wall: float) -> float:
    """A ``time.time()`` reading moved onto the span clock."""
    return _trace._clock() - (time.time() - t_wall)


def _on_compile_span(event: str, start_time: float, end_time: float, **kwargs) -> None:
    if event != COMPILE_EVENT:
        return
    stack = _open_compiles()
    span_id, parent, ann = stack.pop() if stack else (None, _parent_id(), None)
    if ann is not None:
        ann.__exit__(None, None, None)
    if not _trace.obs_enabled():
        return
    t_start = _wall_to_span_clock(start_time)
    _trace.record_span(
        "jax.compile", t_start, t_start + max(0.0, end_time - start_time),
        {"fun_name": str(kwargs.get("fun_name", ""))},
        parent_id=parent, span_id=span_id,
    )


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event != CACHE_LOAD_EVENT or not _trace.obs_enabled():
        return
    stack = _open_compiles()
    parent = stack[-1][0] if stack else _parent_id()
    t_end = _trace._clock()
    _trace.record_span("jax.cache_load", t_end - duration_secs, t_end, {}, parent_id=parent)


def install_pause_hooks() -> None:
    """Install the collection and JAX monitoring hooks, once per process."""
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring as monitoring  # deferred: obs itself never requires jax

        gc.callbacks.append(_on_gc)
        monitoring.register_scalar_listener(_on_compile_start)
        monitoring.register_event_time_span_listener(_on_compile_span)
        monitoring.register_event_duration_secs_listener(_on_duration)
        _installed = True
