"""Structured tracing: nested host-side spans over the compiled tiers.

A *span* is one timed host-side operation — a serve dispatch, a recovery
solve, a streaming compaction, an autotune measurement pass — with a start,
a monotonic duration, a parent (spans nest through a ``contextvars`` stack,
so the tree is correct under asyncio interleaving and threads), and a small
attribute dict (``tenant=…, node=…, shard=…, pattern=…``).

Spans wrap compiled-step *invocations* and never run inside them: all of
this is plain host Python, recorded only where the repo already crosses the
host↔device boundary.  Finished spans land in a process-wide fixed-capacity
ring buffer (:class:`TraceBuffer`; ``REPRO_OBS_BUFFER`` rows, default 16384 —
overflow evicts the oldest and is counted, never grows) and export as JSONL
(:func:`export_jsonl`) for offline timeline assembly; each span also feeds
the ``obs_span_us{name=…}`` histogram in the default metrics registry so
``obs-report`` shows latency distributions without replaying the trace.

One clock with the profiler: ``jax.profiler`` stamps its host events with
the wall clock (``CLOCK_REALTIME``, ns since the epoch; an ``.xplane.pb``
stores them relative to its ``profile_start_time``).  A span measures its
duration on the monotonic clock and reports its start (``ts``, seconds) on
that wall clock, through an offset between the two taken once at import, so
a ring row can be put against the device trace.  Host pauses that no
``with`` block can wrap — collections, XLA compiles, persistent-cache loads
— are recorded as spans by :mod:`repro.obs.pauses` through
:func:`record_span` / :func:`defer_span`.

Gating: ``REPRO_OBS=0`` disables span recording (counters stay on — they are
the tiers' stats objects).  ``REPRO_OBS_PROFILER=1`` additionally brackets
every span in a ``jax.profiler.TraceAnnotation`` so spans line up with XLA
activity in a profiler trace viewer.

The clock is a module seam (:func:`set_clock`) mirroring the serving tier's
``VirtualClock`` pattern: the span-tree tests drive a fake clock (whose
readings are reported as they are, with no offset) and assert exact
timestamps — zero sleeps.
"""

from __future__ import annotations

import collections
import contextvars
import itertools
import json
import os
import threading
import time
from typing import Callable, List, Optional

from ..analysis import compiled_path
from .metrics import default_registry, log_bounds

__all__ = [
    "Span",
    "TraceBuffer",
    "configure_buffer",
    "default_buffer",
    "export_jsonl",
    "flush",
    "obs_enabled",
    "profiler_enabled",
    "set_clock",
    "trace_span",
]

OBS_ENV = "REPRO_OBS"                  # opt-out: 0/off disables span recording
BUFFER_ENV = "REPRO_OBS_BUFFER"        # ring capacity (rows)
PROFILER_ENV = "REPRO_OBS_PROFILER"    # opt-IN: jax.profiler annotations

_OFF_VALUES = ("0", "off", "false", "no", "none")
DEFAULT_BUFFER_ROWS = 16384

# Latency spans span ~µs (cache hit) to ~minutes (mesh solve): µs-resolution
# log buckets, one shared shape for every obs_span_us series.
SPAN_BOUNDS = log_bounds(1.0, 1e8, 2.0)


def obs_enabled() -> bool:
    """Span recording on?  Default ON; ``REPRO_OBS=0`` opts out."""
    return os.environ.get(OBS_ENV, "1").strip().lower() not in _OFF_VALUES


def profiler_enabled() -> bool:
    """jax.profiler trace annotations on?  Default OFF (opt-in)."""
    return os.environ.get(PROFILER_ENV, "0").strip().lower() not in _OFF_VALUES


def _buffer_rows() -> int:
    try:
        return max(1, int(os.environ.get(BUFFER_ENV, str(DEFAULT_BUFFER_ROWS))))
    except ValueError:
        return DEFAULT_BUFFER_ROWS


def _wall_offset() -> float:
    """Seconds to add to a ``time.perf_counter`` reading to read the
    profiler's host clock (the wall clock), from one wall reading bracketed
    by two monotonic ones."""
    a = time.perf_counter()
    wall = time.time_ns() * 1e-9
    b = time.perf_counter()
    return wall - 0.5 * (a + b)


# Monotonic clock seam (tests swap in a fake; see module docstring), and the
# offset that puts its readings on the profiler's clock.
_DEFAULT_CLOCK: Callable[[], float] = time.perf_counter
_WALL_OFFSET = _wall_offset()
_clock: Callable[[], float] = _DEFAULT_CLOCK
_offset = _WALL_OFFSET


def set_clock(clock: Callable[[], float]) -> Callable[[], float]:
    """Swap the span clock; returns the previous one (restore in teardown).
    Any clock but the default reports its readings as they are."""
    global _clock, _offset
    prev, _clock = _clock, clock
    _offset = _WALL_OFFSET if clock is _DEFAULT_CLOCK else 0.0
    return prev


_span_ids = itertools.count(1)
_current: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "repro_obs_current_span", default=None
)


class Span:
    """One in-flight (then finished) span.  Created by :func:`trace_span`."""

    __slots__ = (
        "name", "span_id", "parent_id", "t_start", "t_end", "attrs",
        "_token", "_annotation",
    )

    def __init__(self, name: str, parent_id: Optional[int], attrs: dict):
        self.name = name
        self.span_id = next(_span_ids)
        self.parent_id = parent_id
        self.t_start = _clock()
        self.t_end: Optional[float] = None
        self.attrs = attrs
        self._token = None
        self._annotation = None

    def set_attr(self, **kw) -> "Span":
        """Attach attributes discovered mid-span (e.g. rows dispatched)."""
        self.attrs.update(kw)
        return self

    @property
    def duration_us(self) -> float:
        end = self.t_end if self.t_end is not None else _clock()
        return (end - self.t_start) * 1e6

    def as_dict(self) -> dict:
        return _row(self.name, self.span_id, self.parent_id, self.t_start,
                    self.duration_us, self.attrs)


def _row(name, span_id, parent_id, t_start, dur_us, attrs) -> dict:
    """One ring row; ``ts`` is the start on the profiler's clock."""
    return {
        "name": name,
        "span": span_id,
        "parent": parent_id,
        "ts": t_start + _offset,
        "dur_us": dur_us,
        "attrs": attrs,
    }


class _NullSpan:
    """The shared do-nothing span handed out when ``REPRO_OBS=0``."""

    __slots__ = ()
    name = ""
    span_id = 0
    parent_id = None
    attrs: dict = {}
    duration_us = 0.0

    def set_attr(self, **kw) -> "_NullSpan":
        return self


_NULL_SPAN = _NullSpan()


class TraceBuffer:
    """Fixed-capacity ring of finished spans + a serialized JSONL exporter."""

    def __init__(self, capacity: Optional[int] = None):
        self.capacity = _buffer_rows() if capacity is None else max(1, int(capacity))
        self._rows: List[dict] = []
        self._next = 0
        self.recorded = 0
        self.dropped = 0       # evicted by overflow (ring semantics)
        self.exported = 0
        self._lock = threading.Lock()

    def record(self, row: dict) -> None:
        with self._lock:
            self.recorded += 1
            if len(self._rows) < self.capacity:
                self._rows.append(row)
            else:
                self._rows[self._next] = row
                self._next = (self._next + 1) % self.capacity
                self.dropped += 1

    def rows(self) -> List[dict]:
        """Buffered spans, oldest first."""
        with self._lock:
            return self._rows[self._next:] + self._rows[: self._next]

    def clear(self) -> None:
        with self._lock:
            self._rows = []
            self._next = 0

    @property
    def stats(self) -> dict:
        with self._lock:
            return {
                "capacity": self.capacity,
                "buffered": len(self._rows),
                "recorded": self.recorded,
                "dropped": self.dropped,
                "exported": self.exported,
            }

    def export_jsonl(self, path: str, *, clear: bool = False) -> int:
        """Append the buffered spans to ``path`` as JSONL; returns the row
        count.  The whole buffer goes out in ONE ``write`` of pre-joined
        lines under the buffer lock, so concurrent exporters (and recorders)
        interleave at line granularity — every line in the file is valid
        JSON no matter how many threads export at once."""
        with self._lock:
            rows = self._rows[self._next:] + self._rows[: self._next]
            payload = "".join(json.dumps(r, sort_keys=True) + "\n" for r in rows)
            if clear:
                self._rows = []
                self._next = 0
            self.exported += len(rows)
            with open(path, "a", encoding="utf-8") as f:
                f.write(payload)
        return len(rows)


_BUFFER = TraceBuffer()

# ``obs_span_us{name=…}`` handles by span name, for the registry they were
# resolved in (tests swap the registry): a span exit is then a dict hit, not
# a registry lookup (a label sort and a lock).
_hists: dict = {}
_hists_registry = None


def _span_hist(name: str):
    global _hists_registry
    reg = default_registry()
    if reg is not _hists_registry:
        _hists.clear()
        _hists_registry = reg
    h = _hists.get(name)
    if h is None:
        h = _hists[name] = reg.histogram(
            "obs_span_us", labels={"name": name}, bounds=SPAN_BOUNDS,
            help="span durations by name (µs)",
        )
    return h


def _finish(row: dict) -> None:
    _BUFFER.record(row)
    _span_hist(row["name"]).observe(row["dur_us"])


def record_span(
    name: str,
    t_start: float,
    t_end: float,
    attrs: Optional[dict] = None,
    *,
    parent_id: Optional[int] = None,
    span_id: Optional[int] = None,
) -> None:
    """Record a span timed elsewhere — a host pause a hook saw after it
    happened.  ``t_start``/``t_end`` are readings of the span clock
    (``_clock``)."""
    _finish(_row(
        name, next(_span_ids) if span_id is None else span_id, parent_id,
        t_start, (t_end - t_start) * 1e6, attrs if attrs is not None else {},
    ))


# Spans seen where no lock may be taken: a collector callback can run while
# this very thread holds the ring's or a histogram's lock.  Queued as
# ``record_span`` arguments; every span exit records them (:func:`flush`).
# Bounded like the ring: a long stretch with no span exit keeps the newest.
_deferred: collections.deque = collections.deque(maxlen=DEFAULT_BUFFER_ROWS)


def defer_span(name: str, t_start: float, t_end: float, attrs: dict,
               parent_id: Optional[int] = None) -> None:
    """Queue a finished span for :func:`flush`; takes no lock."""
    _deferred.append((name, t_start, t_end, attrs, parent_id))


def flush() -> None:
    """Record every span queued by :func:`defer_span`."""
    while _deferred:
        try:
            name, t0, t1, attrs, parent = _deferred.popleft()
        except IndexError:  # another thread took the last one
            return
        record_span(name, t0, t1, attrs, parent_id=parent)


def default_buffer() -> TraceBuffer:
    """The process-wide span ring ``trace_span`` records into."""
    return _BUFFER


def configure_buffer(capacity: Optional[int] = None) -> TraceBuffer:
    """Replace the process-wide buffer (fresh ring, e.g. per report run or
    per test); returns the new buffer."""
    global _BUFFER
    _BUFFER = TraceBuffer(capacity)
    return _BUFFER


@compiled_path("obs.export", kind="host")
def export_jsonl(path: str, *, clear: bool = False) -> int:
    """Export the default buffer (see :meth:`TraceBuffer.export_jsonl`)."""
    return _BUFFER.export_jsonl(path, clear=clear)


def _profiler_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` — or None if jax/profiler is
    unavailable (obs must never be the reason a host tool can't import)."""
    try:
        import jax.profiler  # deferred: obs itself never requires jax

        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return None


class trace_span:
    """``with trace_span("serve.dispatch", tenant=t) as sp:`` — one span.

    Class-based (not ``@contextmanager``) to keep the disabled path at two
    attribute checks and zero generator frames: the serving hot path enters
    one of these per dispatch.
    """

    __slots__ = ("_name", "_attrs", "_span")

    def __init__(self, name: str, **attrs):
        self._name = name
        self._attrs = attrs
        self._span: object = _NULL_SPAN

    def __enter__(self):
        if not obs_enabled():
            return _NULL_SPAN
        parent = _current.get()
        span = Span(
            self._name,
            parent.span_id if parent is not None else None,
            self._attrs,
        )
        span._token = _current.set(span)
        if profiler_enabled():
            ann = _profiler_annotation(self._name)
            if ann is not None:
                ann.__enter__()
                span._annotation = ann
        self._span = span
        return span

    def __exit__(self, exc_type, exc, tb):
        span = self._span
        if span is _NULL_SPAN:
            return False
        if span._annotation is not None:
            span._annotation.__exit__(exc_type, exc, tb)
        _current.reset(span._token)
        span.t_end = _clock()
        if exc_type is not None:
            span.attrs.setdefault("error", exc_type.__name__)
        # Queued spans ended before this one: record them first, so that a
        # burst of them cannot push this span out of a small ring.
        if _deferred:
            flush()
        _finish(span.as_dict())
        return False
