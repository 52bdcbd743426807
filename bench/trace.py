"""Reduce a profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so that the reduction can be tested on a small
recorded trace (``tests/bench/fixtures``):

* :func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
  (``jax.profiler.ProfileData``) into plain lists: each device's operations,
  and each host thread's events, as ``[name, start_ns, duration_ns]``.
* :func:`reduce` turns those lists into device busy time (the union of the
  intervals in which an operation ran, averaged over the chips used), the
  time of each operation by the name the trace gives it, the operations that
  took most time, and the idle gaps, each gap put down to the host event
  that covers most of it: a ``repro.obs`` span (``layer.op``) where one
  does, else the innermost host event, else ``none``.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

# Device planes, and the line on each that holds one event per operation.
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
# A repro.obs span name: dotted lower-case words ("serve.dispatch").
_SPAN = re.compile(r"^[a-z_][a-z0-9_]*(\.[a-z_][a-z0-9_]*)+$")
TOP = 10


def extract(path: str, *, chips: int) -> dict:
    """The device operations of the first ``chips`` TPUs and the host
    threads' events, as plain lists."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if dev >= chips:
                continue
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    devices[dev] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events
                    ]
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                    if e.duration_ns > 0 and not e.name.startswith("$")
                ]
                if evs:
                    host.append({"thread": line.name, "events": evs})
    return {"devices": [devices[k] for k in sorted(devices)], "host": host}


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


# Gaps shorter than this sit between two operations of one launch stream and
# are counted as such, without a search of the host events.
SHORT_GAP_NS = 20_000.0


def _cause(gap_s: float, gap_e: float, host: "_HostIndex") -> str:
    """The host event covering most of [gap_s, gap_e): a repro.obs span
    first, then the innermost (shortest) event."""
    if gap_e - gap_s < SHORT_GAP_NS:
        return "between_ops"
    best, best_key = "none", None
    for name, s, d in host.overlapping(gap_s, gap_e):
        overlap = min(gap_e, s + d) - max(gap_s, s)
        key = (bool(_SPAN.match(name)), overlap, -d)
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


class _HostIndex:
    """Host events sorted by start, for the events that overlap a gap."""

    def __init__(self, events: list):
        self.events = sorted(events, key=lambda e: e[1])
        self.starts = [e[1] for e in self.events]
        self.longest = max((e[2] for e in self.events), default=0.0)

    def overlapping(self, s: float, e: float):
        hi = bisect.bisect_left(self.starts, e)
        lo = bisect.bisect_left(self.starts, s - self.longest)
        for ev in self.events[lo:hi]:
            if ev[1] + ev[2] > s:
                yield ev


_INSTR = re.compile(r"^%?([^\s=]+)\s*=")


def op_name(name: str) -> str:
    """The HLO instruction name of a trace event ("%fusion.57 = f32[..."
    -> "fusion.57")."""
    m = _INSTR.match(name)
    return m.group(1) if m else name


def leaves(ops: list) -> list:
    """The operations that hold no other: a ``while`` or ``call`` event on
    the ops line spans the operations of its body."""
    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, s, d) in enumerate(ops):
        nxt = ops[i + 1][1] if i + 1 < len(ops) else float("inf")
        if nxt >= s + d:
            out.append([name, s, d])
    return out


def reduce(ex: dict, *, window_ns: tuple = None) -> dict:
    """Busy and idle time, per-operation time and attributed gaps.

    Operation times are of the innermost operations, keyed by the full event
    name (which carries the operand shapes); the breakdown names them by
    their HLO instruction.
    ``window_ns`` is the traced stretch as (start, end) on the trace's
    clock; by default it runs from the first to the last event, device or
    host.  Times are seconds, averaged over the devices.
    """
    devices = [d for d in ex["devices"] if d]
    if not devices:
        raise ValueError("the trace holds no device operation")
    host_events = [e for t in ex["host"] for e in t["events"]]
    if window_ns is None:
        every = [e for d in devices for e in d] + host_events
        w0 = min(e[1] for e in every)
        w1 = max(e[1] + e[2] for e in every)
    else:
        w0, w1 = window_ns
    index = _HostIndex(host_events)
    n = len(devices)
    busy = 0.0
    op_time: dict = collections.Counter()
    op_count: dict = collections.Counter()
    gaps: dict = collections.Counter()
    gap_count = 0
    for ops in devices:
        spans = _union([[max(s, w0), min(s + d, w1)] for _, s, d in ops if s + d > w0 and s < w1])
        busy += sum(e - s for s, e in spans)
        for name, s, d in leaves(ops):
            if s + d > w0 and s < w1:
                op_time[name] += d
                op_count[name] += 1
        edges = [w0] + [x for sp in spans for x in sp] + [w1]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                gaps[_cause(gs, ge, index)] += ge - gs
                gap_count += 1
    window = (w1 - w0) * 1e-9
    busy_s = busy * 1e-9 / n
    return {
        "busy_s": busy_s,
        "window_s": window,
        "idle_share": 1.0 - busy_s / window if window > 0 else 0.0,
        "op_seconds": {k: v * 1e-9 / n for k, v in op_time.items()},
        "op_calls": {k: v / n for k, v in op_count.items()},
        "device_ops": [[op_name(k), v * 1e-9 / n] for k, v in op_time.most_common(TOP)],
        "idle_gaps": [[k, v * 1e-9 / n] for k, v in gaps.most_common(TOP)],
        "gap_count": gap_count,
        "devices": n,
    }


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def reduce_dir(log_dir: str, *, chips: int) -> dict:
    return reduce(extract(find_xplane(log_dir), chips=chips))
