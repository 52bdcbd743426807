"""What the program recorded about itself, for the per-layer readers that
read its own spans and counters (``repro.obs``) once a traced run's window
has closed.

The readers are handed no reading of the program's instruments taken at the
window's start, so each reads what covers the window without one: the
registry's totals over the whole run, where set-up adds next to nothing, or
the rows the span ring still holds, cut to the window by the program's own
spans.  Against a program without the spans or counters a reader reads,
every function here returns ``None``.
"""

from __future__ import annotations

from typing import Optional

# The spans of a host pause that no other pause contains: a persistent-cache
# load (``jax.cache_load``) runs inside a ``jax.compile`` span.
PAUSES = ("process.gc", "jax.compile")


def obs():
    """The program's ``repro.obs``, with the spans its hooks queued recorded."""
    import repro.obs as o

    flush = getattr(o, "flush", None)
    if flush is not None:
        flush()
    return o


def span_totals(name: str) -> Optional[tuple]:
    """(count, seconds) of the span ``name`` over the whole run."""
    for labels, snap in obs().default_registry().collect().get("obs_span_us", {}).items():
        if dict(labels).get("name") == name and snap.count:
            return snap.count, snap.total * 1e-6
    return None


def has_pause_spans() -> bool:
    return hasattr(obs(), "install_pause_hooks")


def alg1_window(ctx) -> Optional[list]:
    """The ring's rows inside the Algorithm 1 window.

    The window ends where its last solve ends, at the end of the last
    ``kmedian.cost`` span, and is ``solves × solve_s`` long (how
    ``drivers/alg1.py`` defines ``solve_s``).  ``None`` where the program records no
    ``kmedian.cost`` span, or where the ring no longer reaches back to the
    window's start.  So the readers built on it need ``kmedian.cost`` to stay
    the last span of a solve, and a ring deep enough for the window.

    Drop this function, and read the window's spans from that reading
    instead, once the harness hands the readers a reading of the program's
    registry taken around ``drv.window`` (``ctx["program"]``)."""
    c = ctx["counters"]
    if not c.get("solves"):
        return None
    buf = obs().default_buffer()
    rows = buf.rows()
    ends = [r["ts"] + r["dur_us"] * 1e-6 for r in rows if r["name"] == "kmedian.cost"]
    if not ends:
        return None
    end = max(ends)
    start = end - c["solves"] * c["solve_s"]
    if buf.stats["dropped"] and min(r["ts"] for r in rows) > start:
        return None
    return [r for r in rows if start <= r["ts"] <= end]


def seconds(rows: list, names) -> float:
    return sum(r["dur_us"] for r in rows if r["name"] in names) * 1e-6
