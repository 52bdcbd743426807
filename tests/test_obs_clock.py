"""Span starts and the profiler's host events on one clock.

The profiler is started inside the test (never at import), on the CPU, with
``REPRO_OBS_PROFILER=1`` so the span is also written into the trace as a
``TraceAnnotation``; the ring row's start must land within 100 µs of that
annotation's start in the ``.xplane.pb``.
"""

import glob
import os
import time

from repro.obs import trace_span
from repro.obs import trace as trace_mod


def _profile_start_ns(pd) -> int:
    for plane in pd.planes:
        for name, value in plane.stats:
            if name == "profile_start_time":
                return int(value)
    raise AssertionError("the trace records no profile_start_time")


def test_ring_row_start_lines_up_with_the_profiler_trace(tmp_path, monkeypatch):
    import jax
    from jax.profiler import ProfileData

    monkeypatch.delenv("REPRO_OBS", raising=False)
    monkeypatch.setenv("REPRO_OBS_PROFILER", "1")
    trace_mod.flush()  # pause spans queued by earlier tests go to the old ring
    prev_buf = trace_mod._BUFFER
    buf = trace_mod.configure_buffer(64)
    prev_clock = trace_mod.set_clock(trace_mod._DEFAULT_CLOCK)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with trace_span("obs.clock_probe"):
                time.sleep(0.002)
        finally:
            jax.profiler.stop_trace()
        (row,) = [r for r in buf.rows() if r["name"] == "obs.clock_probe"]
    finally:
        trace_mod.set_clock(prev_clock)
        trace_mod._BUFFER = prev_buf
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    t0 = _profile_start_ns(pd)
    starts = [
        (t0 + e.start_ns) * 1e-9
        for plane in pd.planes if plane.name.startswith("/host:CPU")
        for line in plane.lines for e in line.events if e.name == "obs.clock_probe"
    ]
    assert len(starts) == 1
    assert abs(starts[0] - row["ts"]) < 100e-6
