"""The readers of the program's own spans and counters (``bench/program.py``
and the metrics that use it): on a fresh registry and span ring filled by
hand, where what they read is there and where it is not (as on a program
that lacks it), and on what a tiny window of each cell leaves."""

import numpy as np
import pytest

ALG1 = "alg1.sift128_k512.deadline"
SERVE = "serve.sift128_k512.zipf4"
ALG1_METRICS = ("pack_ms.alg1", "recovery_ms.alg1", "host_pause_ms.alg1")
SERVE_METRICS = ("fetch_ms.serve", "query_p95_ms.serve")


@pytest.fixture
def fresh_program(monkeypatch):
    """An empty registry and ring and a fake span clock, restored after."""
    from repro.obs import MetricsRegistry, set_default_registry
    from repro.obs import trace as trace_mod

    monkeypatch.delenv("REPRO_OBS", raising=False)
    trace_mod.flush()
    prev_reg = set_default_registry(MetricsRegistry())
    prev_buf = trace_mod._BUFFER
    trace_mod.configure_buffer(256)
    prev_clock = trace_mod.set_clock(lambda: 0.0)
    yield trace_mod
    trace_mod.set_clock(prev_clock)
    trace_mod._BUFFER = prev_buf
    set_default_registry(prev_reg)


def _read(metric, ctx):
    import harness

    return harness.reader_of(metric).read(ctx)


def _solve(tm, t, *, lp=0.0, pack=0.2, pause=None):
    """One solve's spans from ``t`` (s); returns its end."""
    if lp:
        tm.record_span("session.recovery_solve", t, t + lp)
        t += lp
    tm.record_span("session.pack", t, t + pack, {"hit": False})
    t += pack
    if pause:
        tm.record_span(pause[0], t, t + pause[1])
    tm.record_span("kmedian.local", t, t + 1.0)
    tm.record_span("kmedian.cost", t + 1.0, t + 1.1)
    return t + 1.1


def test_alg1_readers_cut_the_ring_to_the_window(fresh_program):
    tm = fresh_program
    t = _solve(tm, 0.0, lp=0.5, pause=("jax.compile", 3.0))  # set-up's warm-up solve
    t0 = t + 1.0
    t = _solve(tm, t0, lp=0.3, pause=("process.gc", 0.01))
    t = _solve(tm, t, pause=("jax.cache_load", 0.05))
    ctx = {"counters": {"solves": 2, "solve_s": (t - t0) / 2}}
    assert _read("pack_ms.alg1", ctx) == pytest.approx(200.0)
    assert _read("recovery_ms.alg1", ctx) == pytest.approx(150.0)
    assert _read("host_pause_ms.alg1", ctx) == pytest.approx(10.0)


def test_alg1_readers_without_the_programs_spans(fresh_program, monkeypatch):
    import repro.obs

    tm = fresh_program
    ctx = {"counters": {"solves": 2, "solve_s": 1.0}}
    for metric in ALG1_METRICS:
        assert _read(metric, ctx) is None  # nothing recorded (REPRO_OBS=0)
    tm.record_span("session.recovery_solve", 0.0, 0.4)
    tm.record_span("kmedian.cost", 0.5, 1.0)
    assert _read("pack_ms.alg1", ctx) is None
    assert _read("recovery_ms.alg1", ctx) == pytest.approx(200.0)
    assert _read("host_pause_ms.alg1", ctx) == 0.0
    monkeypatch.delattr(repro.obs, "install_pause_hooks")
    assert _read("host_pause_ms.alg1", ctx) is None  # a program without pause spans
    assert _read("pack_ms.alg1", {"counters": {"solves": 0, "solve_s": 0.0}}) is None


def test_alg1_window_beyond_an_overflowed_ring(fresh_program):
    tm = fresh_program
    tm.configure_buffer(4)
    t = 0.0
    for _ in range(3):
        t = _solve(tm, t)
    ctx = {"counters": {"solves": 3, "solve_s": 1.3}}
    assert _read("pack_ms.alg1", ctx) is None


def test_serve_readers(fresh_program):
    tm = fresh_program
    ctx = {"counters": {}}
    for metric in SERVE_METRICS:
        assert _read(metric, ctx) is None
    for i in range(4):
        tm.record_span("serve.fetch", 0.0, 0.0005 * (i + 1))
    assert _read("fetch_ms.serve", ctx) == pytest.approx(1.25)
    ctx["counters"]["stretch_latency_ms"] = np.arange(1.0, 201.0)
    assert _read("query_p95_ms.serve", ctx) == pytest.approx(190.05)
    ctx["counters"]["stretch_latency_ms"] = np.zeros(0)
    assert _read("query_p95_ms.serve", ctx) is None


@pytest.mark.parametrize("cell,metrics", [(ALG1, ALG1_METRICS), (SERVE, SERVE_METRICS)])
def test_tiny_window_reads_every_new_metric(cell, metrics):
    import harness
    from conftest import tiny_cell

    c = tiny_cell(cell)
    drv = harness.driver_of(c.traffic)
    st = drv.setup(c, 1.0, log=lambda s: None)
    win = drv.window(st, 1.0, harness.Tracer(False))
    ctx = {"cell": c, "counters": win.counters}
    for metric in metrics:
        value = _read(metric, ctx)
        assert value is not None and value >= 0.0, metric
    if cell == ALG1:
        assert _read("pack_ms.alg1", ctx) > 0.0
    else:
        assert _read("fetch_ms.serve", ctx) > 0.0
        assert _read("query_p95_ms.serve", ctx) > 0.0
