"""Deterministic concurrency suite for the serving frontend.

Every scenario drives the sans-io :class:`ServingFrontend` with a
:class:`VirtualClock` — time moves only when a test calls ``advance`` — so
"concurrency" is a replayable sequence of submit/advance/flush calls with
zero wall-clock sleeps and zero timing dependence.  The asyncio shell is
exercised once at the end with a zero-length window (timers fire on the
next loop tick, still no sleeping).
"""

import asyncio

import numpy as np
import pytest

from repro.serve import (
    AdmissionError,
    AsyncFrontend,
    ServingFrontend,
    VirtualClock,
)
from repro.stream import StreamingSession

D, K = 3, 3
WINDOW = 0.002


def make_session(d=D, seed=0, rounds=2, n=160):
    rng = np.random.default_rng(seed)
    s = StreamingSession(d=d, k=K, num_nodes=4, leaf_size=64, seed=seed)
    for _ in range(rounds):
        s.ingest(rng.normal(size=(n, d)).astype(np.float32))
    s.solve()
    return s


def make_frontend(session, *, max_batch=64, cache_size=128, **kw):
    clk = VirtualClock()
    fe = ServingFrontend(
        window=WINDOW, max_batch=max_batch, cache_size=cache_size, clock=clk, **kw
    )
    fe.add_tenant("a", session)
    return fe, clk


# ------------------------------------------------------------ batch window


def test_batch_window_close_collects_concurrent_submits():
    fe, clk = make_frontend(make_session())
    rng = np.random.default_rng(1)
    tickets = [fe.submit("a", rng.normal(size=(2, D))) for _ in range(5)]
    assert all(not t.done for t in tickets)
    # The window has not elapsed: flushing now dispatches nothing.
    assert fe.flush() == 0
    assert all(not t.done for t in tickets)
    clk.advance(WINDOW / 2)
    assert fe.flush() == 0
    # Window elapses → ONE compiled dispatch answers all five submits.
    clk.advance(WINDOW / 2)
    assert fe.flush() == 1
    assert all(t.done and t.state == "done" for t in tickets)
    assert fe.dispatches == 1
    assert fe.served == 10
    for t in tickets:
        assert t.result.indices.shape == (2,)
        assert t.result.indices.dtype == np.int32


def test_window_anchors_at_first_submit_not_last():
    fe, clk = make_frontend(make_session())
    rng = np.random.default_rng(2)
    t1 = fe.submit("a", rng.normal(size=(1, D)))
    clk.advance(WINDOW * 0.9)
    t2 = fe.submit("a", rng.normal(size=(1, D)))  # joins the open bucket
    clk.advance(WINDOW * 0.1)
    # Deadline is first-submit + window: both go out now, t2 waited only 10%.
    assert fe.flush() == 1
    assert t1.done and t2.done


def test_max_batch_closes_bucket_without_waiting_out_the_window():
    fe, clk = make_frontend(make_session(), max_batch=8)
    rng = np.random.default_rng(3)
    tickets = [fe.submit("a", rng.normal(size=(1, D))) for _ in range(8)]
    # Bucket filled → closed at submit time; flush needs no clock advance.
    assert fe.flush() == 1
    assert all(t.done for t in tickets)
    assert fe.batcher.size_closes == 1 and fe.batcher.window_closes == 0


def test_due_reports_next_deadline_for_the_scheduler_shell():
    fe, clk = make_frontend(make_session())
    assert fe.due() is None
    fe.submit("a", np.zeros((1, D), np.float32))
    assert fe.due() == pytest.approx(clk.now() + WINDOW)
    clk.advance(2 * WINDOW)  # overdue → due is "now"
    assert fe.due() == pytest.approx(clk.now())


# ----------------------------------------------------- shape-bucket isolation


def test_shape_buckets_isolate_tenants_and_dims():
    sa, sb = make_session(seed=0), make_session(d=5, seed=1)
    clk = VirtualClock()
    fe = ServingFrontend(window=WINDOW, max_batch=64, cache_size=64, clock=clk)
    fe.add_tenant("a", sa)
    fe.add_tenant("b", sb)
    rng = np.random.default_rng(4)
    qa = rng.normal(size=(3, D)).astype(np.float32)
    qb = rng.normal(size=(2, 5)).astype(np.float32)
    ta = fe.submit("a", qa)
    tb = fe.submit("b", qb)
    clk.advance(WINDOW)
    # Two buckets → two dispatches, answered by each tenant's own model.
    assert fe.flush() == 2
    assert fe.dispatches == 2
    # Cross-check against the tenants' own synchronous query paths.
    ra, rb = sa.query(qa), sb.query(qb)
    np.testing.assert_array_equal(ta.result.indices, ra.indices)
    np.testing.assert_array_equal(tb.result.indices, rb.indices)
    assert ta.result.version == sa.version
    assert tb.result.version == sb.version


def test_same_tenant_single_bucket_mixed_row_counts():
    fe, clk = make_frontend(make_session())
    rng = np.random.default_rng(5)
    sizes = [1, 4, 2, 7]
    tickets = [fe.submit("a", rng.normal(size=(m, D))) for m in sizes]
    clk.advance(WINDOW)
    assert fe.flush() == 1  # one (tenant, d) bucket despite ragged rows
    for t, m in zip(tickets, sizes):
        assert t.result.indices.shape == (m,)
    assert 0.0 < fe.occupancy <= 1.0


# --------------------------------------------------------- admission control


def test_admission_rejects_at_submit_when_bound_already_violated():
    sess = make_session()
    fe, clk = make_frontend(sess)
    rng = np.random.default_rng(6)
    sess.ingest(rng.normal(size=(50, D)))  # staleness: 50 points, 1 ingest
    with pytest.raises(AdmissionError) as ei:
        fe.submit("a", rng.normal(size=(1, D)), max_staleness_points=49)
    assert ei.value.tenant == "a"
    assert ei.value.staleness["points"] == 50
    assert fe.rejected == 1
    # The same query without a bound (or with a satisfiable one) is admitted.
    t = fe.submit("a", rng.normal(size=(1, D)), max_staleness_points=50)
    assert not t.done


def test_admission_rechecked_at_dispatch_after_concurrent_ingest():
    sess = make_session()
    fe, clk = make_frontend(sess)
    rng = np.random.default_rng(7)
    # Admitted: staleness is 0 at submit time.
    t_bounded = fe.submit("a", rng.normal(size=(2, D)), max_staleness_points=10)
    t_free = fe.submit("a", rng.normal(size=(2, D)))
    # Ingest lands while the tickets wait out the batch window.
    sess.ingest(rng.normal(size=(50, D)))
    clk.advance(WINDOW)
    assert fe.flush() == 1
    # The bounded ticket is rejected by the dispatch-time re-check; the
    # unbounded one is answered (with the honest staleness bound attached).
    assert t_bounded.state == "rejected"
    assert "bound" in t_bounded.error
    assert t_free.state == "done"
    assert t_free.result.staleness_points == 50
    assert fe.rejected == 1


def test_rejected_ticket_wakes_async_waiter_with_admission_error():
    sess = make_session()
    fe, clk = make_frontend(sess)
    rng = np.random.default_rng(8)
    t = fe.submit("a", rng.normal(size=(1, D)), max_staleness_ingests=0)
    woken = []
    t.waiter = lambda tk: woken.append(tk.state)
    sess.ingest(rng.normal(size=(20, D)))
    clk.advance(WINDOW)
    fe.flush()
    assert woken == ["rejected"]


# ------------------------------------------------- elastic patch in flight


def test_in_flight_queries_survive_an_elastic_patch():
    rng = np.random.default_rng(9)
    sess = make_session(rounds=3)
    fe, clk = make_frontend(sess)
    t = fe.submit("a", rng.normal(size=(4, D)))
    # A persistent straggler (node 0 dead every round) trips the session's
    # ElasticPolicy(patience=2) while the ticket is waiting out its window.
    alive = np.array([False, True, True, True])
    for _ in range(4):
        sess.ingest(rng.normal(size=(40, D)).astype(np.float32), alive=alive)
    assert fe.tenant("a").elastic_patches >= 1
    clk.advance(WINDOW)
    assert fe.flush() == 1
    # The in-flight ticket completed against the live model, with the
    # staleness of the ingests that landed mid-flight reported honestly.
    assert t.state == "done"
    assert t.result.staleness_points == 160
    assert t.result.staleness_ingests == 4
    np.testing.assert_array_equal(
        t.result.indices, sess.query(t.queries).indices
    )


# ------------------------------------------------------------ replayability


def _scripted_run(seed):
    """One fixed submit/advance/flush script; returns its observable trace."""
    rng = np.random.default_rng(seed)
    fe, clk = make_frontend(make_session(seed=seed), max_batch=8)
    trace = []
    tickets = []
    for step in range(12):
        tickets.append(fe.submit("a", rng.normal(size=(1 + step % 3, D))))
        if step % 3 == 2:
            clk.advance(WINDOW)
            trace.append(("flush", fe.flush()))
    clk.advance(WINDOW)
    trace.append(("final", fe.flush()))
    for t in tickets:
        trace.append((t.rows, t.result.indices.tolist(), t.result.version))
    trace.append(("stats", fe.dispatches, fe.served, fe.batcher.batches_closed))
    return trace


def test_scripted_run_is_replayable():
    assert _scripted_run(11) == _scripted_run(11)


# ------------------------------------------------------------- async shell


def test_async_frontend_gathers_concurrent_queries_without_sleeping():
    sess = make_session()
    rng = np.random.default_rng(12)

    async def main():
        # window=0: due == now, timers fire on the next loop tick.
        af = AsyncFrontend(window=0.0, max_batch=64, cache_size=32)
        af.core.add_tenant("a", sess)
        qs = [rng.normal(size=(2, D)).astype(np.float32) for _ in range(6)]
        results = await asyncio.gather(*[af.query("a", q) for q in qs])
        return qs, results

    qs, results = asyncio.run(main())
    for q, r in zip(qs, results):
        np.testing.assert_array_equal(r.indices, sess.query(q).indices)


def test_async_frontend_raises_admission_error():
    sess = make_session()
    rng = np.random.default_rng(13)
    sess.ingest(rng.normal(size=(30, D)))

    async def main():
        af = AsyncFrontend(window=0.0, max_batch=64)
        af.core.add_tenant("a", sess)
        with pytest.raises(AdmissionError):
            await af.query("a", rng.normal(size=(1, D)), max_staleness_points=5)

    asyncio.run(main())


# ---------------------------------------------------------------- validation


def test_unknown_tenant_and_bad_shapes_fail_fast():
    fe, clk = make_frontend(make_session())
    with pytest.raises(KeyError):
        fe.submit("ghost", np.zeros((1, D), np.float32))
    with pytest.raises(ValueError):
        fe.submit("a", np.zeros((0, D), np.float32))
    with pytest.raises(ValueError):
        fe.add_tenant("a", make_session())  # duplicate registration


# ---------------------------------------------------------------- warm-start


def test_warmup_recompiles_observed_buckets_and_reports():
    fe, clk = make_frontend(make_session())
    rng = np.random.default_rng(21)
    # Serve once so the tenant's (bucket, d) set is observed.
    fe.submit("a", rng.normal(size=(2, D)))
    clk.advance(WINDOW)
    assert fe.flush() == 1
    state = fe.tenant("a")
    assert state.observed_buckets, "dispatch must record the bucket it served"
    report = fe.warmup("a")
    assert report.errors == 0
    assert report.warmed == len({b for (b, bd) in state.observed_buckets if bd == D})
    assert state.warmups == 1 and fe.stats["warmups"] == 1
    # Warming a tenant that never served traffic still warms the minimum
    # bucket (first-query traffic should not pay compile either way).
    fe.add_tenant("fresh", make_session(seed=3))
    report = fe.warmup("fresh")
    assert report.warmed >= 1 and report.errors == 0


def test_generation_bump_auto_warms_and_env_opts_out(monkeypatch):
    monkeypatch.delenv("REPRO_WARM_START", raising=False)
    fe, clk = make_frontend(make_session())
    rng = np.random.default_rng(22)
    fe.submit("a", rng.normal(size=(2, D)))
    clk.advance(WINDOW)
    fe.flush()
    sess = fe.tenant("a").session
    before = fe.warmups
    # A model generation bump fires the solve listener → auto warm-up of the
    # observed buckets against the NEW centers.
    sess.ingest(rng.normal(size=(80, D)).astype(np.float32))
    sess.solve()
    assert fe.warmups == before + 1
    # Post-warmup queries still answer correctly against the new model.
    t = fe.submit("a", rng.normal(size=(2, D)))
    clk.advance(WINDOW)
    fe.flush()
    assert t.done and t.state == "done"
    # Opting out suppresses the auto warm-up (listener stays registered).
    monkeypatch.setenv("REPRO_WARM_START", "0")
    sess.ingest(rng.normal(size=(80, D)).astype(np.float32))
    sess.solve()
    assert fe.warmups == before + 1


# ------------------------------------------------------- queue-wait stamps


def test_dispatch_stamps_tickets_and_counts_queue_wait_once():
    from repro.obs import default_registry

    fe, clk = make_frontend(make_session())
    reg = default_registry()
    rng = np.random.default_rng(7)
    clk.set(10.0)
    t1 = fe.submit("a", rng.normal(size=(2, D)))
    clk.advance(0.0005)
    t2 = fe.submit("a", rng.normal(size=(3, D)))
    clk.advance(WINDOW - 0.0005)
    assert t1.dispatched_at is None and t1.completed_at is None
    assert fe.flush() == 1
    assert t1.dispatched_at == t2.dispatched_at == clk.now()
    assert t1.completed_at == t2.completed_at == clk.now()
    # (10.002 − 10.0) + (10.002 − 10.0005): one increment for the dispatch.
    assert reg.value("serve_queue_wait_seconds", fe._obs_labels) == pytest.approx(
        2 * WINDOW - 0.0005)
    assert reg.value("serve_dispatched_tickets", fe._obs_labels) == 2
    # A cache hit completes at submit and is never dispatched.
    clk.advance(1.0)
    hit = fe.submit("a", t1.queries)
    assert hit.from_cache
    assert hit.completed_at == clk.now() and hit.dispatched_at is None
    assert reg.value("serve_dispatched_tickets", fe._obs_labels) == 2


class _TickingClock(VirtualClock):
    """Moves on by ``tick`` after every reading, so that each stamp the
    frontend takes (submit, dispatch, completion) is a distinct time."""

    def __init__(self, tick):
        super().__init__()
        self.tick = tick

    def now(self):
        t = super().now()
        self.advance(self.tick)
        return t


def test_latency_and_queue_wait_are_read_from_the_ticket_stamps():
    from repro.obs import default_registry

    clk = _TickingClock(0.0001)
    fe = ServingFrontend(window=WINDOW, max_batch=64, cache_size=128, clock=clk)
    fe.add_tenant("a", make_session())
    rng = np.random.default_rng(11)
    tickets = [fe.submit("a", rng.normal(size=(2, D))) for _ in range(3)]
    clk.advance(WINDOW)
    assert fe.flush() == 1
    for t in tickets:
        assert t.submitted_at < t.dispatched_at < t.completed_at
    snap = fe._lat_hist("a").snapshot()
    assert snap.samples == pytest.approx(
        sorted((t.completed_at - t.submitted_at) * 1e6 for t in tickets))
    assert default_registry().value(
        "serve_queue_wait_seconds", fe._obs_labels
    ) == pytest.approx(sum(t.dispatched_at - t.submitted_at for t in tickets))


def test_dispatch_span_holds_fetch_and_is_followed_by_complete():
    from repro.obs import configure_buffer
    from repro.obs import trace as trace_mod

    trace_mod.flush()  # pause spans queued by earlier tests go to the old ring
    prev = trace_mod._BUFFER
    buf = configure_buffer(256)
    try:
        fe, clk = make_frontend(make_session())
        fe.submit("a", np.ones((4, D), np.float32))
        clk.advance(WINDOW)
        assert fe.flush() == 1
        rows = [r for r in buf.rows() if r["name"].startswith("serve.")]
    finally:
        trace_mod._BUFFER = prev
    # The ring takes a span as it ends: the fetch inside the dispatch first,
    # then the dispatch, then the completion that follows it.
    assert [r["name"] for r in rows] == ["serve.fetch", "serve.dispatch", "serve.complete"]
    fetch, dispatch, complete = rows
    assert fetch["parent"] == dispatch["span"]
    assert complete["parent"] == dispatch["parent"]
    assert fetch["attrs"] == {"rows": 4, "bucket": dispatch["attrs"]["bucket"]}
    assert complete["attrs"] == {"tickets": 1}
