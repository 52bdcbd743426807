"""Open-loop nearest-center queries through ``ServingFrontend`` submit and
flush, with Poisson arrivals over tenants of Zipf popularity.

Set-up builds each tenant's model the way a user does (``StreamingSession``
ingest of seeded rows, then ``solve``), registers the tenants with a
frontend at its defaults, warms its shape buckets, and draws every request
of the window from the seed: arrival time, tenant, and 1–64 query rows
(log-uniform) near that tenant's data.  One thread submits each request
when it falls due and flushes whatever batches have closed.  A request's
latency runs from when the schedule said it was due to when its answer is
on the host, so a stall that delays later submissions is charged to them.
The window reports the median of every request's latency; its 95th
percentile is noted, and read per layer over the requests due in a traced
run's profiled stretch: over a whole window a single stall of the host of a
second or more sets it.

Traffic parameters (``bench/traffic/<mix>.json``): ``rate`` (requests per
second, fixed below the measured knee), ``zipf``, ``rows`` (least and most
rows of a request), ``checked_requests``, ``trace_seconds`` and ``limits``.
The schedule covers exactly the window's ``--seconds``.
"""

from __future__ import annotations

import gc
import sys
import time

import numpy as np

import gen
import harness

TAIL = 95.0


class State:
    pass


def setup(cell: harness.Cell, seconds: float, log=print) -> State:
    from repro.serve import ServingFrontend
    from repro.stream import StreamingSession

    c, tr = cell.config, cell.traffic
    sv = c["serve"]
    ref = cell.config_module
    st = State()
    st.cell, st.cfg, st.tr = cell, c, tr
    data = ref.make_datasets(
        int(gen.sub_seeds(cell.seed, 1, 1)[0]), count=sv["tenants"], n=sv["points_per_tenant"],
        d=c["d"], planted=c["data"]["planted"], scale=c["data"]["scale"],
        noise=c["data"]["noise"])
    st.names = [f"t{i}" for i in range(sv["tenants"])]
    fe = ServingFrontend()
    seeds = gen.sub_seeds(cell.seed, 2, len(st.names))
    for name, pts, s in zip(st.names, data, seeds):
        sess = StreamingSession(d=c["d"], k=c["k"], num_nodes=sv["nodes"], leaf_size=sv["leaf"],
                                coreset_size=sv["coreset"], seed=int(s))
        fe.add_tenant(name, sess)
        for lo in range(0, len(pts), sv["ingest_batch"]):
            sess.ingest(pts[lo:lo + sv["ingest_batch"]])
        sess.solve()
    report = fe.warmup()
    if report.errors:
        raise RuntimeError(f"serving warm-up: {report.errors} entries failed")
    st.fe = fe
    st.centers = {n: np.array(fe.tenant(n).session.centers, np.float32) for n in st.names}
    _draw(st, data, seconds)
    # One request of each bucket size per tenant runs before the window, so
    # no shape compiles inside it.
    for name, pts in zip(st.names, data):
        for m in (1, 2, 4, 8, 16, 32, 64, 128, 256, 512):
            fe.submit(name, pts[:m] + np.float32(0.5))
            fe.drain()
    st.data = data
    return st


def _draw(st: State, data: list, seconds: float) -> None:
    """Every request of a window of ``seconds`` from the seed, and the
    sample of them that the check compares."""
    tr, d = st.tr, st.cfg["d"]
    rate = float(tr["rate"])
    r = gen.rng(st.cell.seed, 3)
    st.arrivals = gen.poisson_arrivals(st.cell.seed, rate, seconds)
    n = len(st.arrivals)
    pick = gen.rng(st.cell.seed, 4).choice(n, size=min(n, int(tr["checked_requests"])),
                                           replace=False)
    st.checked = np.zeros(n, bool)
    st.checked[pick] = True
    pop = 1.0 / np.arange(1, len(st.names) + 1) ** float(tr["zipf"])
    st.tenant_of = r.choice(len(st.names), size=n, p=pop / pop.sum())
    lo, hi = tr["rows"]
    st.rows_of = np.floor(np.exp(r.uniform(np.log(lo), np.log(hi + 1), size=n))).astype(int)
    st.rows_of = np.clip(st.rows_of, lo, hi)
    st.offsets = np.concatenate([[0], np.cumsum(st.rows_of)])
    total = int(st.offsets[-1])
    src = r.integers(0, len(data[0]), size=total)
    st.queries = np.empty((total, d), np.float32)
    for t in range(len(st.names)):
        sel = np.repeat(st.tenant_of == t, st.rows_of)
        st.queries[sel] = data[t][src[sel]]
    st.queries += r.standard_normal((total, d), dtype=np.float32)


def window(st: State, seconds: float, tracer: harness.Tracer) -> harness.WindowResult:
    from repro.obs import default_buffer

    fe = st.fe
    due = st.arrivals[st.arrivals < seconds]
    n = st.n_window = len(due)
    default_buffer().clear()
    d0 = fe.dispatches
    s0 = fe.served
    # Only the checked requests' answers are kept once they arrive; the
    # window holds no more than what is outstanding besides.
    st.kept = {}
    done_at = np.full(n, np.inf)
    late = np.zeros(n)
    outstanding = []
    failed = 0
    # A traced run profiles the window's first ``trace_seconds``: starting
    # the profiler stalls the host, so it starts before the clock does, and
    # the per-layer counters are read where it stops.
    trace_until = float(st.tr["trace_seconds"]) if tracer.enabled else np.inf
    layer = None
    held = _Held()
    tracer.begin()
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter() - t0
        held.step(now)
        if layer is None and now >= trace_until:
            layer = _layer_counters(fe, d0, s0)
            tracer.end()
        while i < n and due[i] <= now:
            q = st.queries[st.offsets[i]:st.offsets[i + 1]]
            try:
                outstanding.append((i, fe.submit(st.names[st.tenant_of[i]], q)))
            except Exception as e:  # a refused request counts as failed
                failed += 1
                print(f"request {i} refused: {e!r}", file=sys.stderr)
            late[i] = now - due[i]
            i += 1
        held.mark("flush", time.perf_counter() - t0)
        fe.flush()
        stamp = time.perf_counter() - t0
        held.mark("sleep", stamp)
        still = []
        for j, t in outstanding:
            if not t.done:
                still.append((j, t))
            elif t.error is not None:
                failed += 1
            else:
                done_at[j] = stamp
                if st.checked[j]:
                    st.kept[j] = t.result
        outstanding = still
        if i >= n and not outstanding:
            break
        nxt = due[i] if i < n else np.inf
        wake = fe.due()
        wake = (wake - fe.clock.now()) + (time.perf_counter() - t0) if wake is not None else np.inf
        pause = min(nxt, wake) - (time.perf_counter() - t0)
        if pause > 0:
            time.sleep(min(pause, 0.05))
    tracer.end()
    held.close()
    lat_ms = (done_at - due) * 1e3
    p50 = float(np.median(lat_ms)) if n else float("inf")
    p95 = float(np.percentile(lat_ms, TAIL)) if n else float("inf")
    beyond = int(np.sum(lat_ms > p95))
    if layer is None:
        layer = _layer_counters(fe, d0, s0)
    notes = [
        f"served requests {n} rows {fe.served - s0} dispatches {fe.dispatches - d0} failed {failed}",
        f"late generator p50_ms {float(np.median(late)) * 1e3!r} max_ms {float(late.max()) * 1e3 if n else 0.0!r}",
        f"tail p50_ms {p50!r} p95_ms {p95!r} beyond_p95 {beyond}",
        *held.notes(),
    ]
    return harness.WindowResult(
        attempted=n, failed=failed, metrics={"query_p50_ms": p50},
        counters={"requests": n, "latency_ms": lat_ms,
                  "stretch_latency_ms": lat_ms[due < trace_until], **layer},
        notes=notes,
    )


class _Held:
    """Where the submitting loop was held up: each pass of the loop that took
    ``HELD_S`` or more, with the phase it stood in (``submit``, ``flush`` or
    ``sleep``, a sleep asking 50 ms at most), its wall and process CPU
    seconds; and the compiles and collections that ran in the window.  A
    hold with next to no CPU in a sleep is the host standing still, not the
    program."""

    HELD_S = 0.1

    def __init__(self):
        import jax.monitoring

        self.rows = []
        self.compiles = []
        self.gc_s = []
        self._gc_t = None
        self._open = True
        self._prev = None
        self._marks = {}
        self._cpu = time.process_time()

        def on_compile(event, duration, **kwargs):
            if self._open and event == "/jax/core/compile/backend_compile_duration":
                self.compiles.append((str(kwargs.get("fun_name", "")), duration))

        def on_gc(phase, info):
            if not self._open:
                return
            if phase == "start":
                self._gc_t = time.perf_counter()
            elif self._gc_t is not None:
                self.gc_s.append(time.perf_counter() - self._gc_t)

        jax.monitoring.register_event_duration_secs_listener(on_compile)
        gc.callbacks.append(on_gc)
        self._on_gc = on_gc

    def mark(self, phase: str, at: float) -> None:
        self._marks[phase] = at

    def step(self, now: float) -> None:
        """The loop is back at its top at ``now``: note the pass just ended
        if it held the loop."""
        cpu = time.process_time()
        prev = self._prev
        if prev is not None and now - prev >= self.HELD_S:
            f = self._marks.get("flush", prev)
            s = self._marks.get("sleep", f)
            walls = {"submit": f - prev, "flush": s - f, "sleep": now - s}
            phase = max(walls, key=walls.get)
            self.rows.append((prev, now - prev, phase, walls[phase], cpu - self._cpu))
        self._prev, self._cpu = now, cpu
        self._marks.clear()

    def close(self) -> None:
        self._open = False
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def notes(self) -> list:
        worst = sorted(self.rows, key=lambda r: -r[1])[:6]
        return [
            f"held passes {len(self.rows)} total_s {sum(r[1] for r in self.rows)!r} longest "
            + " ".join(f"[at_s {a:.3f} wall_s {w:.3f} {p} {pw:.3f} cpu_s {c:.3f}]"
                       for a, w, p, pw, c in worst),
            f"window compiles {len(self.compiles)} s {sum(d for _, d in self.compiles)!r} "
            + " ".join(sorted({n for n, _ in self.compiles})[:8]),
            f"window collections {len(self.gc_s)} s {sum(self.gc_s)!r} "
            f"max_ms {1e3 * max(self.gc_s, default=0.0)!r}",
        ]


def _layer_counters(fe, d0: int, s0: int) -> dict:
    """The frontend's own counters and ``serve.dispatch`` spans so far."""
    from repro.obs import default_buffer

    spans = [r for r in default_buffer().rows() if r.get("name") == "serve.dispatch"]
    return {"dispatches": fe.dispatches - d0, "rows": fe.served - s0,
            "dispatch_spans_s": [r["dur_us"] * 1e-6 for r in spans]}


def _checked(st: State) -> list:
    return [int(i) for i in np.flatnonzero(st.checked[:st.n_window])]


def compared_numbers(st: State, answers: dict) -> dict:
    """``answers`` maps a checked request to the (indices, distances) it was
    served.  ``dist_gap``, the worst row: how far the served distance, and
    the distance to the served center, lie from the distance to the nearest
    center (float64), relative to it.  A near-tie served either way reads
    at the rounding of a distance; a wrong center reads its excess."""
    ref = st.cell.config_module
    gap = 0.0
    for i, (idx, dist) in answers.items():
        if idx is None:
            return {"dist_gap": float("inf")}
        q = st.queries[st.offsets[i]:st.offsets[i + 1]].astype(np.float64)
        c = st.centers[st.names[st.tenant_of[i]]].astype(np.float64)
        _, best2 = ref.ref_nearest(q, c)
        best = np.maximum(np.sqrt(best2), 1e-6)
        to_served = np.sqrt(((q - c[np.asarray(idx)]) ** 2).sum(axis=1))
        served = np.asarray(dist, np.float64)
        gap = max(gap, float(np.max(np.maximum(np.abs(served - best), to_served - best) / best)))
    return {"dist_gap": gap}


def _program_answers(st: State) -> dict:
    out = {}
    for i in _checked(st):
        res = st.kept.get(i)
        out[i] = (res.indices, res.distances) if res is not None else (None, None)
    return out


def _control_answers(st: State) -> dict:
    """The reference in the program's place at matmul precision ``high``
    (three bf16 passes), one step below the ``highest`` the deployment
    states."""
    import jax
    import jax.numpy as jnp

    dot = st.cell.config_module.dot

    @jax.jit
    def near(q, c):
        d2 = jnp.sum(q * q, 1)[:, None] + jnp.sum(c * c, 1)[None, :] - 2.0 * dot(q, c.T, "high")
        idx = jnp.argmin(d2, axis=1)
        return idx, jnp.sqrt(jnp.maximum(jnp.take_along_axis(d2, idx[:, None], 1)[:, 0], 0.0))

    hi = int(st.tr["rows"][1])
    out = {}
    for i in _checked(st):
        q = st.queries[st.offsets[i]:st.offsets[i + 1]]
        pad = np.zeros((hi, q.shape[1]), np.float32)
        pad[:len(q)] = q
        idx, dist = near(jnp.asarray(pad), jnp.asarray(st.centers[st.names[st.tenant_of[i]]]))
        out[i] = (np.asarray(idx)[:len(q)], np.asarray(dist)[:len(q)])
    return out


def free_program(st: State) -> None:
    st.fe = None
    st.data = None
    gc.collect()


def check(st: State) -> list:
    answers = _program_answers(st)
    free_program(st)
    got = compared_numbers(st, answers)
    return [harness.Check(k, got[k], float(v)) for k, v in st.tr["limits"].items()]


def readings(st: State) -> dict:
    answers = _program_answers(st)
    free_program(st)
    return {"sound": compared_numbers(st, answers),
            "control": compared_numbers(st, _control_answers(st))}
