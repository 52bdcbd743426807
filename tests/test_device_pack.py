"""The workers' shards are packed on the device: ``pack_local_shards`` gathers
them from the points by the assignment's slot table, and a session solve
uploads the caller's points once."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ElasticPolicy, ResilienceSession, cyclic_assignment
from repro.core import kmedian as km
from repro.core.assignment import Assignment, bernoulli_assignment, node_loads
from repro.core.executor import get_executor


def _host_pack(points, assignment):
    """The plain host pack: each node's shards in order, zero-padded to the
    largest load, weight 1 on a shard and 0 on padding."""
    s = assignment.num_nodes
    loads = [assignment.shards_of(i) for i in range(s)]
    m = max((len(l) for l in loads), default=1) or 1
    xs = np.zeros((s, m, points.shape[1]), dtype=np.float32)
    ws = np.zeros((s, m), dtype=np.float32)
    for i, l in enumerate(loads):
        xs[i, : len(l)] = points[l]
        ws[i, : len(l)] = 1.0
    return xs, ws


def _pts(n, d=3, seed=0):
    pts = np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32)
    pts[1] = -0.0  # a sign bit that a bitwise comparison sees
    return pts


def _skewed():
    """Loads 8/8/8/8/4/4/4/4: padding on half the nodes; nodes 6 and 7
    alone hold shards 16–19."""
    mat = np.zeros((8, 20), dtype=np.uint8)
    mat[[0, 2], 0:8] = 1
    mat[[1, 3], 8:16] = 1
    mat[4, 0:4] = 1
    mat[5, 4:8] = 1
    mat[[6, 7], 16:20] = 1
    return Assignment(matrix=mat, scheme="skewed", params={})


def _bernoulli_with_empty_node():
    for seed in range(100):
        a = bernoulli_assignment(6, 8, ell=1.0, rng=np.random.default_rng(seed))
        if (node_loads(a) == 0).any():
            return a
    raise AssertionError("no zero-load draw in 100 seeds")


def _patched():
    """The assignment after an elastic patch: nodes 6 and 7 straggle until
    their shards are re-replicated onto healthy nodes."""
    sess = ResilienceSession(_skewed(), elastic=ElasticPolicy(enabled=True, patience=2))
    dead = np.ones(8, dtype=bool)
    dead[[6, 7]] = False
    for _ in range(3):
        sess.observe(dead)
    assert sess.version >= 1 and sess.stats.elastic_patches >= 1
    return sess.assignment


ASSIGNMENTS = {
    "cyclic": lambda: cyclic_assignment(40, 4, 2),
    "skewed": _skewed,
    "bernoulli_empty_node": _bernoulli_with_empty_node,
    "elastic_patch": _patched,
}


@pytest.mark.parametrize("on_device", [False, True], ids=["host_points", "device_points"])
@pytest.mark.parametrize("name", sorted(ASSIGNMENTS))
def test_device_pack_is_the_host_pack_bit_for_bit(name, on_device):
    a = ASSIGNMENTS[name]()
    pts = _pts(a.num_shards)
    want_xs, want_ws = _host_pack(pts, a)
    xs, ws = km.pack_local_shards(jnp.asarray(pts) if on_device else pts, a)
    assert isinstance(xs, jax.Array)
    got = np.asarray(xs)
    assert got.shape == want_xs.shape and got.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.uint32), want_xs.view(np.uint32))
    np.testing.assert_array_equal(ws, want_ws)
    # The weights are the caller's to change: a fresh writable copy each call.
    assert isinstance(ws, np.ndarray) and ws.flags.writeable
    _, ws2 = km.pack_local_shards(pts, a)
    assert ws2 is not ws
    ws[:] = 7.0
    np.testing.assert_array_equal(km.pack_local_shards(pts, a)[1], want_ws)
    # The slot table is built and uploaded once per assignment.
    assert a.device_slot_rows is a.device_slot_rows


def test_pack_refuses_points_that_miss_shards():
    with pytest.raises(ValueError, match="shards as rows"):
        km.pack_local_shards(_pts(30), cyclic_assignment(40, 4, 2))


@pytest.mark.parametrize("executor", ["local", "mesh"])
def test_session_kmedian_equals_pipeline_on_the_host_pack(executor):
    pts = _pts(120, seed=4)
    a = cyclic_assignment(120, 6, 2)
    alive = np.array([True, True, False, True, True, True])
    sess = ResilienceSession(a, executor=executor)
    out = sess.kmedian(pts, 3, alive, local_iters=3, coord_iters=4, seed=11)
    xs, ws = _host_pack(pts, a)
    centers, cost, y, wy = km._coordinator_pipeline(
        pts, 3, xs, ws, sess.recovery(alive).b_full, get_executor(executor),
        local_iters=3, coord_iters=4, seed=11, impl="auto",
    )
    np.testing.assert_array_equal(out.centers, centers)
    assert out.cost == cost
    np.testing.assert_array_equal(out.summary_points, y)
    np.testing.assert_array_equal(out.summary_weights, wy)


def test_solve_uploads_its_points_once(monkeypatch):
    """The pipeline gets the session's device points and shards, and the
    full-data cost reads that same array: the caller's points cross to the
    device once, in the pack, and the shard stack never crosses."""
    from repro.core import kmeans
    from repro.obs import configure_buffer
    from repro.obs import trace as trace_mod

    pipeline, cost_fn = km._coordinator_pipeline, kmeans.clustering_cost
    seen = {}

    def pipeline_spy(points, k, xs, *a, **kw):
        seen["pipeline"], seen["xs"] = points, xs
        return pipeline(points, k, xs, *a, **kw)

    def cost_spy(x, *a, **kw):
        seen["cost"] = x
        return cost_fn(x, *a, **kw)

    monkeypatch.setattr(km, "_coordinator_pipeline", pipeline_spy)
    monkeypatch.setattr(kmeans, "clustering_cost", cost_spy)
    pts = _pts(96)
    trace_mod.flush()
    prev = trace_mod._BUFFER
    buf = configure_buffer(512)
    try:
        sess = ResilienceSession(cyclic_assignment(96, 4, 2))
        sess.kmedian(pts, 3, np.array([True, False, True, True]),
                     local_iters=2, coord_iters=2)
        rows = buf.rows()
    finally:
        trace_mod._BUFFER = prev
    assert isinstance(seen["pipeline"], jax.Array) and isinstance(seen["xs"], jax.Array)
    assert seen["cost"] is seen["pipeline"]
    packs = [r for r in rows if r["name"] == "session.pack"]
    assert [p["attrs"]["uploaded"] for p in packs] == [pts.nbytes]
    # Of the pipeline's inputs only the (4, 48) weights still cross.
    uploads = [r for r in rows if r["name"] == "kmedian.upload"]
    assert [u["attrs"]["bytes"] for u in uploads] == [4 * 48 * 4]
    # Device points are used as they are.
    dev = jnp.asarray(pts)
    assert sess.prepare(dev, np.ones(4, bool))[0] is dev
