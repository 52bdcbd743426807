"""Algorithm 1 — straggler-resilient distributed k-median (paper §3.2).

Pipeline (exactly the paper's):

1. Allocate ``P`` to ``s`` workers by an assignment with Property 1.
2. Each worker solves weighted k-median on its local shard; the centers
   ``Y_i`` are weighted by their (weighted) cluster sizes ``w_i``.
3. The coordinator collects ``{(Y_i, w_i)}`` from the alive set ``R``,
   reweights by the recovery vector (``w(c) = b_i·w_i(c)``), and solves
   weighted k-median on the union.  Theorem 3: cost ≤ 3(1+δ)·OPT.

Execution: WHERE step 2 runs is the executor seam
(:mod:`repro.core.executor`) — the default :class:`LocalExecutor` simulates
all workers as one vmapped batch over padded local shards (one compiled
program regardless of node count / load skew);
:class:`repro.launch.distributed.MeshExecutor` runs the identical per-node
program node-parallel under ``shard_map`` on a device mesh, with the
recovery weights applied as a runtime mask inside the compiled step.  The
combine keeps the fixed ``(s·k,)`` stacked shape in both cases — straggler
rows carry recovery weight 0 and are inert in the coordinator solve, so the
straggler pattern never changes a compiled shape.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace_span
from . import kmeans
from .assignment import Assignment
from .executor import Executor, get_executor
from .recovery import RecoveryResult

__all__ = [
    "pack_local_shards",
    "prepare_resilient_run",
    "local_cluster_batch",
    "resilient_kmedian",
    "ignore_stragglers_kmedian",
    "ResilientClusteringOutput",
]


@dataclasses.dataclass
class ResilientClusteringOutput:
    centers: np.ndarray          # (k, d) final coordinator centers
    cost: float                  # cost(P, centers) on the FULL dataset
    recovery: RecoveryResult     # the b used (diagnostics: δ, coverage)
    summary_points: np.ndarray   # the coordinator's weighted input Y (s·k, d)
    summary_weights: np.ndarray  # b-weighted center weights (s·k,); 0 at stragglers


def pack_local_shards(
    points: np.ndarray, assignment: Assignment
) -> tuple[np.ndarray, np.ndarray]:
    """Pad per-node shards to the max load: (s, m, d) data + (s, m) weights.

    Padding rows are zeros with weight 0 — inert in every weighted statistic.
    Row ``i`` is exactly the data the assignment matrix maps to node ``i``,
    so sharding the stacked array over a device mesh's node axis IS the
    paper's data placement.
    """
    s = assignment.num_nodes
    loads = [assignment.shards_of(i) for i in range(s)]
    m = max((len(l) for l in loads), default=1) or 1
    d = points.shape[1]
    xs = np.zeros((s, m, d), dtype=np.float32)
    ws = np.zeros((s, m), dtype=np.float32)
    for i, l in enumerate(loads):
        xs[i, : len(l)] = points[l]
        ws[i, : len(l)] = 1.0
    return xs, ws


def prepare_resilient_run(
    points,
    assignment: Assignment,
    alive,
    *,
    recovery_method: Optional[str] = None,
    executor: Union[None, str, Executor] = None,
    session=None,
):
    """Shared prelude of every distributed algorithm: dtype coercion,
    recovery solve, all-dead guard, executor resolution, shard packing.

    The state lives in a :class:`repro.core.resilience.ResilienceSession` —
    pass ``session=`` to share the per-pattern recovery cache and packed
    shards across calls (and algorithms); otherwise a throwaway session
    reproduces the old per-call behaviour (``recovery_method`` defaults to
    ``"auto"``).  When a session is given it owns the (possibly
    elastically-patched) assignment and the executor, and any explicitly
    passed ``assignment``/``executor``/``recovery_method`` that contradicts
    the session's is an error — silently preferring one side would return
    plausible results computed against the wrong matrix/device/solver.  (Any
    assignment from the session's own lineage — the original or a patched
    successor — is accepted, so callers may keep passing their pre-patch
    reference mid-run.)

    Returns ``(points, alive, rec, ex, xs, ws)``.  Keeping this in one place
    keeps the guard/dtype handling from drifting between Algorithms 1–3.
    """
    from .resilience import ResilienceSession

    if session is None:
        session = ResilienceSession(
            assignment, recovery_method=recovery_method or "auto", executor=executor
        )
    else:
        if recovery_method is not None and recovery_method != session.recovery_method:
            raise ValueError(
                f"recovery_method={recovery_method!r} conflicts with the session's "
                f"{session.recovery_method!r}; construct the ResilienceSession with "
                "the method you want"
            )
        if assignment is not None and id(assignment) not in session._assignment_lineage:
            raise ValueError(
                "assignment= is not the session's assignment (nor a pre-patch "
                "version of it); a session owns exactly one assignment — build "
                "a new ResilienceSession for a different one"
            )
        if executor is not None and get_executor(executor) is not session.executor:
            raise ValueError(
                f"executor={executor!r} conflicts with the session's "
                f"{session.executor.name!r} executor; construct the "
                "ResilienceSession with the executor you want"
            )
    return session.prepare(points, alive)


@functools.lru_cache(maxsize=None)
def _local_solve_fn(k: int, iters: int, median: bool, impl: str):
    """Per-node local solve, memoized so executors can key jit caches on it.

    ``b`` is the node's recovery weight — applied to the center weights
    INSIDE the compiled step, so straggling is a runtime input, not a shape.
    """

    def one(key, x, w, b):
        from ..kernels.weighted_segsum import ops as ss

        res = kmeans.lloyd(key, x, k, weights=w, iters=iters, median=median, impl=impl)
        _, tot = ss.weighted_segsum(x, w, res.assignment, k, impl=impl)
        return res.centers, b.astype(tot.dtype) * tot

    return one


def local_cluster_batch(
    key, xs, ws, k: int, *, iters: int = 20, median: bool = True, impl: str = "auto",
    executor: Union[None, str, Executor] = None,
):
    """All workers' local clustering through the executor seam.

    Returns (centers (s, k, d), center_weights (s, k)) where center weights
    are the weighted local cluster sizes (the paper's ``w_i(c)``).
    ``impl`` selects the kernel implementation (repro.kernels.dispatch);
    ``executor`` selects where the per-node solves run (repro.core.executor).
    """
    ex = get_executor(executor)
    s = xs.shape[0]
    keys = jax.random.split(key, s)
    ones = jnp.ones((s,), jnp.float32)  # no recovery weighting at this layer
    fn = _local_solve_fn(k, iters, median, impl)
    return ex.map_nodes(fn, (keys, jnp.asarray(xs), jnp.asarray(ws), ones))


def _coordinator_pipeline(
    points: np.ndarray,
    k: int,
    xs: np.ndarray,
    ws: np.ndarray,
    b_full: np.ndarray,
    ex: Executor,
    *,
    local_iters: int,
    coord_iters: int,
    seed: int,
    impl: str,
) -> tuple[np.ndarray, float, np.ndarray, np.ndarray]:
    """Shared steps 2–3: local solves (via executor), b-weighted fixed-shape
    union, coordinator weighted k-median, full-dataset cost.

    Four sibling spans: ``kmedian.upload`` (the packed shards handed to the
    device), then ``kmedian.local``, ``kmedian.coordinator`` and
    ``kmedian.cost``, each ending where its result is on the host."""
    s, m, d = xs.shape
    with trace_span("kmedian.upload", rows=s * m, bytes=xs.nbytes + ws.nbytes):
        # Not waited for: the span ends once the copy is handed to the
        # runtime, and the rest of the transfer shows under kmedian.local,
        # where the local solves wait for it.  A wait here would only keep
        # the host from dispatching them meanwhile.
        xs_d, ws_d = jnp.asarray(xs), jnp.asarray(ws)
        b_d = jnp.asarray(b_full, jnp.float32)
    with trace_span("kmedian.local", nodes=s, rows=s * m):
        keys = jax.random.split(jax.random.PRNGKey(seed), s)
        fn = _local_solve_fn(k, local_iters, True, impl)
        centers_s, wts_s = ex.map_nodes(fn, (keys, xs_d, ws_d, b_d))
        # Fixed-shape union: (s·k, d) points, b-weighted weights (0 at
        # stragglers — inert in the weighted coordinator solve, like
        # in-shard padding rows).
        y = np.asarray(centers_s).reshape(s * k, d)
        wy = np.asarray(wts_s).reshape(s * k)
    with trace_span("kmedian.coordinator", rows=s * k):
        res = kmeans.lloyd(
            jax.random.PRNGKey(seed + 1), jnp.asarray(y), k, weights=jnp.asarray(wy),
            iters=coord_iters, median=True, impl=impl,
        )
        centers = np.asarray(res.centers)
    with trace_span("kmedian.cost", rows=points.shape[0]):
        full_cost = float(
            kmeans.clustering_cost(
                jnp.asarray(points), jnp.asarray(centers), median=True, impl=impl
            )
        )
    return centers, full_cost, y, wy


def resilient_kmedian(
    points: np.ndarray,
    k: int,
    assignment: Assignment,
    alive: np.ndarray,
    *,
    recovery_method: Optional[str] = None,
    local_iters: int = 20,
    coord_iters: int = 40,
    seed: int = 0,
    impl: str = "auto",
    executor: Union[None, str, Executor] = None,
    session=None,
) -> ResilientClusteringOutput:
    """Paper Algorithm 1, end-to-end.  ``executor`` selects local vs mesh
    execution of the per-worker solves (see repro.core.executor);
    ``session`` shares recovery/pack state across calls
    (see repro.core.resilience)."""
    points, alive, rec, ex, xs, ws = prepare_resilient_run(
        points, assignment, alive, recovery_method=recovery_method,
        executor=executor, session=session,
    )
    centers, full_cost, y, wy = _coordinator_pipeline(
        points, k, xs, ws, rec.b_full, ex,
        local_iters=local_iters, coord_iters=coord_iters, seed=seed, impl=impl,
    )
    return ResilientClusteringOutput(
        centers=centers, cost=full_cost, recovery=rec,
        summary_points=y, summary_weights=wy,
    )


def ignore_stragglers_kmedian(
    points: np.ndarray,
    k: int,
    assignment: Assignment,
    alive: np.ndarray,
    *,
    local_iters: int = 20,
    coord_iters: int = 40,
    seed: int = 0,
    impl: str = "auto",
    executor: Union[None, str, Executor] = None,
) -> ResilientClusteringOutput:
    """The paper's Fig 1(b) baseline: no recovery weighting — alive workers'
    centers are combined as-is (b ≡ 1 on the alive set).  With a
    non-redundant assignment this silently drops the stragglers' data."""
    points = np.asarray(points, dtype=np.float32)
    alive = np.asarray(alive, dtype=bool)
    if not alive.any():
        raise ValueError("no surviving nodes with data — cannot form union")
    ex = get_executor(executor)
    xs, ws = pack_local_shards(points, assignment)
    centers, full_cost, y, wy = _coordinator_pipeline(
        points, k, xs, ws, alive.astype(np.float32), ex,
        local_iters=local_iters, coord_iters=coord_iters, seed=seed, impl=impl,
    )
    from .recovery import lp_recovery

    rec = lp_recovery(assignment, alive)
    return ResilientClusteringOutput(
        centers=centers, cost=full_cost, recovery=rec,
        summary_points=y, summary_weights=wy,
    )
