"""Executor seam: WHERE per-node local computations run.

The paper's Algorithms 1–3 share one shape: pack shards per the
:class:`~repro.core.assignment.Assignment`, run an independent local
computation on every node's shard, then combine the alive nodes' outputs with
the recovery weights ``b`` (Lemma 3).  The *algorithms* (kmedian, pca,
coreset, kmeans) define the per-node function; the *executor* decides where
it runs:

* :class:`LocalExecutor` — single process: ``map_nodes`` runs all nodes as
  one ``jax.vmap`` batch; the fused masked combine evaluates them one at a
  time (:func:`~repro.core.aggregation.resilient_map_sum`).  The default.
* :class:`~repro.launch.distributed.MeshExecutor` — every node is placed on
  a device of a 1-D ``("nodes",)`` mesh and the same per-node function runs
  under ``shard_map``; the alive/recovery mask is a *runtime input* of the
  compiled step (no recompile when the straggler set changes) and the
  Lemma-3 combine (``core.aggregation``) executes on device as a ``psum``.

Both executors compile the *identical* inner function (the mesh path merely
splits the node batch across devices), so their outputs agree to float32
round-off — `tests/test_distributed_executor.py` pins cost parity at 1e-5.

Per-node functions must be *stable objects* (module-level or
``functools.lru_cache``-memoized closures): the executor keys its jit cache
on the function identity, so a fresh closure per call would recompile every
time.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from ..analysis import compiled_path
from ..obs import trace_span
from .aggregation import resilient_map_sum, resilient_sum
from .recovery import jax_recovery_masked

__all__ = ["Executor", "LocalExecutor", "get_executor"]


def _as_jax_tree(a):
    """Coerce one argument — an array OR an arbitrary pytree of arrays (a
    params dict, a grad tree) — to jax arrays leaf-wise."""
    return jax.tree_util.tree_map(jnp.asarray, a)


class Executor:
    """Protocol: map an independent per-node function over node-stacked data.

    ``node_args`` are arrays with a leading node axis (one slice per node,
    e.g. the padded shards from ``pack_local_shards``); ``broadcast_args``
    are shared by every node (e.g. a candidate center set — or a whole
    params pytree: broadcast arguments and ``fn`` outputs may be arbitrary
    pytrees, which is what lets a training step route its per-group gradient
    trees through the same Lemma-3 combine as the clustering scalars).
    Node-stacked arguments must be plain arrays (they are padded and sliced
    along the node axis).
    """

    name = "abstract"

    def map_nodes(self, fn: Callable, node_args: Sequence[Any], broadcast_args: Sequence[Any] = ()):
        """``stack_i fn(node_args[..][i], *broadcast_args)`` — one output row
        per node."""
        raise NotImplementedError

    def resilient_reduce(
        self,
        fn: Callable,
        node_args: Sequence[Any],
        broadcast_args: Sequence[Any],
        b_full,
    ):
        """Lemma-3 combine: ``Σ_i b_i · fn(node_i)`` over every output leaf.

        ``b_full`` carries zeros at stragglers, so their contributions vanish
        wherever the reduction runs.
        """
        raise NotImplementedError

    def resilient_reduce_masked(
        self,
        fn: Callable,
        node_args: Sequence[Any],
        broadcast_args: Sequence[Any],
        A,
        alive,
        *,
        iters: int = 300,
        b_override=None,
    ):
        """Lemma-3 combine with the recovery weights solved ON DEVICE.

        The compiled step takes the full assignment matrix ``A`` and the
        boolean ``alive`` mask as runtime arrays, runs
        :func:`repro.core.recovery.jax_recovery_masked` inside the step, and
        combines — so a previously-unseen straggler pattern costs zero host
        solves and zero recompiles.  Returns ``(reduced, b_full)``; the
        weights come back so callers can parity-check against the host LP
        without a second solve.

        ``b_override`` (optional ``(s,)`` weights) routes the combine through
        caller-supplied weights instead of the on-device solve — as *runtime
        data* through the SAME compiled program (a ``jnp.where`` select on a
        runtime flag).  This is how degenerate patterns fall back to host
        best-effort weights without compiling a second full program for the
        fallback path.
        """
        raise NotImplementedError

    def replicated_compute(self, fn: Callable, args: Sequence[Any]):
        """Run ``fn(*args)`` redundantly on every node; return ONE result.

        Compute redundancy, the dual of the paper's data redundancy: all
        inputs are replicated, every node computes the identical output, and
        any alive replica serves it — a straggler mid-computation costs
        nothing.  Locally one compiled call stands in for all replicas; the
        mesh executor really does run the program on every device (see
        :meth:`repro.launch.distributed.MeshExecutor.replicated_compute`).
        Used by the streaming layer's tree compactions
        (:mod:`repro.stream.buffer`).
        """
        raise NotImplementedError

    # --------------------------------------------------- placement helpers
    # Sessions (repro.core.resilience) keep node-stacked inputs resident
    # across rounds; these helpers make placement explicit so only changed
    # blocks move after an elastic re-assignment.

    def place_node_stacked(self, arr):
        """Place a node-stacked array where this executor wants it (padded
        to the executor's node-axis granularity where applicable)."""
        return jnp.asarray(arr)

    def place_broadcast(self, arr):
        """Place an array replicated/shared across all nodes."""
        return jnp.asarray(arr)

    def update_node_rows(self, arr, rows: Sequence[int], new_rows):
        """Return ``arr`` with ``arr[rows[i]] = new_rows[i]`` applied, moving
        only the storage that actually owns those rows."""
        raise NotImplementedError


class LocalExecutor(Executor):
    """All nodes simulated in one process on one device: a single vmapped
    batch for ``map_nodes``, a scan over nodes for the masked combine."""

    name = "local"

    def __init__(self):
        self._jitted: dict = {}

    def _compiled(self, fn: Callable, n_node: int, n_bcast: int):
        key = (fn, n_node, n_bcast)
        if key not in self._jitted:
            in_axes = (0,) * n_node + (None,) * n_bcast
            self._jitted[key] = jax.jit(jax.vmap(fn, in_axes=in_axes))
        return self._jitted[key]

    def map_nodes(self, fn, node_args, broadcast_args=()):
        node_args = tuple(jnp.asarray(a) for a in node_args)
        broadcast_args = tuple(_as_jax_tree(a) for a in broadcast_args)
        return self._compiled(fn, len(node_args), len(broadcast_args))(
            *node_args, *broadcast_args
        )

    def resilient_reduce(self, fn, node_args, broadcast_args, b_full):
        # Host-side span around the compiled combine INVOCATION (dispatch,
        # not device execution — jax returns before the result is ready).
        with trace_span("executor.combine", executor=self.name):
            per_node = self.map_nodes(fn, node_args, broadcast_args)
            return resilient_sum(per_node, jnp.asarray(b_full, jnp.float32))

    @compiled_path("local.masked_reduce", kind="factory")
    def _masked_step_raw(self, fn: Callable, n_node: int, n_bcast: int, iters: int):
        """The UNCOMPILED fused step — solve → select → combine.  Exposed
        separately from :meth:`_compiled_masked` so the Layer-2 jaxpr audit
        (:mod:`repro.analysis.jaxpr_audit`) can trace and instrument the raw
        python callable the hot path actually jits."""

        def step(A, alive, use_override, b_override, *args):
            solved = jax_recovery_masked(A, alive, iters=iters)
            # The override is runtime data, not a branch: degenerate-pattern
            # fallbacks flow through THIS program with use_override=True
            # instead of compiling a second full program.
            b_full = jnp.where(use_override, b_override, solved)
            # One node at a time, like the mesh executor's per-device block.
            return resilient_map_sum(fn, b_full, args[:n_node], args[n_node:]), b_full

        return step

    def _compiled_masked(self, fn: Callable, n_node: int, n_bcast: int, iters: int):
        key = ("masked", fn, n_node, n_bcast, iters)
        if key not in self._jitted:
            self._jitted[key] = jax.jit(self._masked_step_raw(fn, n_node, n_bcast, iters))
        return self._jitted[key]

    def resilient_reduce_masked(
        self, fn, node_args, broadcast_args, A, alive, *, iters: int = 300,
        b_override=None,
    ):
        node_args = tuple(jnp.asarray(a) for a in node_args)
        broadcast_args = tuple(_as_jax_tree(a) for a in broadcast_args)
        A = jnp.asarray(A, jnp.float32)
        use_ov = jnp.asarray(b_override is not None)
        b_ov = (
            jnp.zeros((A.shape[0],), jnp.float32)
            if b_override is None
            else jnp.asarray(b_override, jnp.float32)
        )
        with trace_span(
            "executor.masked_reduce", executor=self.name,
            nodes=int(A.shape[0]), override=b_override is not None,
        ):
            return self._compiled_masked(fn, len(node_args), len(broadcast_args), iters)(
                A, jnp.asarray(alive, bool), use_ov, b_ov,
                *node_args, *broadcast_args,
            )

    def replicated_compute(self, fn, args):
        key = ("replicated", fn)
        if key not in self._jitted:
            self._jitted[key] = jax.jit(fn)
        with trace_span("executor.replicated", executor=self.name):
            return self._jitted[key](*(_as_jax_tree(a) for a in args))

    def update_node_rows(self, arr, rows, new_rows):
        idx = jnp.asarray(list(rows), jnp.int32)
        return jnp.asarray(arr).at[idx].set(jnp.asarray(new_rows))


_LOCAL_SINGLETON: Optional[LocalExecutor] = None
_MESH_SINGLETON = None


def get_executor(spec: Union[None, str, Executor] = None) -> Executor:
    """Resolve an ``executor=`` argument.

    ``None`` / ``"local"`` → the shared :class:`LocalExecutor`;
    ``"mesh"`` → the shared :class:`~repro.launch.distributed.MeshExecutor`
    over all visible devices; an :class:`Executor` instance passes through.
    Singletons are shared so jit caches persist across calls.
    """
    global _LOCAL_SINGLETON, _MESH_SINGLETON
    if spec is None or spec == "local":
        if _LOCAL_SINGLETON is None:
            _LOCAL_SINGLETON = LocalExecutor()
        return _LOCAL_SINGLETON
    if spec == "mesh":
        if _MESH_SINGLETON is None:
            from ..launch.distributed import MeshExecutor  # lazy: core must not pull launch eagerly

            _MESH_SINGLETON = MeshExecutor()
        return _MESH_SINGLETON
    if isinstance(spec, Executor):
        return spec
    raise ValueError(f"unknown executor {spec!r}; expected None, 'local', 'mesh', or an Executor")
