"""Sharded end-to-end execution of the paper pipeline (tentpole of PR 2).

:class:`MeshExecutor` turns the assignment → local solve → straggler mask →
recovery-weighted combine pipeline from a single-process numpy loop into an
actual distributed program:

* **Placement** — the per-node shards packed by
  :func:`repro.core.kmedian.pack_local_shards` (one row per node, exactly the
  rows of the :class:`~repro.core.assignment.Assignment` matrix) are
  ``device_put`` onto a 1-D ``("nodes",)`` device mesh, one contiguous block
  of nodes per device.
* **Local solve** — the algorithm's per-node function (local k-median Lloyd,
  coreset sampling, PCA sketch, cost evaluation …) runs node-parallel under
  ``jax.shard_map``, vmapped over the node block a device owns.
* **Straggler mask** — the recovery weights ``b_full`` (zero at stragglers,
  from :mod:`repro.core.recovery` over an alive mask from
  :mod:`repro.core.stragglers`) enter the compiled step as a *runtime array
  argument*: a new straggler pattern is a new input, never a recompile.
* **Combine** — :meth:`MeshExecutor.resilient_reduce` executes Lemma 3
  (:func:`repro.core.aggregation.resilient_sum` within each device's block,
  :func:`repro.core.aggregation.resilient_psum` across the mesh axis) on
  device; only the final replicated scalar/summary returns to the host.

The same program runs on 1 host device or under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (or a real TPU/GPU
mesh) with no code change; the inner functions are identical to
:class:`~repro.core.executor.LocalExecutor`'s, so costs agree to f32
round-off (pinned at 1e-5 by tests/test_distributed_executor.py).

Node-count handling: ``s`` nodes are padded up to a multiple of the device
count with zero rows (zero data, zero weights, zero recovery weight — inert
in every weighted statistic, exactly like the in-shard padding rows).
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from ..analysis import compiled_path
from ..core.aggregation import resilient_map_sum, resilient_psum, resilient_sum
from ..core.executor import Executor
from ..core.recovery import jax_recovery_masked
from ..obs import trace_span

__all__ = ["MeshExecutor", "node_mesh"]

NODE_AXIS = "nodes"


def node_mesh(devices: Optional[Sequence[jax.Device]] = None):
    """1-D mesh over ``devices`` (default: all visible) with axis "nodes"."""
    devices = tuple(devices) if devices is not None else tuple(jax.devices())
    return jax.make_mesh(
        (len(devices),), (NODE_AXIS,), axis_types=(AxisType.Auto,),
        devices=np.array(devices),
    )


class MeshExecutor(Executor):
    """Run per-node computations node-parallel on a jax device mesh."""

    name = "mesh"

    def __init__(self, devices: Optional[Sequence[jax.Device]] = None):
        self.devices = tuple(devices) if devices is not None else tuple(jax.devices())
        self.mesh = node_mesh(self.devices)
        self._jitted: dict = {}

    @property
    def num_devices(self) -> int:
        return len(self.devices)

    def describe(self) -> str:
        kinds = {d.device_kind for d in self.devices}
        return f"mesh[{self.num_devices}x{'/'.join(sorted(kinds))}]"

    # ------------------------------------------------------------ internals

    def _place(self, arr, spec: P):
        """Explicit placement: shard node-stacked inputs over the mesh.

        ``arr`` may be a single array or an arbitrary pytree (a params dict);
        the sharding applies leaf-wise, so broadcast pytrees replicate whole.
        """
        return jax.device_put(
            jax.tree_util.tree_map(jnp.asarray, arr), NamedSharding(self.mesh, spec)
        )

    def _pad_nodes(self, node_args):
        """Zero-pad the node axis to a device-count multiple.

        Zero rows are inert everywhere downstream: zero data + zero weights
        never contribute to a weighted statistic, and an all-zero PRNG key is
        still a valid key for the (discarded) padded solves.
        """
        s = int(jnp.shape(node_args[0])[0])
        pad = (-s) % self.num_devices
        if pad == 0:
            return tuple(jnp.asarray(a) for a in node_args), s
        out = []
        for a in node_args:
            a = jnp.asarray(a)
            widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
            out.append(jnp.pad(a, widths))
        return tuple(out), s

    @compiled_path("mesh.map_reduce", kind="factory")
    def _compiled(self, fn: Callable, n_node: int, n_bcast: int, reduce_: bool):
        key = (fn, n_node, n_bcast, reduce_)
        if key in self._jitted:
            return self._jitted[key]
        in_axes = (0,) * n_node + (None,) * n_bcast
        inner = jax.vmap(fn, in_axes=in_axes)

        if reduce_:
            # (b_blk, *node_blks, *bcast) -> Lemma-3 combine, replicated out.
            def step(b_blk, *args):
                per_node = inner(*args)
                local = resilient_sum(per_node, b_blk)
                return resilient_psum(local, jnp.float32(1.0), NODE_AXIS)

            in_specs = (P(NODE_AXIS),) * (1 + n_node) + (P(),) * n_bcast
            out_specs = P()
        else:
            def step(*args):
                return inner(*args)

            in_specs = (P(NODE_AXIS),) * n_node + (P(),) * n_bcast
            out_specs = P(NODE_AXIS)

        sharded = jax.shard_map(
            step, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        self._jitted[key] = jax.jit(sharded)
        return self._jitted[key]

    # -------------------------------------------------------------- seam API

    def map_nodes(self, fn, node_args, broadcast_args=()):
        node_args, s = self._pad_nodes(node_args)
        node_args = tuple(self._place(a, P(NODE_AXIS)) for a in node_args)
        broadcast_args = tuple(self._place(a, P()) for a in broadcast_args)
        out = self._compiled(fn, len(node_args), len(broadcast_args), reduce_=False)(
            *node_args, *broadcast_args
        )
        return jax.tree_util.tree_map(lambda leaf: leaf[:s], out)

    def resilient_reduce(self, fn, node_args, broadcast_args, b_full):
        b_full = jnp.asarray(b_full, jnp.float32)
        node_args, _ = self._pad_nodes((b_full,) + tuple(node_args))
        node_args = tuple(self._place(a, P(NODE_AXIS)) for a in node_args)
        broadcast_args = tuple(self._place(a, P()) for a in broadcast_args)
        with trace_span(
            "executor.combine", executor=self.name, devices=self.num_devices
        ):
            return self._compiled(fn, len(node_args) - 1, len(broadcast_args), reduce_=True)(
                *node_args, *broadcast_args
            )

    @compiled_path("mesh.masked_reduce", kind="factory")
    def _masked_step_raw(self, fn: Callable, n_node: int, n_bcast: int, iters: int):
        """The UNCOMPILED fused per-device step (must run under shard_map) —
        exposed for the Layer-2 jaxpr audit, same contract as
        :meth:`repro.core.executor.LocalExecutor._masked_step_raw`: each
        device accumulates its node block one node at a time
        (:func:`repro.core.aggregation.resilient_map_sum`), then psums."""

        def step(A, alive, use_override, b_override, *args):
            solved = jax_recovery_masked(A, alive, iters=iters)
            # Runtime select, not a Python branch: the fallback path shares
            # this one compiled program (see Executor.resilient_reduce_masked).
            b_full = jnp.where(use_override, b_override, solved)
            blk = args[0].shape[0]  # this device's node-block size (static)
            i = jax.lax.axis_index(NODE_AXIS)
            b_blk = jax.lax.dynamic_slice(b_full, (i * blk,), (blk,))
            local = resilient_map_sum(fn, b_blk, args[:n_node], args[n_node:])
            return resilient_psum(local, jnp.float32(1.0), NODE_AXIS), b_full

        return step

    def _compiled_masked(self, fn: Callable, n_node: int, n_bcast: int, iters: int):
        """Fused mask → on-device recovery solve → Lemma-3 psum.

        ``A`` and ``alive`` enter replicated (``P()``); every device runs the
        (small, O(s·n)) projected-gradient solve redundantly and slices its
        own node block of ``b_full`` by ``axis_index`` — cheaper than a
        gather, and the straggler pattern stays runtime data.
        """
        key = ("masked", fn, n_node, n_bcast, iters)
        if key in self._jitted:
            return self._jitted[key]
        step = self._masked_step_raw(fn, n_node, n_bcast, iters)
        in_specs = (P(), P(), P(), P()) + (P(NODE_AXIS),) * n_node + (P(),) * n_bcast
        out_specs = (P(), P())
        sharded = jax.shard_map(
            step, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
        self._jitted[key] = jax.jit(sharded)
        return self._jitted[key]

    def resilient_reduce_masked(
        self, fn, node_args, broadcast_args, A, alive, *, iters: int = 300,
        b_override=None,
    ):
        node_args, _ = self._pad_nodes(tuple(node_args))
        s_pad = int(jnp.shape(node_args[0])[0])
        A = jnp.asarray(A, jnp.float32)
        alive = jnp.asarray(alive, bool)
        use_ov = jnp.asarray(b_override is not None)
        b_ov = (
            jnp.zeros((A.shape[0],), jnp.float32)
            if b_override is None
            else jnp.asarray(b_override, jnp.float32)
        )
        pad = s_pad - A.shape[0]
        if pad:  # padded node rows: no shards, never alive → b pinned to 0
            A = jnp.pad(A, ((0, pad), (0, 0)))
            alive = jnp.pad(alive, (0, pad))
            b_ov = jnp.pad(b_ov, (0, pad))
        node_args = tuple(self._place(a, P(NODE_AXIS)) for a in node_args)
        broadcast_args = tuple(self._place(a, P()) for a in broadcast_args)
        # Span covers the host-side dispatch of the sharded step (placement
        # already done above); device execution is asynchronous beyond it.
        with trace_span(
            "executor.masked_reduce", executor=self.name,
            nodes=int(A.shape[0]), devices=self.num_devices,
            override=b_override is not None,
        ):
            return self._compiled_masked(fn, len(node_args), len(broadcast_args), iters)(
                self._place(A, P()), self._place(alive, P()),
                self._place(use_ov, P()), self._place(b_ov, P()),
                *node_args, *broadcast_args,
            )

    def replicated_compute(self, fn, args):
        """Genuinely redundant execution: the same program on EVERY device.

        Inputs are placed replicated (``P()``) and the computation runs under
        ``shard_map`` with fully-replicated specs, so each mesh device owns a
        complete copy of the result — the streaming layer's tree compactions
        survive any straggling device without re-execution or data movement.
        The host fetches from whichever replica is local; numerically all
        replicas are identical (same program, same inputs).
        """
        key = ("replicated", fn, len(args))
        if key not in self._jitted:
            def step(*a):
                return fn(*a)

            n = len(args)
            sharded = jax.shard_map(
                step, mesh=self.mesh, in_specs=(P(),) * n, out_specs=P(),
                check_vma=False,
            )
            self._jitted[key] = jax.jit(sharded)
        placed = tuple(self._place(a, P()) for a in args)
        with trace_span(
            "executor.replicated", executor=self.name, devices=self.num_devices
        ):
            return self._jitted[key](*placed)

    # --------------------------------------------------- placement helpers

    def place_node_stacked(self, arr):
        """Pad to the device-count multiple and shard over the node axis."""
        (arr,), _ = self._pad_nodes((arr,))
        return self._place(arr, P(NODE_AXIS))

    def place_broadcast(self, arr):
        return self._place(arr, P())

    def update_node_rows(self, arr, rows, new_rows):
        """Re-place ONLY the device blocks that own ``rows``.

        Per-device surgery: pull back just the affected devices' node blocks,
        patch the changed rows, `device_put` those blocks to their device, and
        reassemble the global array from the (mostly untouched) single-device
        shards — the unchanged blocks never cross the host↔device boundary.
        """
        rows = [int(r) for r in rows]
        new_rows = np.asarray(new_rows)
        if not isinstance(arr, jax.Array) or arr.sharding != NamedSharding(
            self.mesh, P(NODE_AXIS)
        ):
            arr = self.place_node_stacked(arr)
        blk = arr.shape[0] // self.num_devices
        by_dev: dict[int, list[int]] = {}
        for j, r in enumerate(rows):
            by_dev.setdefault(r // blk, []).append(j)
        shard_data = {s.device: s.data for s in arr.addressable_shards}
        for dev_idx, updates in by_dev.items():
            dev = self.devices[dev_idx]
            block = np.array(shard_data[dev])  # copy: shard views are read-only
            for j in updates:
                block[rows[j] - dev_idx * blk] = new_rows[j]
            shard_data[dev] = jax.device_put(block, dev)
        return jax.make_array_from_single_device_arrays(
            arr.shape, arr.sharding, [shard_data[d] for d in self.devices]
        )
