#!/usr/bin/env python3
"""Drive the system's main paths once on a TPU, each against a plain reference.

    python chip_smoke.py              # one chip: the cluster, serve and train phases
    python chip_smoke.py --chips 4    # four chips: MeshExecutor against LocalExecutor only
    python chip_smoke.py --rehearse   # tiny sizes on the CPU; never prints "ok": true

Phases on one chip (device 0), all through the entry points a user calls:

* ``cluster`` — paper Algorithm 1 through ``ResilienceSession.kmedian`` at
  the widths of ``configs/paper_kmedian.py`` ``production_scale()`` (d=64,
  k=1024), n=2^21 seeded points on s=8 nodes under a cyclic ℓ=2 placement,
  plus ``step_cost`` rounds under the ``deadline`` straggler scenario.  The
  reference is the same call with ``impl="xla_ref"`` on a session whose
  executor runs each node's solve as its own program.
* ``serve`` — ``StreamingSession(d=64, k=1024, num_nodes=8)`` ingests 2^20
  points and solves; ``ServingFrontend`` answers query batches for two
  tenants.  The reference is ``assign_min_ref`` on the same centers.
* ``train`` — ``Trainer`` at qwen3-1.7b's published widths with the depth
  cut to fit one chip, on the fused device-recovery path (fractional
  repetition, 4 groups, ℓ=2), seq 1024, 3 steps.  The reference is step 0
  with ``ModelContext(attn_impl="xla_ref")``.

With ``--chips 4`` the script runs only what exists across chips: the
cluster step and two train steps with ``executor="mesh"`` over all four
chips against ``executor="local"`` on device 0, and checks that each chip
holds a quarter of the nodes' rows.

Each phase prints one line: its set-up seconds, its compile-and-run seconds
(compilation included; the persistent compile cache is on) and the
process's peak device memory so far.  A mismatch, a failed autotune
measurement or a failed warm-up raises, and the script exits non-zero
without a result line.  It also exits non-zero where JAX finds no TPU.
The last line of a passing chip run is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# Step-cost parity at fixed centers: both impls evaluate the same f32
# expansion ‖x‖² + ‖c‖² − 2·x·c per point; only the summation order differs.
COST_RTOL = 1e-4
# Algorithm 1 end to end.  The reference pipeline runs every node's solve
# as its own program (_per_node_executor): on the chip a vmapped XLA
# argmin over 8 nodes × 2^19 rows × 1024 centers returns wrong indices and
# infinite distances for six of the eight nodes (PERF.md), and a vmapped
# reference took that program.  Both runs make the same seeded draws, but their f32
# distances differ in summation order, so a d-sampling draw or a Lloyd
# assignment at a near-tie can part them, and from there they follow
# different, equally valid trajectories, as far apart as two seeds.  With
# as many planted clusters as centers, k-median++ misses some clusters and
# seeds differ by several percent (chip: 2.3e-3 between the kernels and the
# per-node reference; CPU: 10% between three seeds); with k/4 planted
# clusters every cluster gets centers (chip: 3.2e-5; CPU: 6e-4 between
# three seeds), so the data plants k/4 and the bound sits above the seed
# spread.  The final cost is also recomputed at the run's own centers by
# the reference (COST_RTOL).
PIPELINE_RTOL = 1e-3
# Serving answers: distances from the same centers, f32.
DIST_RTOL = 1e-4
# Train step 0, loss and gradient norm: bf16 compute; the two attention
# impls round differently (chip: 1.9e-6 and 1.5e-6 apart).
TRAIN_RTOL = 1e-2
# Mesh against local on the same chips' kernels: only the combine order
# differs (psum across chips against a scan on one).
MESH_RTOL = 1e-5


@dataclasses.dataclass(frozen=True)
class Sizes:
    n: int                  # clustering points
    d: int
    k: int
    nodes: int
    ell: int
    cost_rounds: int        # step_cost rounds under the deadline scenario
    local_iters: int
    coord_iters: int
    stream_points: int      # tenant "a"; tenant "b" ingests a quarter
    stream_batch: int
    leaf: int
    coreset: int
    query_batches: int
    query_rows: int
    train_layers: int
    seq_len: int
    train_steps: int
    smoke_model: bool       # the model's smoke widths (rehearsal only)


# On the chip: the repo's own widths.  The depth of qwen3-1.7b is cut to one
# layer: the compiled fused step at published widths needs ~3.7 GiB of
# temporaries beside 2.5 GiB of parameters, 2.5 GiB of combined gradients and
# 5 GiB of Adam moments at one layer, and each further layer adds ~1.7 GiB
# (memory_analysis of the step compiled for a v5e), against 16 GiB.
CHIP = Sizes(
    n=1 << 21, d=64, k=1024, nodes=8, ell=2, cost_rounds=4,
    local_iters=20, coord_iters=40,
    stream_points=1 << 20, stream_batch=1 << 16, leaf=16384, coreset=4096,
    query_batches=48, query_rows=256,
    train_layers=1, seq_len=1024, train_steps=3, smoke_model=False,
)
REHEARSE = Sizes(
    n=4096, d=16, k=32, nodes=8, ell=2, cost_rounds=3,
    local_iters=3, coord_iters=4,
    stream_points=8192, stream_batch=2048, leaf=512, coreset=128,
    query_batches=8, query_rows=32,
    train_layers=1, seq_len=128, train_steps=3, smoke_model=True,
)


def _peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def _report(phase: str, device, **fields) -> None:
    parts = [f"{k}={v!r}" if isinstance(v, float) else f"{k}={v}" for k, v in fields.items()]
    parts.append(f"peak_bytes_in_use={_peak_bytes(device)}")
    print(f"[{phase}] " + " ".join(parts), flush=True)


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _mixture(rng, n: int, d: int, clusters: int) -> np.ndarray:
    """n points around ``clusters`` well-separated planted centers (scale
    10, unit noise)."""
    centers = rng.standard_normal((clusters, d), dtype=np.float32) * 10.0
    labels = rng.integers(0, clusters, n)
    return centers[labels] + rng.standard_normal((n, d), dtype=np.float32)


def _per_node_executor():
    """The reference pipeline's executor: one jitted call per node, stacked
    outside the program, so no per-node program is vmapped (see PERF.md on
    the vmapped ``xla_ref`` argmin)."""
    import jax
    import jax.numpy as jnp

    from repro.core.executor import LocalExecutor

    class PerNodeExecutor(LocalExecutor):
        name = "per_node"

        def map_nodes(self, fn, node_args, broadcast_args=()):
            if fn not in self._jitted:
                self._jitted[fn] = jax.jit(fn)
            f = self._jitted[fn]
            node_args = tuple(jnp.asarray(a) for a in node_args)
            outs = [
                f(*(a[i] for a in node_args), *broadcast_args)
                for i in range(node_args[0].shape[0])
            ]
            return jax.tree_util.tree_map(lambda *o: jnp.stack(o), *outs)

    return PerNodeExecutor()


def _check_autotune() -> None:
    from repro.kernels import autotune

    info = autotune.autotune_cache_info()
    _check(info["errors"] == 0, f"{info['errors']} autotune measurements failed")
    _check(info["warmup_errors"] == 0, f"{info['warmup_errors']} warm-up entries failed")


# ------------------------------------------------------------------ cluster


def _cost_rounds(sz: Sizes, sessions: dict, pts, centers, seed: int, rtol: float, ref: str):
    """step_cost rounds under the deadline scenario.  ``sessions`` maps a
    name to ``(session, impl)``; every cost must agree with ``ref``'s."""
    from repro.core.stragglers import make_scenario

    scen = make_scenario("deadline", sz.nodes, seed=seed)
    worst, alive, t_first = 0.0, None, None
    for r in range(sz.cost_rounds):
        step = next(scen)
        mask = np.asarray(step.alive, bool)
        if not mask.any():
            continue
        alive = mask
        t0 = time.perf_counter()
        costs = {}
        for name, (sess, impl) in sessions.items():
            sess.observe(step)
            costs[name] = sess.step_cost(pts, centers, mask, median=True, impl=impl)
        if t_first is None:
            t_first = time.perf_counter() - t0
        for name, c in costs.items():
            rel = _rel(c, costs[ref])
            _check(np.isfinite(c), f"round {r}: {name} step_cost is {c}")
            worst = max(worst, rel)
            _check(
                rel <= rtol,
                f"round {r}: {name} step_cost {c} vs {ref} {costs[ref]} (rel {rel:.3g} > {rtol})",
            )
    _check(alive is not None, "the scenario left no round with an alive node")
    return worst, alive, t_first


def phase_cluster(sz: Sizes, device, seed: int) -> None:
    import jax.numpy as jnp

    from repro.core.assignment import make_assignment
    from repro.core.kmeans import clustering_cost
    from repro.core.resilience import ResilienceSession

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    pts = _mixture(rng, sz.n, sz.d, sz.k // 4)
    assignment = make_assignment("cyclic", sz.n, sz.nodes, ell=sz.ell)
    session = ResilienceSession(assignment)
    ref_session = ResilienceSession(assignment, executor=_per_node_executor())
    centers = pts[rng.choice(sz.n, sz.k, replace=False)]
    setup = time.perf_counter() - t0

    t0 = time.perf_counter()
    worst, alive, t_first = _cost_rounds(
        sz, {"pallas": (session, "auto"), "xla_ref": (ref_session, "xla_ref")},
        pts, centers, seed, COST_RTOL, ref="xla_ref",
    )
    t_cost = time.perf_counter() - t0

    kw = dict(local_iters=sz.local_iters, coord_iters=sz.coord_iters, seed=seed)
    t0 = time.perf_counter()
    got = session.kmedian(pts, sz.k, alive, **kw)
    t_alg1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = ref_session.kmedian(pts, sz.k, alive, impl="xla_ref", **kw)
    t_ref = time.perf_counter() - t0
    # The run's own centers, costed by the reference.
    at_centers = float(clustering_cost(
        jnp.asarray(pts), jnp.asarray(got.centers), median=True, impl="xla_ref"
    ))
    gap = _rel(got.cost, want.cost)
    _check(np.isfinite(got.cost), f"Algorithm 1 cost is {got.cost}")
    _check(
        _rel(got.cost, at_centers) <= COST_RTOL,
        f"Algorithm 1 cost {got.cost} vs xla_ref at its centers {at_centers}",
    )
    _check(
        gap <= PIPELINE_RTOL,
        f"Algorithm 1 cost {got.cost} vs xla_ref {want.cost} (rel {gap:.3g} > {PIPELINE_RTOL})",
    )
    _check_autotune()
    _report(
        "cluster", device, n=sz.n, d=sz.d, k=sz.k, nodes=sz.nodes,
        stragglers=int((~alive).sum()), setup_s=setup,
        step_cost_first_round_compile_run_s=t_first, step_cost_rounds_s=t_cost,
        step_cost_worst_rel=worst, alg1_compile_run_s=t_alg1,
        alg1_ref_compile_run_s=t_ref, alg1_cost=got.cost, alg1_ref_cost=want.cost,
        alg1_rel=gap, alg1_cost_at_centers_rel=_rel(got.cost, at_centers),
    )


# -------------------------------------------------------------------- serve


def phase_serve(sz: Sizes, device, seed: int) -> None:
    import jax.numpy as jnp

    from repro.kernels.pairwise_dist.ref import assign_min_ref
    from repro.serve import ServingFrontend
    from repro.stream import StreamingSession

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed + 10)
    data = {
        name: _mixture(np.random.default_rng(seed + i), n, sz.d, sz.k // 4)
        for i, (name, n) in enumerate((("a", sz.stream_points), ("b", sz.stream_points // 4)))
    }
    setup = time.perf_counter() - t0

    t0 = time.perf_counter()
    fe = ServingFrontend()
    for i, (name, pts) in enumerate(data.items()):
        sess = StreamingSession(
            d=sz.d, k=sz.k, num_nodes=sz.nodes, leaf_size=sz.leaf,
            coreset_size=sz.coreset, seed=seed + i,
        )
        fe.add_tenant(name, sess)
        for lo in range(0, len(pts), sz.stream_batch):
            sess.ingest(pts[lo : lo + sz.stream_batch])
        sess.solve()
    t_ingest = time.perf_counter() - t0
    report = fe.warmup()
    _check(report.errors == 0, f"serving warm-up: {report.errors} entries failed")

    t0 = time.perf_counter()
    asked = []
    for i in range(sz.query_batches):
        name = "ab"[i % 2]
        pts = data[name]
        q = pts[rng.integers(0, len(pts), sz.query_rows)] + rng.standard_normal(
            (sz.query_rows, sz.d), dtype=np.float32
        )
        asked.append((name, q, fe.submit(name, q)))
        if i % 4 == 3:
            fe.drain()
    fe.drain()
    t_query = time.perf_counter() - t0

    ties = 0
    for name, q, ticket in asked:
        _check(ticket.done and ticket.error is None, f"query for {name} not answered: {ticket.error}")
        centers = fe.tenant(name).session.centers
        ref_idx, ref_d2 = (np.asarray(a) for a in assign_min_ref(jnp.asarray(q), jnp.asarray(centers)))
        got_idx = np.asarray(ticket.result.indices)
        got_dist = np.asarray(ticket.result.distances)
        ref_dist = np.sqrt(np.maximum(ref_d2, 0.0))
        np.testing.assert_allclose(got_dist, ref_dist, rtol=DIST_RTOL, atol=DIST_RTOL)
        diff = got_idx != ref_idx
        if diff.any():
            # Near-ties only: the served center must be as near as the best.
            qd = q[diff].astype(np.float64)
            d_got = np.sum((qd - centers[got_idx[diff]]) ** 2, axis=1)
            d_ref = np.sum((qd - centers[ref_idx[diff]]) ** 2, axis=1)
            _check(
                np.all(np.abs(d_got - d_ref) <= DIST_RTOL * np.maximum(d_ref, 1.0)),
                f"{int(diff.sum())} served indices differ from the reference beyond a tie",
            )
            ties += int(diff.sum())
    _check_autotune()
    _report(
        "serve", device, d=sz.d, k=sz.k, tenants=len(data),
        ingested=sum(len(p) for p in data.values()), setup_s=setup,
        ingest_solve_compile_run_s=t_ingest, query_batches=len(asked),
        rows=len(asked) * sz.query_rows, query_compile_run_s=t_query,
        dispatches=fe.dispatches, index_ties=ties,
    )


# -------------------------------------------------------------------- train


def _train_config(sz: Sizes):
    from repro.configs import qwen3_1_7b

    base = qwen3_1_7b.smoke_config() if sz.smoke_model else qwen3_1_7b.config()
    return dataclasses.replace(base, n_layers=sz.train_layers)


def _train_losses(sz: Sizes, cfg, seed: int, steps: int, *, executor="local", attn="auto",
                  warm_start=True):
    from repro.models.transformer import ModelContext
    from repro.train.optimizer import AdamWConfig
    from repro.train.trainer import Trainer, TrainerConfig

    tcfg = TrainerConfig(
        device_recovery=True, scheme="fr", num_groups=4, num_shards=2, redundancy=2,
        microbatch=1, patch_headroom=0, seq_len=sz.seq_len, steps=steps, seed=seed,
        executor=executor, warm_start=warm_start,
    )
    tr = Trainer(
        cfg, tcfg, opt_cfg=AdamWConfig(total_steps=sz.train_steps),
        ctx=ModelContext(attn_impl=attn),
    )
    tr.run()
    if tr.warmup_report is not None:
        _check(tr.warmup_report.errors == 0, "trainer warm-up failed")
    losses = [h.get("loss") for h in tr.history]
    grad_norms = [h.get("grad_norm") for h in tr.history]
    del tr
    gc.collect()
    return losses, grad_norms


def _first_loss(losses):
    for i, v in enumerate(losses):
        if v is not None:
            return i, v
    raise AssertionError("every step was skipped")


def phase_train(sz: Sizes, device, seed: int) -> None:
    cfg = _train_config(sz)
    t0 = time.perf_counter()
    ref, ref_gn = _train_losses(sz, cfg, seed, 1, attn="xla_ref", warm_start=False)
    t_ref = time.perf_counter() - t0
    t0 = time.perf_counter()
    got, got_gn = _train_losses(sz, cfg, seed, sz.train_steps)
    t_run = time.perf_counter() - t0
    _check(all(v is None or np.isfinite(v) for v in got + got_gn), f"non-finite {got} {got_gn}")
    i, want = _first_loss(ref)
    _check(got[i] is not None, f"step {i} ran in the reference but not in the run")
    # The loss checks the kernel's forward, the gradient norm its custom VJP.
    for what, a, b in (("loss", got[i], want), ("grad_norm", got_gn[i], ref_gn[i])):
        _check(
            _rel(a, b) <= TRAIN_RTOL,
            f"step-{i} {what} {a} vs xla_ref {b} (rel {_rel(a, b):.3g} > {TRAIN_RTOL})",
        )
    _check_autotune()
    _report(
        "train", device, model=cfg.name, d_model=cfg.d_model, layers=cfg.n_layers,
        vocab=cfg.vocab, seq_len=sz.seq_len, steps=len(got),
        ref_step_compile_run_s=t_ref, run_compile_run_s=t_run,
        losses="/".join(repr(v) if v is not None else "skip" for v in got),
        ref_loss=want, loss_rel=_rel(got[i], want), grad_norm=got_gn[i],
        ref_grad_norm=ref_gn[i], grad_norm_rel=_rel(got_gn[i], ref_gn[i]),
    )


# --------------------------------------------------------------- four chips


def phase_mesh(sz: Sizes, devices, seed: int) -> None:
    from repro.core.assignment import make_assignment
    from repro.core.resilience import ResilienceSession

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    pts = _mixture(rng, sz.n, sz.d, sz.k // 4)
    assignment = make_assignment("cyclic", sz.n, sz.nodes, ell=sz.ell)
    sessions = {
        "local": (ResilienceSession(assignment, executor="local"), "auto"),
        "mesh": (ResilienceSession(assignment, executor="mesh"), "auto"),
    }
    centers = pts[rng.choice(sz.n, sz.k, replace=False)]
    setup = time.perf_counter() - t0

    t0 = time.perf_counter()
    worst, alive, _ = _cost_rounds(sz, sessions, pts, centers, seed, MESH_RTOL, ref="local")
    t_cost = time.perf_counter() - t0

    # Placement: each chip holds s/len(devices) nodes' rows, none holds all.
    mesh = sessions["mesh"][0]
    _, _, _, ex, xs, _ = mesh.prepare(pts, alive)
    placed = ex.place_node_stacked(xs)
    shards = placed.addressable_shards
    per_chip = sz.nodes // len(devices)
    _check(
        sorted(s.device.id for s in shards) == sorted(d.id for d in devices),
        f"node rows live on {[s.device.id for s in shards]}, not on every chip",
    )
    _check(
        all(s.data.shape[0] == per_chip for s in shards),
        f"rows per chip {[s.data.shape[0] for s in shards]}, expected {per_chip} each",
    )
    del sessions, mesh, ex, xs, placed, shards, pts  # device 0 needs its memory back
    gc.collect()

    cfg = _train_config(sz)
    t0 = time.perf_counter()
    local, local_gn = _train_losses(sz, cfg, seed, 2, executor="local")
    meshed, mesh_gn = _train_losses(sz, cfg, seed, 2, executor="mesh")
    t_train = time.perf_counter() - t0
    for i, (a, b) in enumerate(zip(meshed, local)):
        _check((a is None) == (b is None), f"step {i} skipped on one executor only")
        if a is not None:
            _check(np.isfinite(a), f"mesh step {i} loss is {a}")
            _check(_rel(a, b) <= MESH_RTOL, f"step {i}: mesh loss {a} vs local {b}")
    _check_autotune()
    _report(
        "mesh", devices[0], chips=len(devices), n=sz.n, nodes=sz.nodes,
        rows_per_chip=per_chip, setup_s=setup, step_cost_rounds_compile_run_s=t_cost,
        step_cost_worst_rel=worst, train_compile_run_s=t_train,
        local_losses="/".join(repr(v) for v in local if v is not None),
        mesh_losses="/".join(repr(v) for v in meshed if v is not None),
        local_grad_norms="/".join(repr(v) for v in local_gn if v is not None),
        mesh_grad_norms="/".join(repr(v) for v in mesh_gn if v is not None),
    )


# --------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU with Pallas in interpret mode")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.rehearse:
        os.environ["REPRO_PALLAS_INTERPRET"] = "1"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
            )

    import jax

    from repro.caches import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices", file=sys.stderr)
        return 2
    enable_compile_cache()
    sz = REHEARSE if args.rehearse else CHIP
    t_start = time.perf_counter()
    try:
        if args.chips == 4:
            phase_mesh(sz, devices[:4], args.seed)
        else:
            for phase in (phase_cluster, phase_serve, phase_train):
                phase(sz, devices[0], args.seed)
                gc.collect()
    except Exception:
        traceback.print_exc()
        return 1
    print(f"[total] seconds={time.perf_counter() - t_start!r}", flush=True)
    if args.rehearse:
        print("chip_smoke: rehearsal passed (not a chip run)", flush=True)
        return 0
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
