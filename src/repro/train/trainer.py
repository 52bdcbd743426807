"""The training loop: redundant pipeline + deadline straggling + recovery
weighting + checkpoint/restart.  This is the host-side orchestration that a
real cluster's per-step control plane would run.

Two recovery paths:

* **Host path** (default, ``device_recovery=False``) — the per-step alive
  mask is solved on the host (LP/NNLS via the plan's session cache) and the
  resulting ``group_weights`` vector enters the jitted step as data.  Exact,
  but every previously-unseen straggler pattern costs one host solve.
* **Mesh-native path** (``device_recovery=True``) — the tentpole: per-group
  gradients run through ``Executor.resilient_reduce_masked``, so the
  recovery solve (projected gradient over the runtime alive mask) happens
  INSIDE the compiled train step: zero host solves and zero recompiles on
  unseen patterns.  Group token blocks live device-resident (node-stacked,
  one row per DP group, pre-packed for ``resident_steps`` step batches);
  when the session's :class:`~repro.core.resilience.ElasticPolicy`
  re-replicates at-risk shards away from persistent stragglers, the trainer
  re-packs ONLY the moved groups' rows and re-places them via
  ``Executor.update_node_rows`` (a patch that outgrows the headroom
  capacity triggers a counted full re-place instead).  Degenerate patterns
  (some shard with zero alive replicas) fall back to the host-solved
  best-effort weights rather than silently dropping the lost shards' mass
  on device.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import compiled_path
from ..core.resilience import ElasticPolicy, ResilienceSession
from ..obs import default_registry, trace_span
from ..kernels import autotune
from ..core.stragglers import StragglerScenario, make_scenario
from ..data.pipeline import RedundantDataPipeline
from ..models import transformer as T
from ..models.registry import ModelConfig
from .checkpoint import latest_step, restore_checkpoint, save_checkpoint
from .compression import CompressionConfig
from .elastic import ElasticGroupManager
from .optimizer import AdamWConfig
from .resilient import make_plan
from .train_step import (
    TrainState,
    init_train_state,
    make_group_grad_fn,
    make_recovered_apply_fn,
    make_train_step,
)

__all__ = ["TrainerConfig", "Trainer"]


# Process-wide jit caches keyed on the (hashable, frozen) config objects:
# trainers are cheap to construct (tests build dozens), the lowered step is
# not — a per-instance ``jax.jit`` re-lowers the whole model each time.
@functools.lru_cache(maxsize=None)
def _jitted_train_step(cfg, ctx, opt_cfg, compression):
    return jax.jit(make_train_step(cfg, ctx, opt_cfg, compression=compression))


@functools.lru_cache(maxsize=None)
def _jitted_apply_fn(opt_cfg, num_shards, compression, moe=None):
    # The state is donated: the update is written in place, so a step never
    # holds two copies of the parameters and optimizer moments (at published
    # model widths a second copy does not fit a chip).
    return jax.jit(
        make_recovered_apply_fn(
            opt_cfg, num_shards, compression=compression, moe=moe),
        donate_argnums=0,
    )


_MOE_KEYS = ("moe_local_pairs", "moe_load_max_ratio")


def _count_moe(host: dict) -> None:
    """A step's routing counts into the registry: ``moe_local_pairs`` (pairs
    on the held experts of the tokens the update covers, over every layer),
    ``moe_steps`` and the gauge ``moe_load_max_ratio``.  ``moe_dropped_pairs``
    stays 0: the expert layer's rows hold every routing (``models/moe.py``)."""
    reg = default_registry()
    reg.counter("moe_local_pairs", help="pairs on the held experts, covered tokens").inc(
        float(host["moe_local_pairs"]))
    reg.counter("moe_steps", help="steps that ran the expert layers").inc(1)
    reg.counter("moe_dropped_pairs", help="routed pairs left uncomputed: none, dropless")
    reg.gauge("moe_load_max_ratio", help="busiest held expert over the mean").set(
        float(host["moe_load_max_ratio"]))


@dataclasses.dataclass
class TrainerConfig:
    num_groups: int = 8
    num_shards: int = 8
    redundancy: int = 2
    scheme: str = "cyclic"
    microbatch: int = 2
    seq_len: int = 128
    steps: int = 100
    ckpt_every: int = 25
    ckpt_dir: Optional[str] = None
    ckpt_keep: int = 3
    seed: int = 0
    simulate_stragglers: bool = True
    straggler_scenario: str = "deadline"  # any repro.core.stragglers scenario
    straggler_deadline: float = 2.0
    scenario_kwargs: Optional[dict] = None  # extra make_scenario kwargs
                                            # (e.g. path= for trace replay)
    compression: Optional[CompressionConfig] = None
    # ---- mesh-native resilient path (on-device gradient recovery) ----
    device_recovery: bool = False  # recovery solve inside the compiled step
    executor: str = "local"        # "local" (vmap) or "mesh" (shard_map);
                                   # only consumed by the device_recovery
                                   # path (enforced in Trainer.__init__)
    elastic_patience: int = 0      # >0 arms ElasticPolicy(patience=...)
    patch_headroom: int = 1        # spare shard slots per group for patches
    warm_start: bool = True        # pre-compile the step (one discarded
                                   # all-alive execution) before the loop;
                                   # REPRO_WARM_START=0 also disables it
    resident_steps: int = 4        # device-resident step batches, cycled by
                                   # step % resident_steps — the fused path
                                   # trains over this FIXED pool (epoch-style
                                   # revisiting), unlike the host path's
                                   # fresh pipeline.batch(step) every step;
                                   # raise it for long runs
    recovery_iters: Optional[int] = None  # PGD iters (default: env/300)


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        tcfg: TrainerConfig,
        opt_cfg: Optional[AdamWConfig] = None,
        ctx: Optional[T.ModelContext] = None,
    ):
        self.cfg = cfg
        self.tcfg = tcfg
        self.opt_cfg = opt_cfg or AdamWConfig(total_steps=tcfg.steps)
        self.ctx = ctx or T.ModelContext()
        if not tcfg.device_recovery and tcfg.executor != "local":
            raise ValueError(
                f"executor={tcfg.executor!r} is only consumed by the "
                "device_recovery path; the host path always runs the "
                "single-process jitted step (set device_recovery=True)"
            )
        # The plan's session owns the executor, the elastic policy, and the
        # pattern cache — the trainer is the third full consumer of
        # ResilienceSession (after the batch and streaming runtimes).
        session_kwargs = None
        if tcfg.device_recovery:
            session_kwargs = dict(
                executor=tcfg.executor,
                elastic=ElasticPolicy(
                    enabled=tcfg.elastic_patience > 0,
                    patience=max(1, tcfg.elastic_patience),
                ),
                device_iters=tcfg.recovery_iters,
            )
        plan = make_plan(
            tcfg.num_groups, tcfg.num_shards,
            redundancy=tcfg.redundancy, scheme=tcfg.scheme,
            session_kwargs=session_kwargs,
        )
        self.plan = plan
        self.elastic = ElasticGroupManager(plan)
        self.pipeline = RedundantDataPipeline(
            plan, vocab=cfg.vocab, microbatch=tcfg.microbatch,
            seq_len=tcfg.seq_len, seed=tcfg.seed,
        )
        # Straggling arrives through the scenario iterator protocol — the
        # same stream type the ResilienceSession and bench_scenarios consume.
        scen_kw = {}
        if tcfg.straggler_scenario in ("iid", "fixed", "deadline"):
            scen_kw["seed"] = tcfg.seed + 1
        if tcfg.straggler_scenario == "deadline":
            scen_kw["deadline"] = tcfg.straggler_deadline
        scen_kw.update(tcfg.scenario_kwargs or {})
        self.scenario: StragglerScenario = make_scenario(
            tcfg.straggler_scenario, tcfg.num_groups,
            assignment=plan.assignment, **scen_kw,
        )
        if tcfg.device_recovery:
            self._init_device_recovery()
        else:
            self._step_fn = _jitted_train_step(
                cfg, self.ctx, self.opt_cfg, tcfg.compression
            )
        self.history: list[dict] = []
        self.warmup_report: Optional[autotune.WarmupReport] = None

    # ------------------------------------------- mesh-native resident state

    def _init_device_recovery(self) -> None:
        tcfg = self.tcfg
        self._capacity = self.plan.shards_per_group + max(0, tcfg.patch_headroom)
        self._pool = max(1, tcfg.resident_steps)
        # Stable per-trainer function objects: the executor keys its jit
        # cache on fn identity, so these must be created exactly once.
        self._group_fn = make_group_grad_fn(self.cfg, self.ctx)
        self._apply_fn = _jitted_apply_fn(
            self.opt_cfg, self.plan.num_shards, tcfg.compression,
            self.cfg.moe,
        )
        self._place_resident(full=False)
        self.plan.session.add_patch_listener(self._on_patch)

    def _pack_group_rows(self, g: int) -> tuple[np.ndarray, np.ndarray]:
        """(P, C·mb, T) token pool + (C,) validity for group ``g`` under the
        CURRENT assignment."""
        shards = self.plan.current_group_shards(g)
        toks, valid = [], None
        for p in range(self._pool):
            rows, valid = self.pipeline.shard_rows(shards, p, self._capacity)
            toks.append(rows)
        return np.stack(toks, axis=0), valid

    def _place_resident(self, *, full: bool) -> None:
        G = self.plan.num_groups
        packed = [self._pack_group_rows(g) for g in range(G)]
        tokens = np.stack([t for t, _ in packed], axis=0)  # (G, P, C·mb, T)
        valid = np.stack([v for _, v in packed], axis=0)   # (G, C)
        ex = self.plan.session.executor
        self._res_tokens = ex.place_node_stacked(tokens)
        self._res_valid = ex.place_node_stacked(valid)
        if full:
            self.plan.session.stats.full_repacks += 1

    def _on_patch(self, moved: list[int], old_m: int, new_m: int) -> None:
        """Patch-aware data movement: re-place ONLY the moved groups' token
        blocks (``Executor.update_node_rows``); a patch that outgrew the
        slot capacity forces a counted full re-place at the new capacity."""
        if new_m > self._capacity:
            self._capacity = new_m + max(0, self.tcfg.patch_headroom)
            self._place_resident(full=True)
            return
        ex = self.plan.session.executor
        rows = [self._pack_group_rows(g) for g in moved]
        self._res_tokens = ex.update_node_rows(
            self._res_tokens, moved, np.stack([t for t, _ in rows], axis=0)
        )
        self._res_valid = ex.update_node_rows(
            self._res_valid, moved, np.stack([v for _, v in rows], axis=0)
        )
        self.plan.session.stats.moved_node_blocks += len(moved)

    # -------------------------------------------------------------- state

    def init_state(self) -> tuple[TrainState, int]:
        """Fresh state, or resume from the newest checkpoint if one exists."""
        state = init_train_state(
            jax.random.PRNGKey(self.tcfg.seed), self.cfg,
            compression=self.tcfg.compression,
        )
        start = 0
        if self.tcfg.ckpt_dir and latest_step(self.tcfg.ckpt_dir) is not None:
            state, start = restore_checkpoint(self.tcfg.ckpt_dir, state)
        return state, start

    # -------------------------------------------------- mesh-native step

    def _recovered_stats(self, state: TrainState, step: int, alive_t: np.ndarray):
        """The fused path's gradient program: per-group statistics combined
        with the recovery weights solved on device.  Returns ``(stats, b,
        covered)``, or ``None`` when every group straggled."""
        sess = self.plan.session
        ex = sess.executor
        A = sess.assignment.matrix.astype(np.float32)
        pool_idx = jnp.asarray(step % self._pool, jnp.int32)
        node_args = (self._res_tokens, self._res_valid)
        bcast = (state.params, pool_idx)
        covered = sess.pattern_covers(alive_t)
        if covered:
            b_override = None
        else:
            # Degenerate pattern: host best-effort weights keep the covered
            # shards' mass instead of silently dropping the lost ones.  The
            # weights ride through the SAME compiled program as runtime data
            # (b_override) — the fallback never lowers a second full-model
            # gradient program.
            w = self.plan.step_weights(alive_t)
            if not w.any():
                return None  # every group straggled
            b_override = w
        stats, b_dev = ex.resilient_reduce_masked(
            self._group_fn, node_args, bcast, A, alive_t,
            iters=sess.device_iters, b_override=b_override,
        )
        if covered:
            sess.stats.device_solves += 1
        return stats, b_dev, covered

    @compiled_path("trainer.device_recovery_step", kind="host")
    def _device_recovery_step(
        self, state: TrainState, step: int, alive_t: np.ndarray
    ) -> tuple[TrainState, Optional[dict]]:
        """One step of the fused path.  Returns (state, record) — record is
        ``None`` when every group straggled (step skipped).  The input
        ``state`` is donated to the update."""
        out = self._recovered_stats(state, step, alive_t)
        if out is None:
            return state, None  # every group straggled: skip the step
        stats, b_dev, covered = out
        sess = self.plan.session
        state, metrics = self._apply_fn(state, stats)
        # ONE blocking device→host transfer per step: every per-step scalar
        # is fetched in a single device_get instead of a float() per metric.
        host = jax.device_get(
            {
                "loss": metrics["loss"],
                "ce": metrics["ce"],
                "grad_norm": metrics["grad_norm"],
                "b_sum": jnp.sum(b_dev),
                **{k: metrics[k] for k in _MOE_KEYS if k in metrics},
            }
        )
        record = {
            "step": step,
            "loss": float(host["loss"]),
            "ce": float(host["ce"]),
            "grad_norm": float(host["grad_norm"]),
            "stragglers": int((~alive_t).sum()),
            "fallback": not covered,
            "b_sum": float(host["b_sum"]),
            "host_solves": sess.stats.host_solves,
            "device_solves": sess.stats.device_solves,
            "patches": sess.stats.elastic_patches,
        }
        if "moe_local_pairs" in host:
            _count_moe(host)
            record["local_pairs"] = int(host["moe_local_pairs"])
        return state, record

    # ------------------------------------------------------------- warm-up

    def warmup(self, state: Optional[TrainState] = None) -> "autotune.WarmupReport":
        """Pre-compile the train step before the loop: ONE throwaway
        all-alive step whose result state is discarded (on the fused path
        the gradient program runs and the state update, which donates its
        input, is compiled without running, so ``state`` survives).

        Running the gradient program (or, off the fused path, the whole
        step) compiles what the loop will reuse and triggers any pending
        autotune measurement for its kernels, and on the mesh-native path it
        also seeds the pattern cache with the all-alive pattern.  Session
        counters are snapshotted
        and restored so the extra step is invisible to every stat the tests
        and benches assert on — only wall clock (reported) is spent.
        """
        if state is None:
            state, _ = self.init_state()
        alive = np.ones(self.tcfg.num_groups, dtype=bool)
        sess = self.plan.session
        # Registry counters are shared state: snapshot/restore through the
        # stats view, never by swapping the object.
        stats_snapshot = sess.stats.snapshot()

        def one_step():
            if self.tcfg.device_recovery:
                # The update donates its state, so it is compiled, not run:
                # the caller's state must survive the warm-up.
                stats, _, _ = self._recovered_stats(state, 0, alive)
                self._apply_fn.lower(state, stats).compile()
                return stats
            batch = {
                "tokens": jnp.asarray(self.pipeline.batch(0)),
                # All-alive weights: compilation only depends on shape/dtype,
                # and the warm state is discarded — the elastic manager is
                # deliberately NOT consulted (its streak state must not see
                # a synthetic round).
                "group_weights": jnp.ones(self.tcfg.num_groups, jnp.float32),
            }
            warm_state, _ = self._step_fn(state, batch)
            return warm_state.params

        try:
            report = autotune.warmup([("train_step", one_step)])
        finally:
            sess.stats.restore(stats_snapshot)
        self.warmup_report = report
        return report

    # -------------------------------------------------------------- loop

    def run(
        self,
        state: Optional[TrainState] = None,
        *,
        start_step: Optional[int] = None,
        on_step: Optional[Callable[[int, dict], None]] = None,
    ) -> TrainState:
        """Train from ``state`` (default: :meth:`init_state`) to
        ``tcfg.steps`` and return the final state.

        On the fused path (``device_recovery=True``) each update donates its
        input state, so a ``state`` passed in is consumed: its buffers are
        deleted by the first step.  Keep the returned state, not the one
        passed in (``state = trainer.run(state)``).
        """
        if state is None:
            state, resumed = self.init_state()
            start_step = resumed if start_step is None else start_step
        start_step = start_step or 0
        if (
            self.tcfg.warm_start
            and autotune.warm_start_enabled()
            and self.warmup_report is None
            and start_step < self.tcfg.steps
        ):
            self.warmup(state)
        for step in range(start_step, self.tcfg.steps):
            if self.tcfg.simulate_stragglers:
                srec = next(self.scenario)
                alive_t, latencies = srec.alive, srec.latencies
            else:
                srec = None
                alive_t = np.ones(self.tcfg.num_groups, dtype=bool)
                latencies = np.zeros((0,))  # scenario-less: not modelled
            if self.tcfg.device_recovery:
                if srec is not None:
                    ev = self.plan.session.observe(srec)
                    if ev["patched"] and hasattr(self.scenario, "rebind"):
                        # Re-aim the adversary at the patched assignment.
                        self.scenario.rebind(self.plan.current_assignment)
                with trace_span(
                    "trainer.step", step=step, path="device_recovery",
                    stragglers=int((~alive_t).sum()),
                ) as span:
                    state, record = self._device_recovery_step(state, step, alive_t)
                    if record is not None and "local_pairs" in record:
                        span.set_attr(local_pairs=record["local_pairs"])
                if record is None:
                    self.history.append({"step": step, "skipped": True})
                    continue
            else:
                weights, rec = self.elastic.step_weights(~alive_t)
                if not weights.any():  # every group straggled: skip the step
                    self.history.append({"step": step, "skipped": True})
                    continue
                batch = {
                    "tokens": jnp.asarray(self.pipeline.batch(step)),
                    "group_weights": jnp.asarray(weights),
                }
                with trace_span(
                    "trainer.step", step=step, path="host_weights",
                    stragglers=int((~alive_t).sum()),
                ):
                    state, metrics = self._step_fn(state, batch)
                record = {
                    "step": step,
                    "loss": float(metrics["loss"]),
                    "ce": float(metrics["ce"]),
                    "grad_norm": float(metrics["grad_norm"]),
                    "stragglers": int((~alive_t).sum()),
                    "delta": float(rec.delta) if np.isfinite(rec.delta) else -1.0,
                    "covered": float(rec.covered_fraction),
                }
            if latencies.size == self.tcfg.num_groups:
                # Only the deadline scenario models latency; mask-only
                # scenarios return an empty array.
                record["mean_latency"] = float(latencies.mean())
            self.history.append(record)
            if on_step:
                on_step(step, record)
            if (
                self.tcfg.ckpt_dir
                and (step + 1) % self.tcfg.ckpt_every == 0
            ):
                save_checkpoint(
                    self.tcfg.ckpt_dir, step + 1, state, keep=self.tcfg.ckpt_keep
                )
        return state
