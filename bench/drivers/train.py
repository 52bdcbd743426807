"""Resilient training steps through the ``Trainer``'s fused device-recovery
path, under the seeded deadline straggler model.

Set-up builds one trainer with its state (seeded weights in the type they
are trained in, made on the device in one call), feeds it the benchmark's
own seeded tokens through its data source, compiles the step, and drives
it through its first steps with the window's own call; the window goes on
from there with the same object.  The reference repeats those first steps
once the window has closed and the program's state is freed.

Traffic parameters (``bench/traffic/<mix>.json``): ``scenario`` (the
deadline model over the data-parallel groups), ``checked_steps``,
``trace_steps`` and ``limits``.
"""

from __future__ import annotations

import gc
import time
from typing import NamedTuple

import numpy as np

import gen
import harness

MAX_STEPS = 20000


class _Round(NamedTuple):
    """One straggler round, in the form the trainer's scenario yields."""

    alive: np.ndarray
    latencies: np.ndarray


class _Tokens:
    """The trainer's data source: shard ``j``'s rows of pool entry ``p``."""

    def __init__(self, table: np.ndarray):
        self.table = table  # (pool, shards, rows, seq_len)

    def shard_rows(self, shard_ids, step: int, capacity: int):
        pool, _, rows, seq = self.table.shape
        out = np.zeros((capacity * rows, seq), np.int32)
        valid = np.zeros((capacity,), np.float32)
        for i, j in enumerate(shard_ids):
            out[i * rows:(i + 1) * rows] = self.table[step % pool, int(j)]
            valid[i] = 1.0
        return out, valid


class State:
    pass


def _step(st: State, step: int):
    """One step through ``Trainer.run``; returns its history record."""
    st.trainer.tcfg.steps = step + 1
    st.state = st.trainer.run(st.state, start_step=step)
    return st.trainer.history[-1]


def _tokens_of(st: State, alive: np.ndarray) -> int:
    t = st.cfg["training"]
    covered = sum(bool(alive[h].any()) for h in st.holders)
    return covered * t["microbatch"] * t["seq_len"]


def setup(cell: harness.Cell, seconds: float, log=print) -> State:
    import jax
    import scipy.optimize  # noqa: F401  (the host recovery LP of an uncovered
    #                        pattern imports it; loaded here, not in the window)

    from repro.models import transformer as T
    from repro.models.transformer import ModelContext
    from repro.train.optimizer import AdamWConfig, init_opt_state
    from repro.train.train_step import TrainState
    from repro.train.trainer import Trainer, TrainerConfig

    c, tr = cell.config, cell.traffic
    t = c["training"]
    ref = cell.config_module
    st = State()
    st.cell, st.cfg, st.tr = cell, c, tr
    st.holders = ref.fr_holders(t["groups"], t["shards"], t["redundancy"])
    mcfg = ref.model_config(c)
    tcfg = TrainerConfig(
        device_recovery=True, scheme="fr", num_groups=t["groups"], num_shards=t["shards"],
        redundancy=t["redundancy"], microbatch=t["microbatch"], patch_headroom=0,
        seq_len=t["seq_len"], steps=0, seed=int(gen.sub_seeds(cell.seed, 1, 1)[0]),
        warm_start=False, resident_steps=t["resident_steps"],
    )
    trainer = Trainer(mcfg, tcfg, opt_cfg=AdamWConfig(**t["optimizer"]), ctx=ModelContext())
    st.tokens = ref.make_tokens(
        int(gen.sub_seeds(cell.seed, 2, 1)[0]), pool=t["resident_steps"], shards=t["shards"],
        rows=t["microbatch"], seq_len=t["seq_len"], vocab=c["vocab_size"])
    trainer.pipeline = _Tokens(st.tokens)
    trainer._place_resident(full=False)
    st.masks = gen.deadline_masks(cell.seed, t["groups"], MAX_STEPS, **tr["scenario"])
    trainer.scenario = iter(_Round(m, np.zeros(0)) for m in st.masks)
    shapes = jax.eval_shape(lambda: T.init_params(jax.random.PRNGKey(0), mcfg))
    params = ref.make_params(int(gen.sub_seeds(cell.seed, 3, 1)[0]), shapes,
                             std=c["initializer_range"])
    st.params0 = jax.device_get(params)
    st.state = TrainState(params=params, opt=init_opt_state(params), ef=None)
    report = trainer.warmup(st.state)
    if report.errors:
        raise RuntimeError(f"trainer warm-up failed: {report.errors} errors")
    st.trainer = trainer
    # The first steps, through the window's own call; the reference follows
    # them once the window has closed.
    b1 = t["optimizer"]["b1"]
    st.first, st.first_grad = [], None
    for step in range(int(tr["checked_steps"])):
        rec = _step(st, step)
        st.first.append(None if rec.get("skipped") else float(rec["loss"]))
        if st.first_grad is None and not rec.get("skipped"):
            # The first update's clipped gradient, as Adam's first moment holds it.
            st.first_grad = jax.tree_util.tree_map(
                lambda x: float(x) / (1.0 - b1), jax.device_get(ref.leaf_norms(st.state.opt.m)))
    # The parameters after those steps, kept on the host so that no second
    # copy lands on the chip beside the program's state; their change is
    # read in the check, the same way as the reference's.
    st.params_checked = jax.device_get(st.state.params)
    st.step = int(tr["checked_steps"])
    jax.block_until_ready(st.state.params)
    return st


def window(st: State, seconds: float, tracer: harness.Tracer) -> harness.WindowResult:
    trace_first = st.step + 2
    trace_last = trace_first + int(st.tr["trace_steps"])
    tokens = steps = skipped = 0
    took = []
    t0 = time.perf_counter()
    t_last = t0
    while time.perf_counter() - t0 < seconds:
        step = st.step
        if step == trace_first:
            tracer.begin()
        elif step == trace_last:
            tracer.end()
        t1 = time.perf_counter()
        rec = _step(st, step)
        took.append(time.perf_counter() - t1)
        st.step += 1
        if rec.get("skipped"):
            skipped += 1
            continue
        t_last = time.perf_counter()
        steps += 1
        tokens += _tokens_of(st, st.masks[step])
    tracer.end()
    rate = tokens / (t_last - t0) if steps else 0.0
    return harness.WindowResult(
        attempted=steps + skipped, failed=0,
        metrics={"train_tokens_per_s": rate},
        counters={"steps": steps, "train_tokens_per_s": rate, "config": st.cfg},
        notes=[f"steps {steps} skipped {skipped} unique_tokens {tokens}", harness.spread_note("step", took)],
    )


def _leaf_gaps(prog: dict, ref: dict, keep=None) -> list:
    """Per leaf: |‖prog‖ − ‖ref‖| over the larger of ‖ref‖ and the median
    leaf's ‖ref‖."""
    import jax

    p = jax.tree_util.tree_leaves(prog)
    r = jax.tree_util.tree_leaves(ref)
    med = float(np.median(r)) if r else 0.0
    gaps = []
    for i, (a, b) in enumerate(zip(p, r)):
        if keep is not None and not keep[i]:
            continue
        denom = max(float(b), med)
        if denom > 0:
            gaps.append(abs(float(a) - float(b)) / denom)
    return gaps


def compare(prog: dict, ref: dict) -> dict:
    """The compared numbers of program readings against the reference's.

    ``grad_gap`` and ``update_gap`` take the worst leaf; ``grad_mean_gap``
    the mean over the leaves of the first gradient, which a matmul precision
    below the stated one moves in every leaf at once, where the worst leaf
    of a sound run is one leaf's rounding."""
    import jax

    losses = [
        abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])
        if a is not None and b is not None
    ]
    skips_agree = [a is None for a in prog["losses"]] == [b is None for b in ref["losses"]]
    g_ref = jax.tree_util.tree_leaves(ref["first_grad"])
    med = float(np.median(g_ref))
    # Leaves whose gradient is nought to rounding move under Adam by
    # round-off alone: they are left out of the change.
    keep = [float(g) >= 1e-3 * med for g in g_ref]
    grad = _leaf_gaps(prog["first_grad"], ref["first_grad"])
    return {
        "loss_gap": (max(losses) if losses else float("inf")) if skips_agree else float("inf"),
        "grad_gap": max(grad, default=0.0),
        "grad_mean_gap": float(np.mean(grad)) if grad else float("inf"),
        "update_gap": max(_leaf_gaps(prog["change"], ref["change"], keep), default=0.0),
    }


def free_program(st: State) -> None:
    st.trainer = None
    st.state = None
    gc.collect()


def _reference(st: State, **kw) -> dict:
    n = int(st.tr["checked_steps"])
    return st.cell.config_module.ref_steps(st.params0, st.tokens, st.masks[:n], st.cfg, **kw)


def _program(st: State) -> dict:
    import jax

    change = jax.tree_util.tree_map(
        st.cell.config_module.host_diff_norm, st.params_checked, st.params0)
    return {"losses": st.first, "first_grad": st.first_grad, "change": change}


def check(st: State) -> list:
    free_program(st)
    got = compare(_program(st), _reference(st))
    return [harness.Check(k, got[k], float(v)) for k, v in st.tr["limits"].items()]


def readings(st: State) -> dict:
    """The compared numbers of the program, of the control (every matmul in
    float8 in the program's place) and of a fault (half of the batch left
    out, the mean taken over the rest) in the program's place."""
    free_program(st)
    want = _reference(st)
    return {
        "sound": compare(_program(st), want),
        "control": compare(_reference(st, mode="fp8"), want),
        "half_batch": compare(_reference(st, keep_shards={0}), want),
    }
