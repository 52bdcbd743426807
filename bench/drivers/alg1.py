"""Algorithm 1 solves back to back through ``ResilienceSession.kmedian``.

Each solve clusters the next of the datasets made at set-up (round robin),
so placement and packing run per job as they would for a real one, under
the next alive mask of the seeded deadline straggler model, so host
recovery sees new patterns.  A mask with no worker alive is skipped and not
counted; a mask that leaves a shard uncovered runs, as it would for a user.

Traffic parameters (``bench/traffic/<mix>.json``): ``executor``,
``scenario`` (the deadline model), ``checked_solves`` (how many of the
window's solves the reference repeats), ``trace_solves`` (how many solves a
traced run profiles) and ``limits``.
"""

from __future__ import annotations

import dataclasses
import gc
import sys
import time

import numpy as np

import gen
import harness

MAX_SOLVES = 100000


@dataclasses.dataclass
class Solve:
    index: int
    dataset: int
    alive: np.ndarray
    seed: int
    centers: np.ndarray
    cost: float
    summary_points: np.ndarray
    summary_weights: np.ndarray


class State:
    pass


def setup(cell: harness.Cell, seconds: float, log=print) -> State:
    from repro.core.assignment import make_assignment
    from repro.core.resilience import ResilienceSession

    cfg, tr = cell.config, cell.traffic
    st = State()
    st.cell = cell
    st.cfg = cfg
    st.tr = tr
    data = cfg["data"]
    st.datasets = cell.config_module.make_datasets(
        int(gen.sub_seeds(cell.seed, 1, 1)[0]), count=data["datasets"], n=cfg["n"],
        d=cfg["d"], planted=data["planted"], scale=data["scale"], noise=data["noise"],
    )
    st.masks = gen.deadline_masks(cell.seed, cfg["nodes"], MAX_SOLVES // 100, **tr["scenario"])
    st.seeds = gen.sub_seeds(cell.seed, 2, len(st.masks))
    assignment = make_assignment("cyclic", cfg["n"], cfg["nodes"], ell=cfg["ell"])
    st.session = ResilienceSession(assignment, executor=tr["executor"])
    st.kw = dict(local_iters=cfg["local_iters"], coord_iters=cfg["coord_iters"])
    # Warm-up: one solve compiles the local, coordinator and cost programs.
    st.session.kmedian(st.datasets[-1], cfg["k"], np.ones(cfg["nodes"], bool),
                       seed=int(gen.sub_seeds(cell.seed, 3, 1)[0]), **st.kw)
    st.solves = []
    return st


def window(st: State, seconds: float, tracer: harness.Tracer) -> harness.WindowResult:
    k = st.cfg["k"]
    n_data = len(st.datasets)
    trace_first, trace_last = 1, 1 + int(st.tr["trace_solves"])
    skipped = failed = 0
    took = []
    t0 = time.perf_counter()
    t_last = t0
    j = 0
    while time.perf_counter() - t0 < seconds:
        if j >= len(st.masks):
            raise RuntimeError("the window outran the generated masks")
        alive, seed = st.masks[j], int(st.seeds[j])
        ds = j % n_data
        j += 1
        if not alive.any():
            skipped += 1
            continue
        done = len(st.solves)
        if done == trace_first:
            tracer.begin()
        elif done == trace_last:
            tracer.end()
        t1 = time.perf_counter()
        try:
            out = st.session.kmedian(st.datasets[ds], k, alive, seed=seed, **st.kw)
        except Exception as e:  # a solve that fails is counted, not hidden
            failed += 1
            st.solves.append(None)
            print(f"solve {j - 1} failed: {e!r}", file=sys.stderr)
            continue
        t_last = time.perf_counter()
        took.append(t_last - t1)
        st.solves.append(Solve(
            index=j - 1, dataset=ds, alive=alive.copy(), seed=seed,
            centers=np.asarray(out.centers), cost=float(out.cost),
            summary_points=np.asarray(out.summary_points),
            summary_weights=np.asarray(out.summary_weights),
        ))
    tracer.end()
    done = [s for s in st.solves if s is not None]
    attempted = len(st.solves)
    solve_s = (t_last - t0) / len(done) if done else float("inf")
    stragglers = [int((~s.alive).sum()) for s in done]
    notes = [
        f"solves {len(done)} failed {failed} skipped_all_dead {skipped}",
        f"stragglers_per_solve mean {float(np.mean(stragglers)) if done else 0.0!r}",
        harness.spread_note("solve", took),
    ]
    return harness.WindowResult(
        attempted=attempted, failed=failed, metrics={"solve_s": solve_s},
        counters={"solves": len(done), "solve_s": solve_s, "config": st.cfg},
        notes=notes,
    )


# What stands in the program's place for a reading: the control (the
# reference one matmul precision below the deployment's) and a second
# control that breaks the recovery guarantee.
STAND_INS = {
    "control": dict(precision="high"),
    "recovery": dict(ignore_stragglers=True),
}
NUMBERS = ("weight_gap", "summary_gap", "size_gap", "recost_gap")
STAND_IN_MASKS = 64  # masks of the schedule a stand-in's recovery weights are read on


def compared_numbers(st: State, program_solves: list, *, stand_in: str = "") -> dict:
    """The numbers that decide ``correct``, each the worst over the solves
    it covers.

    * ``weight_gap`` (every solve): how far the weights a solve gave the
      coordinator fall outside the recovery band [1, 1+δ*] (recovery
      weights and the workers' cluster sizes).
    * ``summary_gap`` (a sample of solves drawn from the seed): the
      coordinator's weighted objective Σ_c w(c)·d(c, centers) over the
      workers' weighted centers, at the returned centers, against the
      reference pipeline's on the same data, mask and seeds (local solves,
      recovery weights and the coordinator together).
    * ``size_gap`` (the sample): the worst alive worker's share of rows
      counted to a center that is not their nearest (float64), read back
      from the cluster sizes it gave the coordinator.
    * ``recost_gap`` (the sample): the reported cost against the float64
      sum over all points of the distance to the nearest returned center.

    ``stand_in`` names an entry of ``STAND_INS``: the reference so set
    stands in the program's place, with its recovery weights on the
    schedule's masks (``weight_gap``) and its whole pipeline on the sampled
    solves."""
    cfg, ref = st.cfg, st.cell.config_module
    s, ell, k, n = cfg["nodes"], cfg["ell"], cfg["k"], cfg["n"]
    kw = dict(k=k, s=s, ell=ell, local_iters=cfg["local_iters"],
              coord_iters=cfg["coord_iters"])
    done = [x for x in program_solves if x is not None]
    if not done:
        return {name: float("inf") for name in NUMBERS}
    r = gen.rng(st.cell.seed, 4)
    pick = sorted(r.choice(len(done), size=min(len(done), int(st.tr["checked_solves"])),
                           replace=False))
    checked = [done[i] for i in pick]
    if stand_in:
        opts = STAND_INS[stand_in]
        ignore = opts.get("ignore_stragglers", False)
        # Over the schedule's first masks, and at least as far as the
        # window went, so that the reading does not hang on how many solves
        # fit the window.
        upto = max(STAND_IN_MASKS, done[-1].index + 1)
        weight_gap = max(
            ref.band_gap(ref.recovery_weights(alive, s=s, ell=ell, ignore_stragglers=ignore),
                         alive, s=s, ell=ell)
            for alive in st.masks[:upto] if alive.any()
        )
        replaced = []
        for sv in checked:
            out = ref.ref_kmedian(st.datasets[sv.dataset], sv.alive, seed=sv.seed, **opts, **kw)
            replaced.append(dataclasses.replace(
                sv, centers=out["centers"], cost=out["cost"],
                summary_points=out["summary_points"], summary_weights=out["summary_weights"]))
        checked = replaced
    else:
        rows = ell * n // s
        weight_gap = max(
            ref.coverage_gap(sv.summary_weights, sv.alive, s=s, ell=ell, k=k,
                             rows_per_node=rows)
            for sv in done
        )
    summary_gap = size_gap = recost_gap = 0.0
    for sv in checked:
        points = st.datasets[sv.dataset]
        want = ref.ref_kmedian(points, sv.alive, seed=sv.seed, **kw)["objective"]
        got = ref.coordinator_objective(sv.summary_points, sv.summary_weights, sv.centers)
        summary_gap = max(summary_gap, abs(got - want) / want)
        size_gap = max(size_gap, ref.size_gap(points, sv.summary_points, sv.summary_weights,
                                              s=s, ell=ell, k=k))
        recost_gap = max(recost_gap, ref.recost_gap(points, sv.centers, sv.cost))
    return {"weight_gap": weight_gap, "summary_gap": summary_gap, "size_gap": size_gap,
            "recost_gap": recost_gap}


def free_program(st: State) -> None:
    st.session = None
    gc.collect()


def check(st: State) -> list:
    free_program(st)
    limits = st.tr["limits"]
    got = compared_numbers(st, st.solves)
    return [harness.Check(name, got[name], float(limits[name])) for name in limits]


def readings(st: State) -> dict:
    """The compared numbers of the window just run, and of each stand-in in
    the program's place on the same solves."""
    free_program(st)
    out = {"sound": compared_numbers(st, st.solves)}
    for name in STAND_INS:
        out[name] = compared_numbers(st, st.solves, stand_in=name)
    return out
