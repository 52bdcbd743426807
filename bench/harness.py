"""The benchmark's shared machinery: find a cell's files by name, run it
once, and assemble the result line.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Everything that
belongs to one configuration, traffic mix or per-layer metric sits in a
file of its own, found by the name the entry gives:

* ``bench/configs/<config>.json``  — the configuration as it is run;
* ``bench/configs/<config>.py``    — its data or weights from the seed, and
  its plain reference;
* ``bench/traffic/<traffic>.json`` — the traffic mix: parameters read by the
  general driver that its ``driver`` key names, and the limits of the
  comparison that decides ``correct``;
* ``bench/drivers/<driver>.py``    — one general window driver per kind of
  work (``setup``, ``window``, ``check``);
* ``bench/metrics/<metric>.py``    — one per-layer metric's reader;
* ``bench/kernels/<kernel>.py``    — one kernel's operation and byte counts.

Adding a cell, a configuration, a mix or a metric adds files and entries;
no existing file changes.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Callable, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

_MODULES: dict = {}


def load_module(path: Path):
    """Import a benchmark file by path (names may hold dots)."""
    path = Path(path)
    key = str(path)
    mod = _MODULES.get(key)
    if mod is None:
        if not path.is_file():
            raise FileNotFoundError(f"no benchmark file {path}")
        name = "bench_" + "_".join(path.relative_to(BENCH_DIR).with_suffix("").parts)
        name = name.replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_of(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_of(name: str) -> dict:
    return load_json(BENCH_DIR / "configs" / f"{name}.json")


def config_module(name: str):
    return load_module(BENCH_DIR / "configs" / f"{name}.py")


def traffic_of(name: str) -> dict:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def driver_of(traffic: dict):
    return load_module(BENCH_DIR / "drivers" / f"{traffic['driver']}.py")


def reader_of(metric: str):
    return load_module(BENCH_DIR / "metrics" / f"{metric}.py")


def kernel_counts(kernel: str):
    return load_module(BENCH_DIR / "kernels" / f"{kernel}.py")


def peaks_of(device_kind: str) -> dict:
    """The chip's published peaks; a kind that is not in the table is an
    error, never a default."""
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]


def metrics_for(bm: dict, cell: str, *, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: its end-to-end metrics
    untraced, its per-layer metrics traced."""
    group = bm["per_layer"] if trace else bm["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ------------------------------------------------------------------ runs


@dataclasses.dataclass
class Cell:
    """What a driver is given: the cell's entry, its configuration and
    traffic, the run's seed, and the devices it may use."""

    name: str
    config: dict
    traffic: dict
    seed: int
    chips: int
    devices: list
    config_module: Any


@dataclasses.dataclass
class WindowResult:
    attempted: int
    failed: int
    metrics: dict            # end-to-end metric name -> value
    counters: dict           # what per-layer readers may read
    notes: list              # earlier lines for standard error


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


class Tracer:
    """Profiles a short steady stretch of the window in a traced run.

    The driver calls :meth:`begin` and :meth:`end` around the stretch it
    chooses; untraced, both do nothing.  Host spans of the program reach the
    trace through ``REPRO_OBS_PROFILER=1``.
    """

    def __init__(self, enabled: bool, log_dir: Optional[str] = None):
        self.enabled = enabled
        self.log_dir = log_dir
        self.t_begin: Optional[float] = None
        self.active = False

    def begin(self) -> None:
        if not self.enabled or self.active or self.t_begin is not None:
            return
        import jax

        jax.profiler.start_trace(self.log_dir)
        self.active = True
        self.t_begin = time.perf_counter()

    def end(self) -> None:
        if not self.active:
            return
        import jax

        jax.effects_barrier()
        jax.profiler.stop_trace()
        self.active = False


def device_info(devices: list, chips: int) -> dict:
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": used[0].platform,
        "kind": used[0].device_kind,
        "count": len(used),
        "memory_peak_bytes": peak,
    }


def result_line(
    *,
    checks: list,
    window: WindowResult,
    metrics: dict,
    units: dict,
    device: dict,
    breakdown: Optional[dict] = None,
) -> dict:
    """The last line of standard output.  ``checks`` comes last: each
    number compared, beside its limit."""
    line = {
        "correct": bool(checks) and all(c.ok for c in checks),
        "attempted": int(window.attempted),
        "failed": int(window.failed),
        "metrics": {
            k: {"value": float(v), "unit": units[k]} for k, v in metrics.items()
        },
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": float(c.value), "limit": float(c.limit)} for c in checks}
    return line


def spread_note(what: str, seconds: list) -> str:
    """One line on how the window's units of work spread: their median,
    90th percentile and longest, in ms, and the time spent beyond three
    times the median."""
    if not seconds:
        return f"{what}_ms none"
    ms = sorted(1e3 * x for x in seconds)
    med = ms[len(ms) // 2]
    stall = sum(x - 3 * med for x in ms if x > 3 * med)
    return (f"{what}_ms p50 {med!r} p90 {ms[int(0.9 * (len(ms) - 1))]!r} max {ms[-1]!r} "
            f"beyond_3x_median_s {stall / 1e3!r}")


def print_checks(checks: list) -> None:
    for c in checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r}", file=sys.stderr, flush=True)


def per_layer_metrics(bm: dict, cell: Cell, ctx: dict) -> dict:
    """Each per-layer metric of the cell from its own reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics_for(bm, cell.name, trace=True):
        value = reader_of(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = float(value)
    return out


def settle() -> None:
    """End set-up: collect its garbage, and move every object it left into
    the collector's permanent generation, so that the window's collections
    scan only what the window makes."""
    gc.collect()
    gc.freeze()


def run_cell(
    cell: Cell,
    *,
    seconds: float,
    trace: bool,
    bm: dict,
    t_process: float,
    trace_dir: Optional[str] = None,
    log: Callable[[str], None] = lambda s: print(s, file=sys.stderr, flush=True),
) -> dict:
    """Set up, warm up, measure for ``seconds``, read the device, free the
    program's state, check against the plain reference, and return the
    result line."""
    drv = driver_of(cell.traffic)
    state = drv.setup(cell, seconds, log=log)
    settle()
    setup_s = time.perf_counter() - t_process
    log(f"setup_s {setup_s!r}")
    tracer = Tracer(trace, trace_dir)
    win = drv.window(state, seconds, tracer)
    tracer.end()
    gc.unfreeze()  # the check frees the program's state, set-up's included
    for note in win.notes:
        log(note)
    device = device_info(cell.devices, cell.chips)
    breakdown = None
    if trace:
        tr = load_module(BENCH_DIR / "trace.py")
        if tracer.t_begin is None:
            raise RuntimeError("the traced run profiled nothing")
        reduced = tr.reduce_dir(trace_dir, chips=cell.chips)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
        ctx = {
            "cell": cell,
            "trace": reduced,
            "counters": win.counters,
            "peaks": peaks_of(device["kind"]),
            "kernels": kernel_counts,
        }
        values = per_layer_metrics(bm, cell, ctx)
    else:
        values = dict(win.metrics)
        values["setup_s"] = setup_s
        wanted = {m["name"] for m in metrics_for(bm, cell.name, trace=False)}
        missing = wanted - set(values)
        if missing:
            raise RuntimeError(f"the window reported no {sorted(missing)}")
        values = {k: v for k, v in values.items() if k in wanted}
    units = {m["name"]: m["unit"] for m in bm["end_to_end"] + bm["per_layer"]}
    checks = drv.check(state)
    print_checks(checks)
    return result_line(
        checks=checks, window=win, metrics=values, units=units, device=device,
        breakdown=breakdown,
    )
