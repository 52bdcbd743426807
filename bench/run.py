#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, makes its data or weights from
the seed, warms up every shape the window uses (all of it timed as
``setup_s``), measures for ``--seconds``, checks what the window produced
against the cell's plain reference, and prints one JSON object as the last
line of standard output.  ``--trace 1`` is a run of its own: it profiles a
short steady stretch of the window and reports the cell's per-layer metrics
in place of its end-to-end ones.

Exits non-zero, with no result line, where JAX finds no TPU or fewer chips
than the cell asks for.  It never falls back to the CPU.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if args.trace:
        # The program's host spans then land in the profiler's trace.
        os.environ["REPRO_OBS_PROFILER"] = "1"
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH_DIR))
    import harness

    bm = harness.benchmark()
    entry = harness.cell_of(bm, args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < entry["chips"]:
        print(f"bench: the cell needs {entry['chips']} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.caches import enable_compile_cache

    enable_compile_cache()
    cell = harness.Cell(
        name=entry["name"],
        config=harness.config_of(entry["config"]),
        traffic=harness.traffic_of(entry["traffic"]),
        seed=args.seed,
        chips=entry["chips"],
        devices=devices,
        config_module=harness.config_module(entry["config"]),
    )
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        line = harness.run_cell(
            cell, seconds=args.seconds, trace=bool(args.trace), bm=bm,
            t_process=T_PROCESS, trace_dir=trace_dir,
        )
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
