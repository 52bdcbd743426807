#!/usr/bin/env python3
"""Readings from which a cell's limits are set: for each seed, one window
of the program at the cell's own size and the compared numbers of its
output, and the same numbers with the control (the plain reference with
the step a later change would be tempted to take) in the program's place.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

All seeds run in one process, so the program compiles once.  Prints one
JSON line per seed.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH_DIR.parent / "src"))
    sys.path.insert(0, str(BENCH_DIR))
    import harness
    import jax

    from repro.caches import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    bm = harness.benchmark()
    entry = harness.cell_of(bm, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.Cell(
            name=entry["name"], config=harness.config_of(entry["config"]),
            traffic=harness.traffic_of(entry["traffic"]), seed=seed, chips=entry["chips"],
            devices=devices, config_module=harness.config_module(entry["config"]),
        )
        drv = harness.driver_of(cell.traffic)
        st = drv.setup(cell, args.seconds, log=lambda s: print(s, file=sys.stderr, flush=True))
        harness.settle()
        drv.window(st, args.seconds, harness.Tracer(False))
        gc.unfreeze()
        out = drv.readings(st)
        print(json.dumps({"seed": seed, **out}), flush=True)
        del st
    return 0


if __name__ == "__main__":
    sys.exit(main())
