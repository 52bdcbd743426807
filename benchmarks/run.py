"""Benchmark harness (deliverable d): one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Roofline numbers come from the
dry-run artifacts (results/dryrun.jsonl via launch.dryrun), summarized here
when present.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig1 kernels
    PYTHONPATH=src python -m benchmarks.run kernels --emit BENCH_kernels.json
"""

from __future__ import annotations

import json
import os
import sys

from . import (
    bench_approx,
    bench_assignment,
    bench_coreset,
    bench_fig1,
    bench_kernels,
    bench_scenarios,
    bench_serve,
    bench_stream,
    bench_train_resilience,
    bench_training,
)
from .common import emit

BENCHES = {
    "fig1": bench_fig1.run,
    "assignment": bench_assignment.run,
    "approx": bench_approx.run,
    "coreset": bench_coreset.run,
    "training": bench_training.run,
    "kernels": bench_kernels.run,
    "scenarios": bench_scenarios.run,
    "serve": bench_serve.run,
    "stream": bench_stream.run,
    "train_resilience": bench_train_resilience.run,
}


def summarize_dryrun(path: str = "results/dryrun.jsonl") -> None:
    if not os.path.exists(path):
        return
    best: dict[tuple, dict] = {}
    with open(path) as f:
        for line in f:
            try:
                d = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "roofline" not in d:
                continue
            best[(d["arch"], d["shape"], d["mesh"])] = d  # last write wins
    for (arch, shape, mesh), d in sorted(best.items()):
        r = d["roofline"]
        emit(
            f"roofline_{arch}_{shape}_{mesh}",
            d.get("compile_s", 0.0) * 1e6,
            f"dom={r['dominant']} compute_ms={r['compute_s']*1e3:.2f} "
            f"memory_ms={r['memory_s']*1e3:.2f} coll_ms={r['collective_s']*1e3:.2f} "
            f"roofline_frac={r['roofline_fraction']:.3f}",
        )


def _take_flag(argv: list[str], flag: str, what: str) -> tuple[list[str], str | None]:
    if flag not in argv:
        return argv, None
    i = argv.index(flag)
    if i + 1 >= len(argv):
        sys.exit(f"error: {flag} requires {what}")
    return argv[:i] + argv[i + 2 :], argv[i + 1]


def main() -> None:
    from repro.caches import enable_compile_cache

    enable_compile_cache()
    argv = sys.argv[1:]
    argv, emit_path = _take_flag(argv, "--emit", "an output path (e.g. --emit BENCH_kernels.json)")
    argv, trace_path = _take_flag(argv, "--trace", "a JSONL alive-mask trace path")
    names = argv or list(BENCHES)
    print("name,us_per_call,derived")
    for n in names:
        if n == "dryrun":
            summarize_dryrun()
            continue
        if n == "scenarios" and trace_path is not None:
            BENCHES[n](trace_path=trace_path)
        else:
            BENCHES[n]()
    if not argv:
        summarize_dryrun()
    if emit_path is not None:
        from .common import ROWS

        with open(emit_path, "w") as f:
            json.dump(
                [
                    {"name": name, "us_per_call": us, "derived": derived}
                    for name, us, derived in ROWS
                ],
                f,
                indent=2,
            )
        print(f"# wrote {len(ROWS)} rows to {emit_path}", file=sys.stderr)


if __name__ == "__main__":
    main()
