"""The Algorithm 1 cell on ``MeshExecutor`` at a tiny size, on four host CPU
devices (a child process, since the device count is fixed when JAX starts):
a sound run is correct, and the cell's per-layer metric reads its window."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "alg1.sift128_k512.deadline.mesh4"

CHILD = """
import copy, json, sys, time
sys.path[:0] = [{src!r}, {bench!r}, {tests!r}]
import jax, harness
from conftest import TINY, _merge
over = copy.deepcopy(TINY["alg1.sift128_k512.deadline"])
bm = harness.benchmark()
e = harness.cell_of(bm, {cell!r})
cell = harness.Cell(
    name=e["name"], config=_merge(harness.config_of(e["config"]), over["config"]),
    traffic=_merge(harness.traffic_of(e["traffic"]), over["traffic"]), seed=2123456789,
    chips=e["chips"], devices=jax.devices(), config_module=harness.config_module(e["config"]))
line = harness.run_cell(cell, seconds=1.0, trace=False, bm=bm, t_process=time.perf_counter(),
                        log=lambda s: None)
drv = harness.driver_of(cell.traffic)
win = drv.window(drv.setup(cell, 1.0, log=lambda s: None), 1.0, harness.Tracer(False))
ctx = {{"cell": cell, "counters": win.counters, "peaks": harness.peaks_of("TPU v5 lite")}}
mfu = harness.reader_of("alg1_mfu").read(ctx)
print(json.dumps({{"devices": len(jax.devices()), "line": line, "alg1_mfu": mfu}}))
"""


@pytest.fixture(scope="module")
def mesh_lines():
    code = CHILD.format(src=str(ROOT / "src"), bench=str(ROOT / "bench"),
                        tests=str(ROOT / "tests" / "bench"), cell=CELL)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cell_runs_on_the_mesh_executor():
    from harness import benchmark, cell_of, traffic_of

    e = cell_of(benchmark(), CELL)
    assert e["chips"] == 4
    mesh, local = traffic_of(e["traffic"]), traffic_of("deadline")
    assert mesh.pop("executor") == "mesh" and local.pop("executor") == "local"
    assert mesh == local


def test_sound_run_line(mesh_lines):
    assert mesh_lines["devices"] == 4
    line = mesh_lines["line"]
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"solve_s", "setup_s"}


def test_per_layer_metric_reads_the_window(mesh_lines):
    """``alg1_mfu``, the cell's per-layer metric, reads the window's own
    counters (a traced run needs the chip)."""
    assert mesh_lines["alg1_mfu"] > 0
