"""Helpers for the benchmark's tests: run a cell through the harness's own
functions at a tiny size on the CPU."""

import copy
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT / "bench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# Tiny sizes of each cell, as overrides of its configuration and traffic.
TINY = {
    # A worker holds 1024 rows at this size, so one near-tie row counted
    # either way reads 9.8e-4 of them: ``size_gap`` keeps a limit of four
    # rows here.
    "alg1.sift128_k512.deadline": {
        "files": ("sift128_k512", "deadline"),
        "config": {"n": 4096, "d": 16, "k": 32, "local_iters": 3, "coord_iters": 4,
                   "data": {"planted": 8, "datasets": 2}},
        "traffic": {"checked_solves": 2, "limits": {"size_gap": 4e-3}},
    },
    # At this size bfloat16 rounds the small leaves' gradients more coarsely
    # than at the published widths, so the comparison keeps limits of its
    # own, set the same way from this size's readings (CPU, seeds 1 to 6):
    # sound loss/grad/mean-grad/update gaps up to 8.7e-5/1.3e-3/3.1e-4/
    # 6.5e-4, the float8 control's from 2.8e-4/5.4e-3/1.5e-3/1.0e-3, half of
    # the batch left out from 2.7e-3/2.8e-2/9.7e-3/6.1e-3, an unchanged
    # state 1 on the update.
    "train.qwen3_1_7b.fr4": {
        "files": ("qwen3_1_7b", "fr4"),
        "config": {"hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
                   "num_key_value_heads": 2, "head_dim": 64, "vocab_size": 2048,
                   "training": {"seq_len": 64}},
        "traffic": {"limits": {"loss_gap": 2e-4, "grad_gap": 3e-3, "grad_mean_gap": 7e-4,
                               "update_gap": 3e-3}},
    },
    "serve.sift128_k512.zipf4": {
        "files": ("sift128_k512", "zipf4"),
        "config": {"d": 128, "k": 32, "data": {"planted": 8},
                   "serve": {"points_per_tenant": 2048, "ingest_batch": 1024, "leaf": 256,
                             "coreset": 64}},
        "traffic": {"rate": 100, "checked_requests": 50},
    },
}


def _merge(a: dict, b: dict) -> dict:
    for k, v in b.items():
        if isinstance(v, dict) and isinstance(a.get(k), dict):
            _merge(a[k], v)
        else:
            a[k] = v
    return a


def tiny_cell(name: str, seed: int = 3, **traffic):
    import harness
    import jax

    over = copy.deepcopy(TINY[name])
    config, mix = over["files"]
    return harness.Cell(
        name=name,
        config=_merge(harness.config_of(config), over["config"]),
        traffic=_merge(_merge(harness.traffic_of(mix), over["traffic"]), traffic),
        seed=seed, chips=1, devices=jax.devices(),
        config_module=harness.config_module(config),
    )


def tiny_run(name: str, *, seconds: float = 1.0, seed: int = 3) -> dict:
    """One untraced run of a tiny cell; returns its result line."""
    import harness

    return harness.run_cell(tiny_cell(name, seed), seconds=seconds, trace=False,
                            bm=harness.benchmark(), t_process=time.perf_counter(),
                            log=lambda s: None)


def tiny_readings(name: str, *, seconds: float = 1.0, seed: int = 3) -> tuple:
    """(readings, limits) of a tiny cell: the program's compared numbers and
    those of the control (and faults) in its place."""
    import harness

    cell = tiny_cell(name, seed)
    drv = harness.driver_of(cell.traffic)
    st = drv.setup(cell, seconds, log=lambda s: None)
    drv.window(st, seconds, harness.Tracer(False))
    return drv.readings(st), cell.traffic["limits"]


@pytest.fixture
def run_tiny():
    return tiny_run


@pytest.fixture
def readings_tiny():
    return tiny_readings
