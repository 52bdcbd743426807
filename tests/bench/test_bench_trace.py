"""The trace reduction, the kernels' operation and byte counts, and the
table of peaks."""

import json
from pathlib import Path

import pytest

import harness

FIXTURES = Path(__file__).resolve().parent / "fixtures"
trace = harness.load_module(harness.BENCH_DIR / "trace.py")


def _synthetic():
    # Device: a while loop holding two kernel calls, then one more op.
    # Host: a repro.obs span over the middle gap, an unnamed event over the
    # last.
    k = ("%_assign_min_jit.2 = (s32[1,64]) custom-call(f32[64,8] %x, f32[1,64] %n, "
         "f32[16,8] %c)")
    return {
        "devices": [[
            ["%while.1 = (f32[8]) while(...)", 30000.0, 5000.0],
            [k, 30000.0, 2000.0],
            [k, 33000.0, 2000.0],
            ["%fusion.3 = f32[8] fusion(...)", 80000.0, 10000.0],
        ]],
        "host": [{"thread": "python", "events": [
            ["session.recovery_solve", 34000.0, 50000.0],
            ["PjitFunction(x)", 89000.0, 30000.0],
        ]}],
    }


def test_busy_union_idle_and_kernel_time():
    r = trace.reduce(_synthetic(), window_ns=(0.0, 130000.0))
    # Busy: [30000, 35000) and [80000, 90000) -> 15000 ns of 130000.
    assert r["busy_s"] == pytest.approx(15000e-9)
    assert r["window_s"] == pytest.approx(130000e-9)
    assert r["idle_share"] == pytest.approx(1 - 15000 / 130000)
    # The while loop holds the two kernel calls: only leaves are timed.
    ops = dict(r["device_ops"])
    assert ops["_assign_min_jit.2"] == pytest.approx(4000e-9)
    assert ops["fusion.3"] == pytest.approx(10000e-9)
    assert "while.1" not in ops


def test_gaps_attributed_to_host_spans():
    r = trace.reduce(_synthetic(), window_ns=(0.0, 130000.0))
    gaps = dict(r["idle_gaps"])
    # [0, 30000) under no host event; [35000, 80000) under the span;
    # [90000, 130000) mostly under the unnamed host event.
    assert gaps["none"] == pytest.approx(30000e-9)
    assert gaps["session.recovery_solve"] == pytest.approx(45000e-9)
    assert gaps["PjitFunction(x)"] == pytest.approx(40000e-9)
    assert r["gap_count"] == 3


def test_short_gaps_are_between_ops():
    ex = {"devices": [[["%a.1 = f32[1] add()", 0.0, 1000.0], ["%b.2 = f32[1] add()", 2000.0, 1000.0]]],
          "host": []}
    r = trace.reduce(ex)
    assert dict(r["idle_gaps"]) == {"between_ops": pytest.approx(1000e-9)}


def test_recorded_trace():
    # 3.5 ms of a traced run of the serving cell on a TPU v5e: a few
    # dispatches, the chip idle while the host serves.
    ex = json.loads((FIXTURES / "trace_small.json").read_text())
    r = trace.reduce(ex)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert 0.0 <= r["idle_share"] < 1.0
    assert r["device_ops"] and len(r["device_ops"]) <= trace.TOP
    assert len(r["idle_gaps"]) <= trace.TOP
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(r["window_s"] - r["busy_s"])
    km = harness.kernel_counts("assign_min")
    assert any(km.matches(name) for name in r["op_seconds"])
    assert r["idle_gaps"][0][0] == "serve.dispatch"


def test_assign_min_counts_by_hand():
    km = harness.kernel_counts("assign_min")
    name = ("%_assign_min_jit.22 = (s32[8,1,32768], f32[8,1,32768]) custom-call("
            "f32[8,32768,128]{2,1,0} %a, f32[8,1,32768]{2,1,0} %b, f32[8,512,128]{2,1,0} %c)")
    sh = km.shapes(name)
    assert sh == {"batch": 8, "n": 32768, "d": 128, "k": 512, "itemsize": 4}
    assert km.flops(**sh) == 2 * 8 * 32768 * 512 * 128
    assert km.bytes_moved(**sh) == 8 * (4 * (32768 * 128 + 32768 + 512 * 128) + 8 * 32768)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = km.least_seconds(name, peaks)
    assert bound == "compute" and t == pytest.approx(3.4359738368e10 / 197e12)
    coord = "%_assign_min_jit.20 = (s32[1,4096]) custom-call(f32[4096,128] %x, f32[1,4096] %n, f32[512,128] %c)"
    assert km.shapes(coord)["batch"] == 1


def test_weighted_segsum_counts_by_hand():
    km = harness.kernel_counts("weighted_segsum")
    assert km.flops(batch=1, n=10, k=3, d=4) == 2 * 10 * 4 + 10
    assert km.bytes_moved(batch=1, n=10, k=3, d=4) == 4 * (40 + 10) + 40 + 4 * (12 + 3)


def test_flash_attention_counts_by_hand():
    km = harness.kernel_counts("flash_attention")
    name = ("%_flash_attention_jit.1 = bf16[16,1024,128] custom-call(bf16[16,1024,128] %q, "
            "bf16[8,1024,128] %k, bf16[8,1024,128] %v)")
    sh = km.shapes(name)
    assert sh == {"bh": 16, "t": 1024, "dh": 128, "bkv": 8, "s": 1024, "itemsize": 2}
    assert km.flops(**sh) == 4 * 16 * (1024 * 1025 / 2) * 128
    assert km.bytes_moved(**sh) == 2 * 128 * (2 * 16 * 1024 + 2 * 8 * 1024)


def test_unknown_device_kind_raises():
    assert harness.peaks_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks_of("cpu")
