"""Unit tests for the loop-aware HLO cost analyzer (the §Roofline substrate)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze_hlo


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_dot_flops_exact():
    x = jax.ShapeDtypeStruct((64, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 16), jnp.float32)
    a = analyze_hlo(_hlo(lambda x, w: x @ w, x, w))
    assert a["flops"] == 2 * 64 * 32 * 16


def test_scan_multiplies_trip_count():
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def scanned(x, w):
        def body(c, _):
            return c @ w, ()
        y, _ = jax.lax.scan(body, x, None, length=8)
        return y

    single = analyze_hlo(_hlo(lambda x, w: x @ w, x, w))["flops"]
    scanned_f = analyze_hlo(_hlo(scanned, x, w))["flops"]
    assert scanned_f == pytest.approx(8 * single, rel=1e-6)


def test_nested_scan_multiplies_product():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def nested(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, ()
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, ()
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    single = analyze_hlo(_hlo(lambda x, w: x @ w, x, w))["flops"]
    got = analyze_hlo(_hlo(nested, x, w))["flops"]
    assert got == pytest.approx(15 * single, rel=1e-6)


def test_bytes_positive_and_scale_with_size():
    small = analyze_hlo(
        _hlo(lambda x: jnp.tanh(x) * 2, jax.ShapeDtypeStruct((128,), jnp.float32))
    )["bytes"]
    big = analyze_hlo(
        _hlo(lambda x: jnp.tanh(x) * 2, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    )["bytes"]
    assert 0 < small < big


def test_dus_in_scan_costs_slice_not_buffer():
    """Stacked scan outputs must not be charged the full buffer per step."""
    x = jax.ShapeDtypeStruct((4, 256), jnp.float32)

    def stacking(x):
        def body(c, _):
            c = c * 1.5
            return c, c  # ys stacking → per-step DUS into (64, 4, 256)
        _, ys = jax.lax.scan(body, x, None, length=64)
        return ys

    a = analyze_hlo(_hlo(stacking, x))
    # Naive costing would be ≥ 2 × 64steps × full(64·4·256·4B) ≈ 33.5 MB;
    # slice-aware costing stays well under 10 MB.
    assert a["bytes"] < 1e7


def test_collectives_counted_with_loop_multiplier():
    import subprocess, sys, textwrap, os, json

    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.hlo_analysis import analyze_hlo
        from jax.sharding import AxisType
        mesh = jax.make_mesh((4,), ("d",), axis_types=(AxisType.Auto,))

        def f(x):
            def body(c, _):
                # psum of a reduced stat keeps the carry's vma type stable.
                return c + jax.lax.psum(jnp.sum(c), "d"), ()
            y, _ = jax.lax.scan(body, x, None, length=5)
            return y

        g = jax.shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P("d"))
        hlo = jax.jit(g).lower(jax.ShapeDtypeStruct((8, 16), jnp.float32)).compile().as_text()
        a = analyze_hlo(hlo)
        print(json.dumps({"coll": a["collective_bytes"], "ops": a["collective_ops"]}))
        """
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300, env=env
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    # 5 loop iterations of a scalar psum: ≥ 5 × 4 B counted (loop-aware).
    assert rec["coll"] >= 5 * 4
    assert rec["ops"] >= 1
