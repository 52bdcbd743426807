"""Launch-layer integration tests.

The mesh/sharding/lowering path needs >1 device, so these tests spawn a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main
pytest process must keep seeing 1 device — smoke tests depend on it).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=540, env=env,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


_PRELUDE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp
    from repro.configs.qwen3_1_7b import smoke_config
    from repro.launch.sharding import (
        make_context, state_shardings, batch_shardings, param_shardings,
        cache_shardings,
    )
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.train.train_step import init_train_state, make_train_step
    from repro.train.optimizer import AdamWConfig
    from repro.models import transformer as T
    cfg = dataclasses.replace(
        smoke_config(), n_layers=4, vocab=512, d_model=128, n_heads=4,
        n_kv_heads=2, d_ff=256, head_dim=32,
    ).validate()
    """
)


def test_train_lowering_single_and_multipod_mini():
    """.lower().compile() succeeds on mini versions of both production
    meshes; collectives exist; the loop-aware analysis sees the layer scan."""
    code = _PRELUDE + textwrap.dedent(
        """
        from jax.sharding import AxisType
        for shape, axes in (((2, 4), ("data", "model")),
                            ((2, 2, 2), ("pod", "data", "model"))):
            mesh = jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
            ctx = make_context(mesh, attn_impl="chunked", remat="full")
            state_struct = jax.eval_shape(
                lambda _: init_train_state(jax.random.PRNGKey(0), cfg), 0)
            st_sh = state_shardings(state_struct, mesh)
            ngroups = 4 if len(axes) == 2 else 4
            specs = {"tokens": jax.ShapeDtypeStruct((16, 64), jnp.int32),
                     "group_weights": jax.ShapeDtypeStruct((ngroups,), jnp.float32)}
            b_sh = batch_shardings(specs, mesh)
            step = make_train_step(cfg, ctx, AdamWConfig())
            comp = jax.jit(step, in_shardings=(st_sh, b_sh),
                           out_shardings=(st_sh, None)).lower(state_struct, specs).compile()
            hlo = comp.as_text()
            a = analyze_hlo(hlo, default_trip=cfg.scan_repeats)
            print(json.dumps({"mesh": "x".join(map(str, shape)),
                              "coll": a["collective_bytes"],
                              "flops": a["flops"]}))
        """
    )
    lines = [json.loads(l) for l in _run_sub(code).strip().splitlines()]
    assert len(lines) == 2
    for rec in lines:
        assert rec["coll"] > 0, "distributed step must emit collectives"
        assert rec["flops"] > 0


def test_decode_lowering_with_cache_shardings():
    code = _PRELUDE + textwrap.dedent(
        """
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        ctx = make_context(mesh, attn_impl="chunked")
        B, S = 8, 128
        params_struct = jax.eval_shape(
            lambda _: T.init_params(jax.random.PRNGKey(0), cfg), 0)
        cache_struct = jax.eval_shape(lambda _: T.init_cache(cfg, B, S), 0)
        p_sh = param_shardings(params_struct, mesh)
        c_sh = cache_shardings(cache_struct, mesh, B)
        def decode_fn(params, cache, tok, cur):
            return T.decode_step(params, cache, tok, cur, cfg, ctx)
        comp = jax.jit(decode_fn, in_shardings=(p_sh, c_sh, None, None)).lower(
            params_struct, cache_struct,
            jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)).compile()
        ca = comp.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca  # dict on jax>=0.5
        print("OK", ca["flops"] > 0)
        """
    )
    assert "OK True" in _run_sub(code)


def test_sharding_rules_divisibility_fallback():
    """14 heads on a 16-way model axis must fall back to replication instead
    of crashing (internvl2 case)."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.launch.sharding import param_spec
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        # 14*64=896-wide head projection: 896 % 4 == 0 → tp applies on dim 1;
        # but a 14-wide bias does not divide 4 → replicated.
        s1 = param_spec("unit/slot0/attn/wq", (128, 896), mesh)
        s2 = param_spec("unit/slot0/attn/wq", (128, 14), mesh)
        print(s1, "|", s2)
        """
    )
    out = _run_sub(code)
    assert "'data', 'model'" in out.replace('"', "'")
    assert "| PartitionSpec('data',)" in out or "| PartitionSpec('data', None)" in out


def test_moe_local_routing_matches_pjit_routing():
    """§Perf iteration 1 must be semantics-preserving: shard-local routing
    and pjit-land routing produce identical MoE outputs on real data."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.deepseek_moe_16b import smoke_config
        from repro.models import moe as M
        cfg = smoke_config().validate()
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        params = M.moe_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model), jnp.float32)
        kw = dict(mesh=mesh, batch_axes=("data",), model_axis="model", fsdp_axis="data")
        o1, a1 = M.moe_apply(params, x, cfg, routing="pjit", **kw)
        o2, a2 = M.moe_apply(params, x, cfg, routing="local", **kw)
        # Dropless in both paths → identical routing decisions and counts.
        np.testing.assert_allclose(np.asarray(o1, np.float32),
                                   np.asarray(o2, np.float32), rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(np.asarray(a1["held"]), np.asarray(a2["held"]))
        assert int(a1["held"].sum()) == 8 * 16 * cfg.moe.top_k  # every pair, none dropped
        print("EQUAL aux", float(a1["balance"].mean()), float(a2["balance"].mean()))
        """
    )
    out = _run_sub(code)
    assert "EQUAL" in out


def test_moe_shard_map_lowering_mini():
    """The MoE expert-parallel shard_map path compiles under a mesh and emits
    a model-axis psum."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp
        from repro.configs.deepseek_moe_16b import smoke_config
        from repro.launch.sharding import make_context, param_shardings
        from repro.models import moe as M
        cfg = smoke_config().validate()
        from jax.sharding import AxisType
        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
        ctx = make_context(mesh)
        params = jax.eval_shape(lambda _: M.moe_init(jax.random.PRNGKey(0), cfg), 0)
        p_sh = param_shardings({"moe": params}, mesh)["moe"]
        x = jax.ShapeDtypeStruct((8, 16, cfg.d_model), jnp.float32)
        def f(p, x):
            out, aux = M.moe_apply(p, x, cfg, mesh=mesh,
                                   batch_axes=("data",), model_axis="model",
                                   fsdp_axis="data")
            return out.sum() + aux["balance"].sum()
        comp = jax.jit(f, in_shardings=(p_sh, None)).lower(params, x).compile()
        txt = comp.as_text()
        print("psum:", "all-reduce" in txt)
        """
    )
    assert "psum: True" in _run_sub(code)
