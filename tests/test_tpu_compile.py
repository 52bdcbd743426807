"""Compile the Pallas kernels for a described TPU v5e at real widths.

Nothing runs: the TPU compiler, which is installed with jax, compiles each
kernel for a ``v5e:2x2`` topology that is described, not attached.  It
refuses what the chip would refuse — block shapes that break the (8, 128)
tiling, layouts that disagree between XLA and Mosaic, tiles that overflow
VMEM — which interpret mode on the CPU never checks.  Each compile takes a
second or two.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import functools
import importlib.util

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dispatch
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.pairwise_dist import ops as pd_ops
from repro.kernels.weighted_segsum import ops as ss_ops


@pytest.fixture(scope="module")
def topo():
    # Skip only where the TPU compiler is not installed; any other failure to
    # describe the chip (a held library lock, a broken install, an API
    # change) fails the test instead of hiding the compile gate.
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU compiler (libtpu) is not installed")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def compile_tpu(one_chip, no_compile_cache):
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
        text = jax.jit(fn).lower(*args).compile().as_text()
        assert "tpu_custom_call" in text, "the Pallas kernel is missing from the program"
        return text

    return run


F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.mark.parametrize("nodes", [None, 8], ids=["plain", "vmap8"])
def test_assign_min_compiles(compile_tpu, nodes):
    n, k, d = 1 << 18, 1024, 64
    cfg = dispatch.pick_blocks(n, k, d)
    fn = functools.partial(pd_ops._assign_pallas_cfg, cfg=cfg, interpret=False)
    if nodes is None:
        compile_tpu(fn, ((n, d), F32), ((k, d), F32))
    else:  # what LocalExecutor.map_nodes does to every local Lloyd solve
        compile_tpu(jax.vmap(fn, in_axes=(0, None)), ((nodes, n, d), F32), ((k, d), F32))


def test_assign_min_wide_d_fits_vmem(compile_tpu):
    # At d=2048 the default (512, 256) tile needs 16.4 MiB of VMEM with the
    # f32 contraction; pick_blocks must shrink it below Mosaic's 16 MiB.
    n, k, d = 1 << 14, 256, 2048
    cfg = dispatch.pick_blocks(n, k, d)
    fn = functools.partial(pd_ops._assign_pallas_cfg, cfg=cfg, interpret=False)
    compile_tpu(fn, ((n, d), F32), ((k, d), F32))


@pytest.mark.parametrize("k", [1024, 4096])
def test_weighted_segsum_compiles(compile_tpu, k):
    n, d = 1 << 16, 128
    fn = functools.partial(ss_ops._segsum_pallas, k=k, interpret=False)
    compile_tpu(fn, ((n, d), F32), ((n,), F32), ((n,), I32))


def test_pairwise_sqdist_compiles(compile_tpu):
    n, k, d = 4096, 1024, 64
    cfg = dispatch.pick_blocks(n, k, d)
    fn = functools.partial(pd_ops._sqdist_pallas_cfg, cfg=cfg, interpret=False)
    compile_tpu(fn, ((n, d), F32), ((k, d), F32))


def test_flash_attention_gqa_compiles(compile_tpu):
    # qwen3-1.7b's attention: 16 query heads over 8 kv heads, head_dim 128.
    B, T, H, KV, dh = 1, 1024, 16, 8, 128
    fn = functools.partial(
        fa_ops._pallas_attention, causal=True, window=None, scale=dh ** -0.5,
        interpret=False,
    )
    compile_tpu(fn, ((B, T, H, dh), BF16), ((B, T, KV, dh), BF16), ((B, T, KV, dh), BF16))
