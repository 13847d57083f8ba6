"""Compile-only checks of every Pallas kernel for a TPU v5e.

Each case lowers and compiles one kernel at ogbn-arxiv size (|E| =
1,166,243, |V| = 169,343) for a described, unattached v5e chip: what
Mosaic refuses (block tiling, unaligned slices, scoped VMEM) fails here,
although the CPU interpreter accepts it. Nothing runs, so nothing here says
anything about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.core.config_space import KernelConfig

E, V = 1_166_243, 169_343
# the largest tiles the selector can pick (config_space candidates)
PR = KernelConfig("PR", 256, 512, 1024, 32)
SR = KernelConfig("SR", 256, 512, 1024, 1)
DTYPES = (jnp.float32, jnp.bfloat16)
FEATS = (128, 256)


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("feat", FEATS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("cfg,reduce,weighted", [
    (PR, "sum", True), (PR, "mean", False), (SR, "sum", True),
    (SR, "max", False), (SR, "mean", True)])
def test_gather_segment_reduce(chip, cfg, reduce, weighted, dtype, feat):
    from repro.kernels.gather_segment_reduce import \
        gather_segment_reduce_pallas

    def fn(h, g, s, w):
        return gather_segment_reduce_pallas(
            h, g, s, V, weight=w if weighted else None, reduce=reduce,
            config=cfg)
    _compile(chip, fn, ((V, feat), dtype), ((E,), jnp.int32),
             ((E,), jnp.int32), ((E,), dtype))


@pytest.mark.parametrize("feat", FEATS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("cfg,reduce", [(PR, "sum"), (SR, "max")])
def test_segment_reduce(chip, cfg, reduce, dtype, feat):
    from repro.kernels.segment_reduce import segment_reduce_pallas
    _compile(chip, lambda x, s: segment_reduce_pallas(x, s, V, reduce, cfg),
             ((E, feat), dtype), ((E,), jnp.int32))


@pytest.mark.parametrize("feat", FEATS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("reduce", ["sum", "mean"])
def test_fused_transform_reduce(chip, reduce, dtype, feat):
    from repro.kernels.fused_transform_reduce import \
        fused_transform_reduce_pallas

    def fn(h, w, g, s, wt):
        return fused_transform_reduce_pallas(h, w, g, s, V, weight=wt,
                                             reduce=reduce, config=PR)
    _compile(chip, fn, ((V, feat), dtype), ((feat, 256), dtype),
             ((E,), jnp.int32), ((E,), jnp.int32), ((E,), dtype))


@pytest.mark.parametrize("heads", [1, 4])
@pytest.mark.parametrize("dtype", DTYPES)
def test_segment_softmax(chip, dtype, heads):
    from repro.kernels.segment_softmax import segment_softmax_pallas
    _compile(chip, lambda x, s: segment_softmax_pallas(x, s, V, config=SR),
             ((E, heads), dtype), ((E,), jnp.int32))


@pytest.mark.parametrize("feat", FEATS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_sddmm(chip, dtype, feat):
    from repro.kernels.sddmm import sddmm_pallas
    _compile(chip, lambda a, b, r, c: sddmm_pallas(a, b, r, c),
             ((V, feat), dtype), ((V, feat), dtype), ((E,), jnp.int32),
             ((E,), jnp.int32))


@pytest.mark.parametrize("feat", FEATS)
@pytest.mark.parametrize("dtype", DTYPES)
def test_segment_matmul(chip, dtype, feat):
    from repro.kernels.segment_matmul import segment_matmul_pallas
    _compile(chip, lambda x, g, w: segment_matmul_pallas(x, g, w),
             ((E, feat), dtype), ((8,), jnp.int32), ((8, feat, 256), dtype))
