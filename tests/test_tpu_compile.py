"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e
that is described, not attached.

Each test lowers one kernel at the widths the serving path runs (qwen3-4b:
d_model 2560, d_ff 9728; attention at S=4096, hd=128) with the block sizes
``kernels.ops`` picks, compiles it with the TPU compiler, and checks that
the kernel survived as a Mosaic custom call.  Interpret-mode tests cannot
catch an op Mosaic refuses; these can, without a chip.

The topology is described inside a module fixture: only the worker that
runs these tests loads the TPU library, and every worker collects the same
tests.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.bitplane_matmul import bitplane_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int4_matmul import int4_matmul
from repro.kernels.quant_matmul import quant_matmul

D_MODEL, D_FF = 2560, 9728          # qwen3-4b MLP projection (K, N)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to a persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("m", [8, 128], ids=["decode", "prefill"])
@pytest.mark.parametrize("planes", [8, 4])
def test_bitplane_matmul_compiles(one_chip, m, planes):
    bm, bn, bk = ops._blocks_for(m, D_FF, D_MODEL)
    fn = functools.partial(bitplane_matmul, n_planes=planes, bm=bm, bn=bn,
                           bk=bk)
    _assert_kernel(fn, _spec((m, D_MODEL), jnp.int8, one_chip),
                   _spec((D_MODEL, D_FF), jnp.int8, one_chip))


@pytest.mark.parametrize("m", [8, 128], ids=["decode", "prefill"])
def test_int4_matmul_compiles(one_chip, m):
    bm, bn, bk = ops._blocks_for(m, D_FF, D_MODEL)
    fn = functools.partial(int4_matmul, bm=bm, bn=bn, bk=bk)
    _assert_kernel(fn, _spec((m, D_MODEL), jnp.int8, one_chip),
                   _spec((D_MODEL, D_FF // 2), jnp.uint8, one_chip),
                   _spec((1, D_FF), jnp.float32, one_chip))


def test_quant_matmul_compiles(one_chip):
    bm, bn, bk = ops._blocks_for(128, D_FF, D_MODEL)
    fn = functools.partial(quant_matmul, act="silu", bm=bm, bn=bn, bk=bk)
    _assert_kernel(fn, _spec((128, D_MODEL), jnp.int8, one_chip),
                   _spec((D_MODEL, D_FF), jnp.int8, one_chip),
                   _spec((1, D_FF), jnp.float32, one_chip),
                   _spec((1, D_FF), jnp.float32, one_chip))


def test_flash_attention_compiles(one_chip):
    s, hd = 4096, 128
    fn = functools.partial(flash_attention, causal=True, scale=hd ** -0.5)
    qkv = [_spec((8, s, hd), jnp.bfloat16, one_chip) for _ in range(3)]
    _assert_kernel(fn, *qkv)
