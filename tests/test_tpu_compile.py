"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e
that is described, not attached.

Each test lowers one kernel at the widths the serving path runs (qwen3-4b:
d_model 2560, d_ff 9728; attention at S=4096, hd=128) with the block sizes
``kernels.ops`` picks, compiles it with the TPU compiler, and checks that
the kernel survived as a Mosaic custom call.  Interpret-mode tests cannot
catch an op Mosaic refuses (or a tile over its VMEM limit); these can,
without a chip.  One test compiles a whole two-layer serve decode step at
full width and reads the compiled HLO.

The topology is described inside a module fixture: only the worker that
runs these tests loads the TPU library, and every worker collects the same
tests.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import policy as pol
from repro.kernels import ops
from repro.kernels.bitplane_matmul import bitplane_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int4_matmul import int4_matmul
from repro.kernels.quant_matmul import quant_matmul

D_MODEL, D_FF = 2560, 9728          # qwen3-4b MLP projection (K, N)
# qwen3-4b's serve linears by (K, N): q, k and v, o, gate and up, down
SERVE_SHAPES = {"q": (D_MODEL, 4096), "k_v": (D_MODEL, 1024),
                "o": (4096, D_MODEL), "gate_up": (D_MODEL, D_FF),
                "down": (D_FF, D_MODEL)}


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


@pytest.mark.parametrize("shape", list(SERVE_SHAPES))
@pytest.mark.parametrize("m", [64, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("planes", [8, 4])
def test_bitplane_matmul_compiles(one_chip, shape, m, planes):
    """Every serve linear at the tiles the chooser gives (one VMEM-sized
    weight tile per grid step) compiles within the kernel's VMEM limit."""
    k, n = SERVE_SHAPES[shape]
    t = ops._bitplane_tiles(m, n, k, planes)
    fn = functools.partial(bitplane_matmul, n_planes=planes, bm=t.bm,
                           bn=t.bn, bk=t.bk)
    _assert_kernel(fn, _spec((t.mp, t.kp), jnp.int8, one_chip),
                   _spec((t.kp, t.np), jnp.int8, one_chip),
                   _spec((), jnp.int32, one_chip))


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


def _weight_producers(hlo: str):
    """(kernel, weight operand, instructions computing that operand) for
    each Mosaic call in compiled HLO text: the operand's own instruction,
    or the body of the fusion that produces it."""
    comps = dict(re.findall(r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\n(.*?)\n\}",
                            hlo, re.S | re.M))
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        kernel = line.split("=", 1)[0].strip().lstrip("%")
        weight = re.search(r"custom-call\(([^)]*)\)", line).group(1)
        weight = weight.split(", ")[-1].lstrip("%")
        define = re.search(rf"^\s*%{re.escape(weight)} = (.*)$", hlo, re.M)
        called = re.search(r"calls=%([\w.\-]+)", define.group(1))
        yield kernel, weight, (comps[called.group(1)] if called
                               else define.group(1))


def test_serve_decode_reads_each_weight_as_stored(one_chip):
    """A two-layer serve decode step at qwen3-4b's widths (48 slots, the
    int8 menu): every linear is one Mosaic call, and no fusion feeding a
    kernel its weight requantizes it (no clamp, no arithmetic shift) —
    the slice of the layer stack goes to the kernel as stored."""
    from repro.models import lm
    from repro.serve.engine import ServeEngine
    cfg = dataclasses.replace(configs.get("qwen3_4b"), n_layers=2,
                              vocab_size=4096)

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    qp = abstract(jax.eval_shape(
        lambda: lm.init_serve_params(cfg, jax.random.PRNGKey(0))))
    ctl = pol.BudgetController({"int8": pol.per_layer([8], [8], "int8")},
                               {"int8": 1.0}, cfg.n_layers)
    B, L, max_len = 48, cfg.n_layers, 128
    eng = ServeEngine(cfg, qp, max_len=max_len, controller=ctl, n_slots=B,
                      prefill_len=64, decode_block=1)
    cache = abstract(jax.eval_shape(lambda: lm.empty_cache(cfg, B, max_len)))
    keys = abstract(jax.eval_shape(
        lambda: jax.random.split(jax.random.PRNGKey(0), 1)))
    ops.set_force_pallas(True)
    try:
        with eng.compute_ctx():
            hlo = eng._decode_scan.lower(
                qp, _spec((B, 1), jnp.int32, one_chip),
                _spec((B,), jnp.int32, one_chip), cache,
                _spec((B, L), jnp.int32, one_chip),
                _spec((B, L), jnp.int32, one_chip),
                _spec((B,), jnp.float32, one_chip),
                _spec((B,), jnp.int32, one_chip), keys).compile().as_text()
    finally:
        ops.set_force_pallas(None, interpret=ops.INTERPRET_ENV)
    found = list(_weight_producers(hlo))
    assert len(found) == 7, [k for k, _, _ in found]   # q k v o gate up down
    for kernel, weight, body in found:
        for op in ("clamp", "shift-right-arithmetic"):
            assert op not in body, (kernel, weight, op)
