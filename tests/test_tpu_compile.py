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
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import policy as pol
from repro.kernels import ops
from repro.kernels.bitplane_matmul import bitplane_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int4_matmul import int4_matmul
from repro.kernels.quant_matmul import quant_matmul
from repro.kernels.ssm_update import ssm_update

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


def test_ssm_update_compiles(one_chip):
    """The decode state update at granite-4.0-h-micro's shapes, 48 slots:
    state (36, 48, 64, 64, 128) f32, one slot's (64, 64, 128) block a grid
    step; one Mosaic call whose state output is its state operand."""
    L, B, H, P, N = 36, 48, 64, 64, 128
    text = jax.jit(ssm_update, donate_argnums=(0,)).lower(
        _spec((L, B, H, P, N), jnp.float32, one_chip),
        _spec((), jnp.int32, one_chip),
        _spec((B, P, H), jnp.float32, one_chip),
        _spec((B, P, H), jnp.float32, one_chip),
        _spec((B, 1, N), jnp.float32, one_chip),
        _spec((B, 1, N), jnp.float32, one_chip),
        _spec((B,), jnp.int32, one_chip)).compile().as_text()
    call = [ln for ln in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(call) == 1 and "output_to_operand_aliasing={{1}: (8, {})}" \
        in call[0]


@pytest.mark.parametrize("shape", [(36, 2048, 8512), (36, 2560, 9728),
                                   (4, 2048, 512), (36, 2560, 1000),
                                   (36, 300, 1000), (2, 100, 200)])
def test_k_minor_matches_the_chips_default_layout(one_chip, shape):
    """``ops._k_minor`` predicts the layout the TPU gives an int8 stack
    (granite's in_proj is K-minor; aligned stacks are not; a tie goes
    K-minor when N is unaligned), so the GEMM takes each stack in the
    orientation it is stored in and XLA copies no stack to relayout it."""
    out = jax.jit(lambda w: w + 1).lower(
        _spec(shape, jnp.int8, one_chip)).compile()
    k_minor = out.input_formats[0][0].layout.major_to_minor == (0, 2, 1)
    assert k_minor == ops._k_minor(*shape[1:]), shape


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


def _abstract_engine(one_chip, cfg, n_slots: int, max_len: int,
                     decode_block: int, prefill_len: int = 64):
    """A serve engine (the int8 menu) over abstract weights on the
    described chip; returns (engine, weights, ``abstract``)."""
    from repro.models import lm
    from repro.serve.engine import ServeEngine

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    qp = abstract(jax.eval_shape(
        lambda: lm.init_serve_params(cfg, jax.random.PRNGKey(0))))
    ctl = pol.BudgetController({"int8": pol.per_layer([8], [8], "int8")},
                               {"int8": 1.0}, cfg.n_layers)
    eng = ServeEngine(cfg, qp, max_len=max_len, controller=ctl,
                      n_slots=n_slots, prefill_len=prefill_len,
                      decode_block=decode_block)
    return eng, qp, abstract


def _compile_decode_block(one_chip, cfg, n_slots: int, max_len: int,
                          decode_block: int):
    """The serve engine's decode block (``ServeEngine._decode_scan``, the
    int8 menu) compiled for the described chip from shapes alone; returns
    (compiled, number of weight leaves, abstract cache)."""
    from repro.models import lm

    eng, qp, abstract = _abstract_engine(one_chip, cfg, n_slots, max_len,
                                         decode_block)
    B, L = n_slots, cfg.n_layers
    cache = abstract(jax.eval_shape(lambda: lm.empty_cache(cfg, B, max_len)))
    keys = abstract(jax.eval_shape(
        lambda: jax.random.split(jax.random.PRNGKey(0), decode_block)))
    ops.set_force_pallas(True)
    try:
        with eng.compute_ctx():
            compiled = eng._decode_scan.lower(
                qp, _spec((B, 1), jnp.int32, one_chip),
                _spec((B,), jnp.int32, one_chip), cache,
                _spec((B, L), jnp.int32, one_chip),
                _spec((B, L), jnp.int32, one_chip),
                _spec((B,), jnp.float32, one_chip),
                _spec((B,), jnp.int32, one_chip), keys).compile()
    finally:
        ops.set_force_pallas(None, interpret=ops.INTERPRET_ENV)
    return compiled, len(jax.tree.leaves(qp)), cache


def _compile_prefill_row(one_chip, cfg, prefill_len: int, max_len: int):
    """The serve engine's row prefill (``ServeEngine._prefill_row``, the
    int8 menu) compiled for the described chip from shapes alone."""
    eng, qp, _ = _abstract_engine(one_chip, cfg, 48, max_len, 8,
                                  prefill_len)
    L = cfg.n_layers
    ops.set_force_pallas(True)
    try:
        with eng.compute_ctx():
            return eng._prefill_row.lower(
                qp, _spec((1, prefill_len), jnp.int32, one_chip),
                _spec((1,), jnp.int32, one_chip),
                _spec((L,), jnp.int32, one_chip),
                _spec((L,), jnp.int32, one_chip)).compile()
    finally:
        ops.set_force_pallas(None, interpret=ops.INTERPRET_ENV)


_DEFINE = re.compile(r"^(\w+)\[([\d,]*)\](\S*) ([\w\-]+)\(")


def _stack_operand(body: str):
    """(dtype, shape, how) of a kernel's weight operand from the line that
    defines it: ``how`` is "held" for the program's own array (a
    parameter, a loop's get-tuple-element of one, or a bitcast view of
    either), "prefetched" for the compiler's copy of it into VMEM (memory
    space S(1)), else the opcode."""
    dtype, dims, layout, op = _DEFINE.match(body).groups()
    shape = tuple(int(d) for d in dims.split(","))
    if op in ("parameter", "get-tuple-element", "bitcast"):
        return dtype, shape, "held"
    if "S(1)" in layout and (op == "copy-done" or
                             'custom_call_target="ConcatBitcast"' in body):
        return dtype, shape, "prefetched"
    return dtype, shape, op


def test_serve_decode_reads_each_weight_as_stored(one_chip):
    """A two-layer serve decode step at qwen3-4b's widths (48 slots, the
    int8 menu): every linear is one Mosaic call, and no fusion feeding a
    kernel its weight requantizes it (no clamp, no arithmetic shift) —
    the layer stack goes to the kernel as stored, whole."""
    cfg = dataclasses.replace(configs.get("qwen3_4b"), n_layers=2,
                              vocab_size=4096)
    hlo = _compile_decode_block(one_chip, cfg, 48, 128, 1)[0].as_text()
    found = list(_weight_producers(hlo))
    assert len(found) == 7, [k for k, _, _ in found]   # q k v o gate up down
    for kernel, weight, body in found:
        for op in ("clamp", "shift-right-arithmetic"):
            assert op not in body, (kernel, weight, op)
        dtype, shape, how = _stack_operand(body)
        assert dtype == "s8" and len(shape) == 3 and shape[0] == 2, (
            kernel, body[:120])
        assert how in ("held", "prefetched"), (kernel, body[:120])


def _weight_copies(hlo: str, least: int):
    """Instructions outside fused computations that copy, pad, slice or
    concatenate (or fuse such a move into) an s8 array of ``least`` bytes
    or more in HBM: (name, op, dims).  Left out: the compiler's own
    prefetches into VMEM (an output in memory space S(1)), which it may
    make of a stack small enough to hold there whole."""
    comps = dict(re.findall(r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\n(.*?)\n\}",
                            hlo, re.S | re.M))
    fused = set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", hlo))
    moves = {"fusion", "copy", "copy-done", "pad", "slice", "dynamic-slice",
             "slice-done", "concatenate"}
    inst = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(")
    for name, body in comps.items():
        if name in fused:
            continue
        for line in body.splitlines():
            m = inst.match(line)
            if not m:
                continue
            op = m.group(3)
            if op == "custom-call":
                if "tpu_custom_call" in line:
                    continue
            elif op not in moves:
                continue
            for dims, layout in re.findall(r"s8\[([\d,]+)\](\{[^}]*\})?",
                                           m.group(2)):
                shape = [int(d) for d in dims.split(",")]
                if int(np.prod(shape)) >= least and "S(1)" not in layout:
                    yield m.group(1), op, shape


@pytest.mark.parametrize("arch,n_layers,program", [
    ("qwen3_4b", 2, "decode"), ("granite_4_0_h_micro", 20, "decode"),
    ("qwen3_4b", 2, "prefill")])
def test_gemm_reads_weights_from_the_layer_stack(one_chip, arch, n_layers,
                                                 program):
    """The cells' decode block (48 slots, decode_block 8) and qwen3-4b's
    row prefill (512 positions), compiled at full widths with fewer
    layers: every serve GEMM's weight operand is a whole (L, K, N) int8
    stack as the program holds it (a parameter, or the loop's
    get-tuple-element of one), and no fusion, copy, pad, slice or other
    custom call makes an s8 array in HBM as large as one layer's
    smallest weight — no per-layer weight copy anywhere in the step.

    The compiler may still prefetch a stack small enough for VMEM whole
    (granite's four-layer attention stacks; at two layers, qwen3-4b's
    too): the kernel then reads the layer's tiles from that copy."""
    from repro.models import lm

    cfg = dataclasses.replace(configs.get(arch), n_layers=n_layers)
    max_len = 768 if arch == "qwen3_4b" else 2560
    if program == "decode":
        compiled = _compile_decode_block(one_chip, cfg, 48, max_len, 8)[0]
    else:
        compiled = _compile_prefill_row(one_chip, cfg, 512, max_len)
    stacks = {tuple(a.shape) for a in jax.tree.leaves(jax.eval_shape(
        lambda: lm.init_serve_params(cfg, jax.random.PRNGKey(0))))
        if a.dtype == jnp.int8 and a.ndim == 3}
    # a K-minor stack (granite's in_proj) goes as its (L, N, K) transpose
    stacks |= {(L, N, K) for L, K, N in stacks if ops._k_minor(K, N)}
    hlo = compiled.as_text()
    found = [(k, body) for k, _, body in _weight_producers(hlo)
             if k.startswith("bitplane_matmul")]
    assert found
    for kernel, body in found:
        dtype, shape, how = _stack_operand(body)
        assert dtype == "s8" and shape in stacks, (kernel, body[:120])
        assert how in ("held", "prefetched"), (kernel, body[:120])
    least = min(k * n for _, k, n in stacks)
    assert list(_weight_copies(hlo, least)) == []


_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "s32": 4, "u32": 4,
          "f32": 4}


def _ring_copies(hlo: str, n_slots: int, ring: int, least: int):
    """Materialized copies, dynamic slices and dynamic-update-slices of
    the KV ring: instructions of the compiled HLO outside fused
    computations that are (or fuse) one of those ops and produce an array
    with the slot and ring-length dims of ``least`` bytes or more."""
    comps = dict(re.findall(r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\n(.*?)\n\}",
                            hlo, re.S | re.M))
    fused = set(re.findall(r"kind=k\w+, calls=%([\w.\-]+)", hlo))
    moves = ("copy", "dynamic-slice", "dynamic-update-slice")
    inst = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                      r"([\w\-]+)\(")
    for name, body in comps.items():
        if name in fused:
            continue
        for line in body.splitlines():
            m = inst.match(line)
            if not m or m.group(2) not in _BYTES:
                continue
            dims = [int(d) for d in m.group(3).split(",") if d]
            op = m.group(4)
            if op == "fusion":
                called = re.search(r"calls=%([\w.\-]+)", line).group(1)
                ops_in = set(re.findall(r"\]\S* ([\w\-]+)\(", comps[called]))
                if not ops_in & set(moves):
                    continue
            elif op not in moves:
                continue
            if (n_slots in dims and ring in dims
                    and int(np.prod(dims)) * _BYTES[m.group(2)] >= least):
                yield m.group(1), op, m.group(2), dims


@pytest.mark.parametrize("arch,n_layers,max_len", [
    ("qwen3_4b", 2, 768), ("granite_4_0_h_micro", 20, 2560)])
def test_decode_block_keeps_kv_ring_in_place(one_chip, arch, n_layers,
                                             max_len):
    """The decode block at the cells' shapes (48 slots, decode_block 8,
    full widths, fewer layers) carries the KV ring through its step and
    layer scans as one buffer, updated in place: every cache leaf's
    output aliases its input, nothing copies or slices out an array as
    large as one layer's K slab, and no temporary grows with the ring.

    Granite compiles two periods: a one-period scan runs once, and the
    compiler then lays its ring out afresh, copying it in and out (the
    served model has four).  Its temporaries also hold the period scan's
    weight slices, so the ring's share is read as the growth of the
    temporaries from a 128-position ring to the full one."""
    cfg = dataclasses.replace(configs.get(arch), n_layers=n_layers)
    B = 48
    compiled, n_q, cache = _compile_decode_block(one_chip, cfg, B, max_len, 8)
    ring = cache["kv"] if "kv" in cache else cache
    k = ring["k"]
    slab = int(np.prod(k.shape[1:])) * k.dtype.itemsize
    hlo = compiled.as_text()
    header = hlo.splitlines()[0]
    for i in range(len(jax.tree.leaves(cache))):    # outputs (tok, t, cache..)
        assert f"{{{2 + i}}}: ({n_q + 2 + i}, {{}}" in header, i
    assert list(_ring_copies(hlo, B, max_len, slab)) == []
    short = _compile_decode_block(one_chip, cfg, B, 128, 8)[0]
    grown = (compiled.memory_analysis().temp_size_in_bytes
             - short.memory_analysis().temp_size_in_bytes)
    assert grown < slab, (grown, slab)
