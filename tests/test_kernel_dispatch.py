"""Kernel-dispatch parity: the serve compute path now lives in
kernels/ops.py — it must be BIT-EXACT against the pre-refactor inline
math (the `_apply_linear1` serve branch, kept verbatim below as the
oracle), and grouped per-row dispatch must be bit-exact against the
per-row vmap baseline, for int8 and packed-int4 containers alike."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bitfluid as bf
from repro.kernels import ops, ref
from repro.models import common as cm


# ---------------------------------------------------------------------------
# Oracles: the pre-refactor inline serve math, verbatim.
# ---------------------------------------------------------------------------

def _inline_serve_linear(p, x, wbits, abits):
    if "q4" in p:
        qw = bf.unpack_int4_halves(p["q4"])
        from_bits = 4
    else:
        qw, from_bits = p["q"], 8
    w_q = bf.requant_shift(qw, wbits, from_bits=from_bits)
    w_s = bf.effective_scale(p["s"], wbits, from_bits=from_bits)
    x2 = x.astype(jnp.float32)
    x_scale = bf.symmetric_scale(x2, abits)
    x_q = bf.quantize(x2, x_scale, abits)
    acc = jax.lax.dot_general(
        x_q, w_q, dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * x_scale * w_s
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(cm.DTYPE)


def _inline_vmap(p, x, wbits, abits):
    B = x.shape[0]
    wb = jnp.broadcast_to(jnp.asarray(wbits, jnp.int32), (B,))
    ab = jnp.broadcast_to(jnp.asarray(abits, jnp.int32), (B,))
    return jax.vmap(lambda xr, w, a: _inline_serve_linear(p, xr, w, a))(
        x, wb, ab)


def _container(rng, container, K=64, N=48, bias=True):
    w = jnp.asarray((rng.normal(size=(K, N)) * 0.1).astype(np.float32))
    p = {"w": w}
    if bias:
        p["b"] = jnp.asarray(rng.normal(size=(N,)).astype(np.float32))
    return cm.quantize_linear(p, container)


def _f32(x):
    return np.asarray(x, np.float32)


# ---------------------------------------------------------------------------
# Scalar-bits parity (the container path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("container", ["int8", "int4"])
@pytest.mark.parametrize("wbits", [2, 4, 8])
def test_scalar_bits_parity(rng, container, wbits):
    p = _container(rng, container)
    x = jnp.asarray(rng.normal(size=(3, 5, 64)).astype(np.float32))
    got = cm.apply_linear(p, x, wbits, 8)
    want = _inline_serve_linear(p, x, wbits, 8)
    np.testing.assert_array_equal(_f32(got), _f32(want))


@pytest.mark.parametrize("container", ["int8", "int4"])
def test_traced_scalar_bits_parity(rng, container):
    """(L,)-vector bits arrive in models as traced scalars via scan; the
    dispatch must stay bit-exact when bits are runtime tensors."""
    p = _container(rng, container)
    x = jnp.asarray(rng.normal(size=(2, 4, 64)).astype(np.float32))

    @jax.jit
    def run(wb, ab):
        return cm.apply_linear(p, x, wb, ab)

    for wb in (2, 4, 8):
        got = run(jnp.asarray(wb, jnp.int32), jnp.asarray(8, jnp.int32))
        want = _inline_serve_linear(p, x, wb, 8)
        np.testing.assert_array_equal(_f32(got), _f32(want))


# ---------------------------------------------------------------------------
# Per-row bits: grouped dispatch vs the inline per-row vmap oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("container", ["int8", "int4"])
@pytest.mark.parametrize("seq", [1, 4])
def test_grouped_dispatch_matches_vmap_oracle(rng, container, seq):
    p = _container(rng, container)
    x = jnp.asarray(rng.normal(size=(6, seq, 64)).astype(np.float32))
    wb = jnp.asarray([2, 4, 8, 8, 4, 2], jnp.int32)
    ab = jnp.asarray([8, 8, 4, 8, 2, 8], jnp.int32)
    got = cm.apply_linear(p, x, wb, ab)
    want = _inline_vmap(p, x, wb, ab)
    np.testing.assert_array_equal(_f32(got), _f32(want))


def test_grouped_dispatch_scalar_abits_vector_wbits(rng):
    p = _container(rng, "int8")
    x = jnp.asarray(rng.normal(size=(4, 2, 64)).astype(np.float32))
    wb = jnp.asarray([8, 4, 4, 8], jnp.int32)
    got = cm.apply_linear(p, x, wb, 8)
    want = _inline_vmap(p, x, wb, 8)
    np.testing.assert_array_equal(_f32(got), _f32(want))


def test_narrowed_families_stay_exact_and_snap_up(rng):
    """An engine narrows the family set to its controller's bits: values
    in the set stay exact; out-of-set values snap UP to the next family."""
    p = _container(rng, "int8", bias=False)
    x = jnp.asarray(rng.normal(size=(4, 1, 64)).astype(np.float32))
    wb = jnp.asarray([4, 8, 4, 8], jnp.int32)
    with ops.bit_families((4, 8)):
        got = cm.apply_linear(p, x, wb, 8)
    np.testing.assert_array_equal(_f32(got), _f32(_inline_vmap(p, x, wb, 8)))
    with ops.bit_families((4, 8)):
        snapped = cm.apply_linear(p, x, jnp.asarray([3, 3, 3, 3], jnp.int32),
                                  8)
    np.testing.assert_array_equal(
        _f32(snapped),
        _f32(_inline_vmap(p, x, jnp.asarray([4, 4, 4, 4], jnp.int32), 8)))


def test_bit_families_context_restores():
    before = ops.get_bit_families()
    with ops.bit_families((4, 8)):
        assert ops.get_bit_families() == (4, 8)
    assert ops.get_bit_families() == before
    with pytest.raises(ValueError):
        ops.set_bit_families(())


def test_grouped_dispatch_zero_retrace(rng):
    """Family membership is data: changing the per-row bit mix never
    retraces the jitted caller."""
    p = _container(rng, "int8")
    x = jnp.asarray(rng.normal(size=(4, 1, 64)).astype(np.float32))
    traces = []

    @jax.jit
    def run(wb):
        traces.append(1)
        return cm.apply_linear(p, x, wb, 8)

    for mix in ([2, 4, 6, 8], [8, 8, 8, 8], [4, 2, 4, 2]):
        run(jnp.asarray(mix, jnp.int32)).block_until_ready()
    assert len(traces) == 1


# ---------------------------------------------------------------------------
# Satellite: _blocks_for, the serve GEMM's tiles, int4 alignment behavior
# ---------------------------------------------------------------------------

def test_blocks_for_shrinks_all_dims():
    assert ops._blocks_for(512, 512, 512) == (128, 128, 128)
    assert ops._blocks_for(64, 32, 16) == (64, 32, 16)
    assert ops._blocks_for(1, 2, 3) == (8, 8, 8)        # floor at 8
    assert ops._blocks_for(100, 72, 200) == (128, 128, 128)  # next pow2 >= 128


# qwen3-4b's seven serve linears, (K, N)
QWEN3_4B_LINEARS = {"q": (2560, 4096), "k": (2560, 1024), "v": (2560, 1024),
                    "o": (4096, 2560), "gate": (2560, 9728),
                    "up": (2560, 9728), "down": (9728, 2560)}
V5E_VMEM = 128 << 20


@pytest.mark.parametrize("n_planes", [8, 4])
@pytest.mark.parametrize("m", [48, 512], ids=["decode", "prefill"])
def test_bitplane_tiles_qwen3_4b(m, n_planes):
    """The serve GEMM's tiles at qwen3-4b's shapes: blocks divide the
    padded dims (aligned weights unpadded), the working set — the plane
    walk's int8 planes and int32 temporaries included — stays inside the
    VMEM limit the kernel is compiled with, and a layer is a few dozen
    grid steps (6,160 at the 128-capped blocks)."""
    from repro.kernels.bitplane_matmul import vmem_bytes, vmem_limit_bytes
    steps = 0
    for K, N in QWEN3_4B_LINEARS.values():
        t = ops._bitplane_tiles(m, N, K, n_planes)
        assert t.mp % t.bm == 0 and t.np % t.bn == 0 and t.kp % t.bk == 0
        assert t.mp >= m and (t.kp, t.np) == (K, N)
        assert t.bm <= 512 and t.bm % 32 == 0 and t.bn % 128 == 0
        ws = vmem_bytes(t.bm, t.bn, t.bk, n_planes)
        planes = (n_planes + 1) * t.bk * t.bn if n_planes < 8 else 0
        assert planes < ws <= ops._VMEM_BUDGET
        assert ws <= vmem_limit_bytes(t.bm, t.bn, t.bk, n_planes) <= V5E_VMEM
        steps += (t.mp // t.bm) * (t.np // t.bn) * (t.kp // t.bk)
    assert steps <= 100


@pytest.mark.parametrize("shape", [(48, 256, 19 * 128), (512, 384, 256),
                                   (64, 300, 640)],
                         ids=["odd_n_blocks", "m512", "k_padded"])
@pytest.mark.parametrize("container", ["int8", "q4"])
@pytest.mark.parametrize("traced", [False, True], ids=["static", "traced"])
@pytest.mark.parametrize("to_bits", ops.BIT_FAMILIES)
def test_serve_gemm_requant_on_tile_exact(rng, shape, container, traced,
                                          to_bits):
    """The kernel at its chosen tiles, requantizing on the tile, is
    bit-identical to ``requant_shift`` then the plane-walk oracle, for
    every family, static and traced bits, int8 and q4 containers."""
    M, K, N = shape
    from_bits = 8 if container == "int8" else 4
    lim = 2 ** (from_bits - 1) - 1                 # symmetric container grid
    x = jnp.asarray(rng.integers(-127, 128, (M, K)).astype(np.int8))
    w = jnp.asarray(rng.integers(-lim, lim + 1, (K, N)).astype(np.int8))
    if container == "q4":
        w = bf.unpack_int4_halves(bf.pack_int4_halves(w))
    if traced:
        got = jax.jit(lambda a, b, t: ops.int8_accum(
            a, b, t, from_bits=from_bits, interpret=True))(
                x, w, jnp.asarray(to_bits, jnp.int32))
    else:
        got = ops.int8_accum(x, w, to_bits, from_bits=from_bits,
                             interpret=True)
    w_req = bf.requant_shift(w, to_bits, from_bits=from_bits)
    want = ref.bitplane_matmul_ref(x, w_req, 8 if traced else to_bits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    exact = np.asarray(x, np.int64) @ np.asarray(w_req, np.int64)
    np.testing.assert_array_equal(np.asarray(got), exact)


# (M, K, N): aligned; N unaligned and stored K-minor (in the kernel as its
# transpose); N unaligned with a K under 128 (stored N-minor, ragged)
STACK_SHAPES = {"aligned": (48, 256, 384), "n_unaligned": (48, 256, 296),
                "n_unaligned_small_k": (16, 64, 296)}


@pytest.mark.parametrize("case", ["static8", "static4", "traced_shift",
                                  "per_row"])
@pytest.mark.parametrize("shape", list(STACK_SHAPES))
def test_stacked_weight_matches_the_layer_slice(rng, case, shape):
    """The stacked entry — the whole (L, K, N) container and a layer index
    traced by a ``lax.scan`` — is bit-identical to the kernel on the
    layer's own (K, N) slice, for static bits at 8 and 4 planes, traced
    bits requantized on the tile (shift 4), and per-row bits through the
    grouped path with two families."""
    M, K, N = STACK_SHAPES[shape]
    L = 3
    w = jnp.asarray(rng.integers(-127, 128, (L, K, N)).astype(np.int8))
    s = jnp.asarray(rng.uniform(0.01, 0.02, (L, 1, N)).astype(np.float32))
    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32))
    xq = jnp.asarray(rng.integers(-127, 128, (M, K)).astype(np.int8))
    wb_rows = jnp.asarray([4, 8] * (M // 2), jnp.int32)
    bits = {"static8": 8, "static4": 4}.get(case)

    def gemm(w, layer, i, tb):
        tb = wb_rows if case == "per_row" else bits or tb
        if case != "per_row":
            return ops.int8_accum(xq, w, tb, layer=layer, interpret=True)
        p = {"q": w, "s": s[i]}
        if layer is not None:
            p["layer"] = layer
        with ops.bit_families((4, 8)):
            return ops.serve_linear(p, x, tb, 8, interpret=True)

    @functools.partial(jax.jit, static_argnums=0)
    def scan(stacked, w, tb):
        def body(_, i):
            if stacked:
                return (), gemm(w, i, i, tb)
            return (), gemm(w[i], None, i, tb)       # the layer's slice
        return jax.lax.scan(body, (), jnp.arange(L, dtype=jnp.int32))[1]

    tb = jnp.asarray(4, jnp.int32)
    got = scan(True, w, tb)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(scan(False, w, tb)))
    if case != "per_row":
        for l in range(L):
            exact = np.asarray(xq, np.int64) @ np.asarray(
                bf.requant_shift(w[l], bits or 4), np.int64)
            np.testing.assert_array_equal(np.asarray(got[l]), exact)


def test_weight_feeds_count_each_linear(rng):
    """``count_weight_feeds`` tells stacked linears from 2-D ones; a stack
    whose K the kernel would pad is sliced first, and counted as such."""
    x = jnp.asarray(rng.normal(size=(2, 128)).astype(np.float32))
    s = jnp.ones((1, 256), jnp.float32)
    stack = jnp.asarray(rng.integers(-127, 128, (3, 128, 256)), jnp.int8)
    odd = jnp.asarray(rng.integers(-127, 128, (3, 300, 256)), jnp.int8)
    x300 = jnp.asarray(rng.normal(size=(2, 300)).astype(np.float32))
    with ops.count_weight_feeds() as feeds:
        a = ops.serve_linear({"q": stack, "s": s, "layer": 1}, x)
        ops.serve_linear({"q": stack[1], "s": s}, x)
        b = ops.serve_linear({"q": odd, "s": s, "layer": 2}, x300)
    assert feeds == [1, 2]
    np.testing.assert_array_equal(
        np.asarray(a), np.asarray(ops.serve_linear({"q": stack[1], "s": s},
                                                   x)))
    np.testing.assert_array_equal(
        np.asarray(b), np.asarray(ops.serve_linear({"q": odd[2], "s": s},
                                                   x300)))


def test_int4_matmul_unaligned_falls_back(rng):
    """Packed-column padding would split nibble halves; an unaligned
    packed width runs the kernel on one full-width column block (exact
    against the int64 oracle) instead of crashing or leaving the kernel."""
    M, K, N = 16, 64, 72                    # N/2 = 36 does not tile
    x = rng.integers(-127, 128, (M, K)).astype(np.int8)
    q4 = rng.integers(-8, 8, (K, N)).astype(np.int8)
    packed = bf.pack_int4_halves(jnp.asarray(q4))
    s = rng.uniform(0.001, 0.05, (1, N)).astype(np.float32)
    got = ops.int4_matmul(jnp.asarray(x), packed, jnp.asarray(s),
                          interpret=True)
    want = (x.astype(np.int64) @ q4.astype(np.int64)).astype(np.float32) * s
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_int4_matmul_unaligned_raises_for_tpu(rng):
    """Off interpret mode (the TPU kernel), an unaligned packed width is
    refused with its shapes before anything compiles."""
    x = jnp.asarray(rng.integers(-10, 10, (8, 64)).astype(np.int8))
    packed = jnp.zeros((64, 36), jnp.uint8)             # N/2 = 36
    ops.set_force_pallas(True, interpret=False)
    try:
        with pytest.raises(ValueError, match=r"\(64, 36\)"):
            ops.int4_matmul(x, packed, jnp.ones((1, 72)))
    finally:
        ops.set_force_pallas(None, interpret=ops.INTERPRET_ENV)


def test_int4_matmul_bad_shapes_raise(rng):
    x = jnp.asarray(rng.integers(-10, 10, (8, 64)).astype(np.int8))
    packed = jnp.zeros((32, 16), jnp.uint8)             # K=32 != 64
    with pytest.raises(ValueError, match="K"):
        ops.int4_matmul(x, packed, jnp.ones((1, 32)))
    packed = jnp.zeros((64, 16), jnp.uint8)             # N = 32
    with pytest.raises(ValueError, match="scale"):
        ops.int4_matmul(x, packed, jnp.ones((1, 7)))


# ---------------------------------------------------------------------------
# Flash attention: chunked ref + model routing through the dispatcher
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 24)])
def test_flash_chunked_ref_matches_oracle(rng, causal, window):
    q = jnp.asarray(rng.normal(size=(2, 100, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 80, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 80, 32)), jnp.float32)
    got = ref.flash_attention_chunked_ref(q, k, v, causal=causal,
                                          window=window, chunk=32)
    want = ref.flash_attention_ref(q, k, v, causal, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_long_seq_attention_routes_through_ops(rng, monkeypatch):
    """No flash math inline in models/: above FLASH_THRESHOLD the
    attention block must reach ops.flash_attention, and its output must
    match the short-path masked SDPA."""
    from repro import configs
    from repro.models import transformer as tf

    cfg = configs.get_smoke("qwen3_4b")
    p = tf.attn_init(jax.random.PRNGKey(0), cfg)
    S = 32
    x = jnp.asarray(rng.normal(size=(2, S, cfg.d_model)) * 0.1, cm.DTYPE)
    positions = jnp.arange(S, dtype=jnp.int32)[None]

    calls = []
    orig = ops.flash_attention

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(tf.kops, "flash_attention", spy)
    monkeypatch.setattr(tf, "FLASH_THRESHOLD", 16)
    out_flash, _ = tf.attention(p, x, cfg, positions=positions)
    assert len(calls) == 1
    monkeypatch.setattr(tf, "FLASH_THRESHOLD", 2048)
    out_sdpa, _ = tf.attention(p, x, cfg, positions=positions)
    np.testing.assert_allclose(_f32(out_flash), _f32(out_sdpa),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# EDP pricing (apsim.metrics.price_bit_vector)
# ---------------------------------------------------------------------------

def test_price_bit_vector_scales_with_bits():
    from repro.apsim import metrics as apm

    gemms = (((64, 128), (128, 64)),) * 4
    c8 = apm.price_bit_vector(gemms, [8] * 4, [8] * 4)
    c4 = apm.price_bit_vector(gemms, [4] * 4, [4] * 4)
    assert len(c8.per_layer_cycles) == len(c8.per_layer_energy_j) == 4
    assert 0 < c4.energy_j < c8.energy_j
    assert 0 < c4.cycles < c8.cycles
    assert 0 < c4.edp < c8.edp
    mixed = apm.price_bit_vector(gemms, [4, 8, 4, 8], [8] * 4)
    assert c4.energy_j < mixed.energy_j < c8.energy_j
    with_head = apm.price_bit_vector(gemms, [8] * 4, [8] * 4,
                                     head=(64, 512))
    assert len(with_head.per_layer_cycles) == 5
    assert with_head.cycles > c8.cycles
    with pytest.raises(ValueError):
        apm.price_bit_vector(gemms, [8] * 3, [8] * 4)


def test_layer_gemm_dims_cover_bit_slots():
    from repro import configs
    from repro.models import lm

    for arch in ("qwen3_4b", "mamba2_1_3b", "zamba2_2_7b",
                 "seamless_m4t_medium", "kimi_k2_1t_a32b"):
        cfg = configs.get_smoke(arch)
        gemms = lm.layer_gemm_dims(cfg)
        assert len(gemms) == lm.n_bit_slots(cfg), arch
        assert all(K > 0 and N > 0 for per in gemms for K, N in per), arch
