"""Pallas kernels vs ref.py oracles: shape/dtype sweeps in interpret mode."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import bitfluid as bf
from repro.kernels import ops, ref
from repro.models import lm


@pytest.mark.parametrize("bits", [1, 2, 4, 8])
@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 384, 128),
                                   (64, 256, 512)])
def test_bitplane_matmul_sweep(rng, bits, shape):
    M, K, N = shape
    x = rng.integers(-127, 128, (M, K)).astype(np.int8)
    # b-bit two's complement range [-2^(b-1), 2^(b-1)-1] (1 bit = {-1, 0})
    w = rng.integers(-(2 ** (bits - 1)), 2 ** (bits - 1), (K, N)
                     ).astype(np.int8)
    exact = x.astype(np.int64) @ w.astype(np.int64)
    out = ops.bitplane_matmul(jnp.asarray(x), jnp.asarray(w),
                              n_planes=bits, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), exact)
    out_ref = ref.bitplane_matmul_ref(jnp.asarray(x), jnp.asarray(w), bits)
    np.testing.assert_array_equal(np.asarray(out_ref), exact)


def test_bitplane_matmul_nonaligned(rng):
    """ops.py pads non-128-multiples."""
    x = rng.integers(-10, 10, (100, 200)).astype(np.int8)
    w = rng.integers(-10, 10, (200, 72)).astype(np.int8)
    out = ops.bitplane_matmul(jnp.asarray(x), jnp.asarray(w),
                              n_planes=8, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(out), x.astype(np.int64) @ w.astype(np.int64))


@pytest.mark.parametrize("maker,bits", [("quantize", 8), ("quantize", 4),
                                        ("quantize", 2),
                                        ("quantize_weight", 8),
                                        ("quantize_weight", 4),
                                        ("int4_halves", 4)])
def test_containers_on_symmetric_grid(rng, maker, bits):
    """Every container maker stays on the symmetric grid |q| <= 2^(b-1)-1
    (the container invariant), even where values clip: requantizing at
    shift 0 is then the identity, which the serve kernel relies on."""
    w = jnp.asarray((rng.standard_t(2, size=(96, 64)) * 0.1
                     ).astype(np.float32))
    if maker == "quantize":                  # half the scale: many clip
        q = bf.quantize(w, bf.symmetric_scale(w, bits, axis=0) / 2, bits)
    else:
        q, _ = lm._quantize_weight(w, bits)
    if maker == "int4_halves":
        q4 = bf.unpack_int4_halves(bf.pack_int4_halves(q))
        np.testing.assert_array_equal(np.asarray(q4), np.asarray(q))
        q = q4
    lim = 2 ** (bits - 1) - 1
    qn = np.asarray(q, np.int32)
    assert qn.max() <= lim and qn.min() >= -lim
    assert np.abs(qn).max() == lim           # the grid's edge is reached
    np.testing.assert_array_equal(
        np.asarray(bf.requant_shift(q, bits, from_bits=bits)), qn)


@pytest.mark.parametrize("act", ["none", "relu", "silu", "gelu"])
@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_sweep(rng, act, out_dtype):
    M, K, N = 128, 256, 128
    x = rng.integers(-127, 128, (M, K)).astype(np.int8)
    w = rng.integers(-127, 128, (K, N)).astype(np.int8)
    s = rng.uniform(0.001, 0.05, (1, N)).astype(np.float32)
    b = rng.normal(size=(1, N)).astype(np.float32)
    got = ops.quant_matmul(jnp.asarray(x), jnp.asarray(w), jnp.asarray(s),
                           jnp.asarray(b), act=act, out_dtype=out_dtype,
                           interpret=True)
    want = ref.quant_matmul_ref(jnp.asarray(x), jnp.asarray(w),
                                jnp.asarray(s), jnp.asarray(b), act,
                                out_dtype)
    assert got.dtype == out_dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2 if out_dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2)


@pytest.mark.parametrize("shape", [(128, 128, 256), (128, 256, 512)])
def test_int4_matmul_sweep(rng, shape):
    M, K, N = shape
    x = rng.integers(-127, 128, (M, K)).astype(np.int8)
    q4 = rng.integers(-8, 8, (K, N)).astype(np.int8)
    packed = bf.pack_int4_halves(jnp.asarray(q4))
    s = rng.uniform(0.001, 0.05, (1, N)).astype(np.float32)
    got = ops.int4_matmul(jnp.asarray(x), packed, jnp.asarray(s),
                          interpret=True)
    want = (x.astype(np.int64) @ q4.astype(np.int64)).astype(np.float32) * s
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_fluid_linear_precision_cost_scaling(rng):
    """The plane kernel's cost scales with wbits (probe: result exactness
    at stored precision, approximation below it)."""
    x = rng.normal(size=(32, 128)).astype(np.float32)
    w = (rng.normal(size=(128, 64)) * 0.05).astype(np.float32)
    ws = bf.symmetric_scale(jnp.asarray(w), 8, axis=0)
    qw = bf.quantize(jnp.asarray(w), ws, 8)
    y8 = ops.fluid_linear(jnp.asarray(x), qw, ws, wbits=8, interpret=True)
    exact = np.asarray(bf.dequantize(qw, ws))
    np.testing.assert_allclose(
        np.asarray(y8), np.asarray(
            bf.fake_quant(jnp.asarray(x), 8) @ jnp.asarray(exact)),
        rtol=5e-2, atol=5e-2)


@pytest.mark.skipif(ops.use_pallas(),
                    reason="dispatch forced to Pallas (REPRO_PALLAS)")
def test_dispatch_uses_ref_on_cpu(rng):
    """Off-TPU without interpret, ops route through XLA ref (same math)."""
    x = rng.integers(-10, 10, (64, 128)).astype(np.int8)
    w = rng.integers(-10, 10, (128, 64)).astype(np.int8)
    assert not ops.use_pallas()
    out = ops.bitplane_matmul(jnp.asarray(x), jnp.asarray(w), n_planes=8)
    np.testing.assert_array_equal(
        np.asarray(out), x.astype(np.int64) @ w.astype(np.int64))


@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64)])
@pytest.mark.parametrize("shape", [(4, 128, 64), (2, 256, 128), (3, 100, 48)])
def test_flash_attention_sweep(rng, causal, window, shape):
    BH, S, hd = shape
    q = jnp.asarray(rng.normal(size=(BH, S, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(BH, S, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(BH, S, hd)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_cross_lengths(rng):
    """Sq != Sk (cross-attention shape) with padded key masking."""
    q = jnp.asarray(rng.normal(size=(2, 64, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 200, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 200, 32)), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=False, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
