"""Bit-fluid quantization — the paper's contribution as composable JAX ops.

BF-IMNA's core insight: on bit-serial hardware, *precision is a runtime
axis* — a layer assigned ``b`` bits simply executes fewer bit passes, with
no hardware reconfiguration.  We map that insight onto TPU as follows
(DESIGN.md §2):

* Weights are stored once at the **container precision** (int8, or packed
  int4 nibbles).  An int8 word *is* its 8 bit planes; the bit-plane GEMM
  kernel (kernels/bitplane_matmul.py) walks planes exactly like the AP's
  bit-serial LUT walk, and masking planes = deactivating MSBs.
* Runtime precision switching uses **dyadic requantization**: a right shift
  ``q_b = round_half_even(q_8 / 2^(8-b))`` re-expresses the stored 8-bit
  value on a b-bit grid of the same scale family.  This matches HAWQ-V3's
  dyadic-arithmetic constraint [53] and makes the per-layer precision
  configuration an ordinary *runtime tensor* — one compiled program serves
  any static or dynamic mixed-precision configuration (the TPU analogue of
  "no reconfiguration overhead at run-time").
* **Container invariant**: every stored container holds values on the
  *symmetric* grid ``|q| <= 2^(b-1) - 1`` of its width ``b`` (``quantize``
  clips there; ``-2^(b-1)`` never occurs).  So ``requant_shift`` at
  ``to_bits >= from_bits`` (shift 0) is the identity, and the serve kernel
  skips it, sending the tile to the MXU as stored.  Anything that makes a
  container must keep this.
* Training uses fake-quant with a straight-through estimator so the same
  per-layer bit vector drives quantization-aware training.

All functions are pure and jit/vmap/scan-compatible; ``bits`` arguments may
be Python ints *or* traced scalars (bit fluidity as data).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

INT_DTYPE = jnp.int8
ACC_DTYPE = jnp.int32


# ---------------------------------------------------------------------------
# Scales / quantize / dequantize (symmetric, mid-rise, power-of-two friendly)
# ---------------------------------------------------------------------------

def qmax(bits) -> jnp.ndarray:
    """Largest magnitude representable at ``bits``: 2^(b-1) - 1."""
    return (2.0 ** (jnp.asarray(bits, jnp.float32) - 1.0)) - 1.0


def symmetric_scale(x: jnp.ndarray, bits, axis=None, eps: float = 1e-8):
    """Per-tensor (axis=None) or per-channel symmetric scale."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    return jnp.maximum(amax, eps).astype(jnp.float32) / qmax(bits)


def quantize(x: jnp.ndarray, scale: jnp.ndarray, bits) -> jnp.ndarray:
    """Symmetric quantization to a signed ``bits``-bit grid, stored as int8.

    Values occupy the low ``bits`` bits (two's complement); for bits < 8 the
    upper bit planes of the int8 container are sign extension — exactly the
    paper's "MSBs are deactivated" storage picture.
    """
    q = jnp.round(x / scale)
    lim = qmax(bits)
    return jnp.clip(q, -lim, lim).astype(INT_DTYPE)


def dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# Runtime-fluid dyadic requantization (the bit-fluid switch)
# ---------------------------------------------------------------------------

def requant_shift(q: jnp.ndarray, to_bits, from_bits: int = 8) -> jnp.ndarray:
    """Re-express an int ``from_bits`` value on a ``to_bits`` grid (dyadic).

    q_b = round(q / 2^(from-to)), then the caller's effective scale becomes
    ``scale * 2^(from-to)``.  ``to_bits`` may be a traced scalar — this is
    the zero-recompilation precision switch.  Rounding is round-half-away
    implemented with integer ops only (AP-friendly: shifts and adds).
    """
    to_bits = jnp.asarray(to_bits, ACC_DTYPE)
    shift = jnp.maximum(jnp.asarray(from_bits, ACC_DTYPE) - to_bits, 0)
    qi = q.astype(ACC_DTYPE)
    half = jnp.where(shift > 0, (1 << jnp.maximum(shift - 1, 0)), 0)
    rounded = jnp.where(qi >= 0, (qi + half) >> shift, -((-qi + half) >> shift))
    lim = (2 ** (to_bits - 1) - 1).astype(ACC_DTYPE)
    return jnp.clip(rounded, -lim, lim).astype(INT_DTYPE)


def effective_scale(scale: jnp.ndarray, to_bits, from_bits: int = 8):
    shift = jnp.maximum(from_bits - jnp.asarray(to_bits, jnp.float32), 0.0)
    return scale * (2.0 ** shift)


# ---------------------------------------------------------------------------
# Bit planes (two's complement) — the AP's native data layout
# ---------------------------------------------------------------------------

def bitplanes(q: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Decompose int8 ``q`` into ``bits`` {0,1} planes, LSB first.

    Plane weights are 2^j for j < bits-1 and -2^(bits-1) for the sign plane
    (two's complement), so  q == sum_j w_j * plane_j  exactly.
    """
    js = jnp.arange(bits, dtype=jnp.int32)
    u = q.astype(jnp.int32) & ((1 << bits) - 1)          # low `bits` field
    return ((u[None] >> js.reshape((bits,) + (1,) * q.ndim)) & 1).astype(INT_DTYPE)


def plane_weights(bits: int) -> jnp.ndarray:
    w = 2.0 ** jnp.arange(bits, dtype=jnp.float32)
    return w.at[bits - 1].set(-(2.0 ** (bits - 1)))


def from_bitplanes(planes: jnp.ndarray, bits: int) -> jnp.ndarray:
    w = plane_weights(bits).reshape((bits,) + (1,) * (planes.ndim - 1))
    return jnp.sum(planes.astype(jnp.float32) * w, axis=0).astype(INT_DTYPE)


# ---------------------------------------------------------------------------
# int4 packing (two nibbles per int8 byte) — decode-bandwidth container
# ---------------------------------------------------------------------------

def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """Pack int4 values (last axis even) into uint8 nibbles, low nibble first."""
    if q.shape[-1] % 2:
        raise ValueError("last axis must be even to pack nibbles")
    u = (q.astype(jnp.int32) & 0xF).astype(jnp.uint8)
    lo, hi = u[..., 0::2], u[..., 1::2]
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4(packed: jnp.ndarray) -> jnp.ndarray:
    """Unpack uint8 nibbles back to signed int8 in [-8, 7]."""
    lo = (packed & 0xF).astype(jnp.int8)
    hi = ((packed >> 4) & 0xF).astype(jnp.int8)
    both = jnp.stack([lo, hi], axis=-1).reshape(packed.shape[:-1] + (-1,))
    return jnp.where(both >= 8, both - 16, both).astype(INT_DTYPE)


def pack_int4_halves(q: jnp.ndarray) -> jnp.ndarray:
    """Half-split nibble layout: columns [0, N/2) in the low nibble, columns
    [N/2, N) in the high nibble.  Unpacking is a nibble select — no
    interleave — which keeps the Pallas int4 kernel's in-VMEM unpack a pure
    elementwise op (TPU-layout friendly; see kernels/int4_matmul.py)."""
    if q.shape[-1] % 2:
        raise ValueError("last axis must be even to pack nibbles")
    half = q.shape[-1] // 2
    lo = (q[..., :half].astype(jnp.int32) & 0xF).astype(jnp.uint8)
    hi = (q[..., half:].astype(jnp.int32) & 0xF).astype(jnp.uint8)
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4_halves(packed: jnp.ndarray) -> jnp.ndarray:
    lo = (packed & 0xF).astype(jnp.int8)
    hi = ((packed >> 4) & 0xF).astype(jnp.int8)
    both = jnp.concatenate([lo, hi], axis=-1)
    return jnp.where(both >= 8, both - 16, both).astype(INT_DTYPE)


# ---------------------------------------------------------------------------
# Fake quantization with straight-through estimator (QAT / mixed-prec train)
# ---------------------------------------------------------------------------

def fake_quant(x: jnp.ndarray, bits, axis=None) -> jnp.ndarray:
    """Differentiable b-bit quantization: forward quantizes, grad passes through.

    ``bits`` may be a traced scalar (per-layer bit vectors flow through scan).
    bits >= 16 acts as identity (the "fp path" sentinel).
    """
    scale = symmetric_scale(jax.lax.stop_gradient(x), bits, axis=axis)
    lim = qmax(bits)
    q = jnp.clip(jnp.round(x / scale), -lim, lim) * scale
    q = jnp.where(jnp.asarray(bits) >= 16, x, q.astype(x.dtype))
    return x + jax.lax.stop_gradient(q - x)


# ---------------------------------------------------------------------------
# Fluid integer matmul — XLA serving path (Pallas kernel mirrors this; see
# kernels/bitplane_matmul.py for the MXU bit-plane walk)
# ---------------------------------------------------------------------------

def fluid_int8_matmul(x: jnp.ndarray, qw: jnp.ndarray, w_scale: jnp.ndarray,
                      wbits=8, abits=8) -> jnp.ndarray:
    """y = x @ dequant(qw) at runtime precisions (wbits, abits).

    x        (..., K) float; dynamically quantized per-tensor to ``abits``.
    qw       (K, N) int8 container (8-bit grid), per-channel ``w_scale`` (N,).
    wbits    runtime scalar or python int — dyadic shift to the b-bit grid.

    Cost on TPU is one int8 MXU matmul regardless of bits (the MXU is a
    fixed 8-bit engine); *bandwidth* scales with the container (int4 packs
    exist for that — see int4 path), and numerics scale with (wbits, abits)
    exactly as on the AP.
    """
    w_q = requant_shift(qw, wbits)
    w_s = effective_scale(w_scale, wbits)
    x_scale = symmetric_scale(x, abits)
    x_q = quantize(x, x_scale, abits)
    acc = jax.lax.dot_general(
        x_q, w_q,
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=ACC_DTYPE)
    return acc.astype(jnp.float32) * x_scale * w_s


def bitplane_matmul_ref(x_q: jnp.ndarray, qw: jnp.ndarray, wbits: int) -> jnp.ndarray:
    """Plane-walk reference:  sum_j w_j * (x_q @ plane_j)  ==  x_q @ q_w.

    This is the mathematically-exact identity the Pallas kernel exploits;
    kept here (jnp-only) as the oracle for kernels/ref.py and tests.
    """
    planes = bitplanes(qw, wbits)                       # (wbits, K, N)
    w = plane_weights(wbits)
    acc = jnp.zeros(x_q.shape[:-1] + (qw.shape[-1],), jnp.float32)
    for j in range(wbits):
        d = jax.lax.dot_general(
            x_q, planes[j],
            dimension_numbers=(((x_q.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=ACC_DTYPE)
        acc = acc + w[j] * d.astype(jnp.float32)
    return acc
