"""Shared building blocks: norms, RoPE, the bit-fluid linear, init helpers.

Parameter conventions
---------------------
Every linear is a dict ``{"w": (K, N) [, "b": (N,)]}`` in training form, or
``{"q": int8 (K, N), "s": f32 (1, N) [, "b"]}`` (int8 container) /
``{"q4": uint8 (K, N/2), "s": ...}`` (packed int4 container) in serving
form.  :func:`apply_linear` dispatches on the keys, so every model runs
both modes through one code path, and per-layer ``wbits`` / ``abits`` may
be traced scalars (bit fluidity as data — see core/bitfluid).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bitfluid as bf
from repro.kernels import ops as kops

DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               scale: Optional[float] = None, dtype=DTYPE):
    w_scale = scale if scale is not None else d_in ** -0.5
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32)
               * w_scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def quantize_linear(p: dict, container: str = "int8") -> dict:
    """Training-form linear -> serving-form (int8 or packed-int4 container).

    ``w`` may be ``(K, N)`` or a stack ``(G, K, N)`` (grouped-conv /
    expert stacks): scales are per-out-channel along the reduction axis
    (``axis=-2``), so every stacked slice quantizes independently."""
    w = p["w"].astype(jnp.float32)
    out = {}
    if container == "int4":
        s = bf.symmetric_scale(w, 4, axis=-2)
        q = bf.quantize(w, s, 4)
        out["q4"] = bf.pack_int4_halves(q)
        out["s"] = s
    else:
        s = bf.symmetric_scale(w, 8, axis=-2)
        out["q"] = bf.quantize(w, s, 8)
        out["s"] = s
    if "b" in p:
        out["b"] = p["b"]
    return out


# ---------------------------------------------------------------------------
# The bit-fluid linear
# ---------------------------------------------------------------------------

def apply_linear(p: dict, x: jnp.ndarray, wbits=8, abits=8) -> jnp.ndarray:
    """y = x @ W (+b) at runtime precisions; dispatches train/serve forms.

    ``wbits``/``abits`` are scalars (shared precision — the fast path) or
    ``(B,)`` vectors matching ``x``'s leading axis (per-request precision:
    serving batches whose rows carry different latency budgets).

    Serve-form containers ({"q"/"q4", "s"}) dispatch wholesale through the
    kernel layer (:func:`repro.kernels.ops.serve_linear`): scalar bits take
    the container path, per-row bits the bit-grouped batch path — one
    weight requantization and one GEMM per distinct bit family instead of
    per row, with rows numerically independent of their batch-mates
    (DESIGN.md §3/§6).  Train form stays here: fake-quant STE is float
    math, not a quantized kernel.
    """
    per_row = (getattr(wbits, "ndim", 0) >= 1
               or getattr(abits, "ndim", 0) >= 1)
    if "w" in p:                                     # train: fake-quant STE
        if per_row:
            B = x.shape[0]
            wb = jnp.broadcast_to(jnp.asarray(wbits, jnp.int32), (B,))
            ab = jnp.broadcast_to(jnp.asarray(abits, jnp.int32), (B,))
            return jax.vmap(lambda xr, w, a: _train_linear(p, xr, w, a))(
                x, wb, ab)
        return _train_linear(p, x, wbits, abits)
    return kops.serve_linear(p, x, wbits, abits).astype(DTYPE)


def _train_linear(p: dict, x: jnp.ndarray, wbits, abits) -> jnp.ndarray:
    """Scalar-bits fake-quant (STE) linear — the QAT path."""
    # stay bf16 END-TO-END around the dot (fake_quant rounds in f32
    # internally but preserves input dtype): both the forward TP
    # partial sums AND the backward dx cotangant reductions then move
    # bf16 — the dominant train all-reduces were f32 activation-shaped
    # cotangents from an f32 round-trip here (§Perf iter 6)
    w = bf.fake_quant(p["w"], wbits, axis=0)
    # one activation scale per sequence (reduce every axis but the batch),
    # as the per-row serve path does: a row's quantization never depends
    # on its batch-mates, so micro-batched gradient accumulation computes
    # the same forward as the full batch.  2-D inputs are one sequence
    # (the per-row vmap path) and keep a single scale.
    axes = tuple(range(1, x.ndim)) if x.ndim > 2 else None
    xq = bf.fake_quant(x.astype(DTYPE), abits, axis=axes)
    y = jnp.einsum("...k,kn->...n", xq, w,
                   preferred_element_type=DTYPE).astype(jnp.float32)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(DTYPE)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
            ).astype(x.dtype)


def apply_norm(p: dict, x: jnp.ndarray, kind: str, eps: float = 1e-5):
    if kind == "layer":
        return layer_norm(x, p["scale"], p["bias"], eps)
    return rms_norm(x, p["scale"], eps)


def norm_init(d: int, kind: str, dtype=DTYPE) -> dict:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layer":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : hd // 2], x32[..., hd // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Masks (iota-based: cheap to constant-fold, never materialized at scale)
# ---------------------------------------------------------------------------

def causal_mask_bias(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                     window: int = 0) -> jnp.ndarray:
    """Additive attention bias (Sq, Sk): 0 where visible, -inf elsewhere.

    ``window`` > 0 adds the sliding-window band (starcoder2)."""
    visible = k_pos[None, :] <= q_pos[:, None]
    if window:
        visible &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(visible, 0.0, -jnp.inf).astype(jnp.float32)


def causal_mask_bias_batched(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                             window: int = 0) -> jnp.ndarray:
    """Per-row additive bias (B, Sq, Sk) from per-row positions (B, S).

    Used when rows carry different valid lengths (continuous-batching
    prefill): padded tokens sit at ``EMPTY_POS`` (a huge positive
    sentinel), so real queries never see them, while padded queries still
    see the padded keys — their softmax stays finite and their outputs
    are discarded by the length-indexed logits gather."""
    visible = k_pos[:, None, :] <= q_pos[:, :, None]
    if window:
        visible &= k_pos[:, None, :] > (q_pos[:, :, None] - window)
    return jnp.where(visible, 0.0, -jnp.inf).astype(jnp.float32)
