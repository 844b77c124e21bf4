"""Pure-jnp oracles for every kernel — the correctness ground truth.

Each ``*_ref`` mirrors its kernel's contract exactly (same dtypes, same
rounding, same scale semantics) with no Pallas, so tests can
``assert_allclose`` across shape/dtype sweeps, and the dry-run lowers the
same math through XLA when Pallas-TPU is unavailable.

Two flash references exist: :func:`flash_attention_ref` is the exact
O(Sq·Sk)-memory softmax oracle tests compare against, and
:func:`flash_attention_chunked_ref` is the blockwise online-softmax
lowering (O(S·chunk) memory) that ``ops.flash_attention`` uses off-TPU
for long sequences — formerly ``models/transformer._flash_sdpa``, now a
kernel-layer concern so dry-run HLO never materializes an S² scores
tensor.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bitfluid as bf

_ACTS = {
    "none": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "silu": lambda x: x * jax.nn.sigmoid(x),
    "gelu": jax.nn.gelu,
}


def _int8_dot(x_q, w_q):
    return jax.lax.dot_general(
        x_q, w_q, dimension_numbers=(((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def bitplane_matmul_ref(x_q: jnp.ndarray, w_q: jnp.ndarray,
                        n_planes: int = 8) -> jnp.ndarray:
    """Plane-serial contract, single-dot form (int32-exact).

    The kernel's plane walk computes  sum_j w_j * (x_q @ plane_j)  over the
    low ``n_planes`` two's-complement field of the container — which is
    identically  x_q @ sign_extend(w_q & (2^n - 1))  (the weighted planes
    reassemble the masked field; see core/bitfluid.bitplane_matmul_ref for
    the loop-form oracle).  One dot instead of ``n_planes`` keeps the XLA
    serving path at container cost — the plane-count cost model is a TPU
    (Pallas) property, not a CPU one.
    """
    field = w_q.astype(jnp.int32) & ((1 << n_planes) - 1)
    sign = (field >> (n_planes - 1)) & 1                 # two's-complement
    w = (field - sign * (1 << n_planes)).astype(jnp.int8)
    return _int8_dot(x_q, w)


def quant_matmul_ref(x_q, w_q, scale, bias, act: str = "none",
                     out_dtype=jnp.float32):
    y = _int8_dot(x_q, w_q).astype(jnp.float32) * scale + bias
    return _ACTS[act](y).astype(out_dtype)


def int4_matmul_ref(x_q, w_packed, scale, out_dtype=jnp.float32):
    w = bf.unpack_int4_halves(w_packed)
    return (_int8_dot(x_q, w).astype(jnp.float32) * scale).astype(out_dtype)


def flash_attention_ref(q, k, v, causal: bool = True, window: int = 0):
    """(BH, Sq, hd) softmax attention oracle (f32 math)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    Sq, Sk = s.shape[1], s.shape[2]
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    vis = jnp.ones((Sq, Sk), bool)
    if causal:
        vis &= kpos <= qpos
    if window:
        vis &= kpos > qpos - window
    s = jnp.where(vis[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


NEG_INF = -1e30
FLASH_CHUNK = 2048


def _pad_axis(x, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention_chunked_ref(q, k, v, causal: bool = True,
                                window: int = 0,
                                chunk: int = FLASH_CHUNK) -> jnp.ndarray:
    """Blockwise (flash) attention in pure JAX: O(S·chunk) memory.

    q: (BH, Sq, hd); k, v: (BH, Sk, hd); positions are 0..S-1 (lock-step
    sequences — ragged callers mask upstream).  Scores exist only as one
    (BH, Qc, Kc) tile per scan step, so XLA-lowered artifacts (dry runs,
    CPU CI) carry the same O(S) memory posture as the Pallas kernel.
    Accumulation is f32 with bf16 tiles when the inputs are bf16,
    matching the Pallas kernel's MXU dtype discipline.
    """
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    Qc, Kc = min(chunk, Sq), min(chunk, Sk)
    qp_full = _pad_axis(q, 1, Qc)
    kp_full = _pad_axis(k, 1, Kc)
    vp_full = _pad_axis(v, 1, Kc)
    nq, nk = qp_full.shape[1] // Qc, kp_full.shape[1] // Kc
    scale = hd ** -0.5

    q5 = jnp.moveaxis(qp_full.reshape(BH, nq, Qc, hd), 1, 0)
    k5 = jnp.moveaxis(kp_full.reshape(BH, nk, Kc, hd), 1, 0)
    v5 = jnp.moveaxis(vp_full.reshape(BH, nk, Kc, hd), 1, 0)
    qpos = jnp.arange(nq * Qc).reshape(nq, Qc)
    kpos = jnp.arange(nk * Kc).reshape(nk, Kc)

    def q_block(_, xs_q):
        qb, qpb = xs_q                            # (BH, Qc, hd), (Qc,)

        def kv_block(carry, xs_k):
            m, l, acc = carry
            kb, vb, kpb = xs_k                    # (BH, Kc, hd), (Kc,)
            s = jnp.einsum("bqd,bkd->bqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            vis = kpb[None, :] < Sk               # padded key slots
            if causal:
                vis &= kpb[None, :] <= qpb[:, None]
                if window:
                    vis &= kpb[None, :] > qpb[:, None] - window
            s = jnp.where(vis[None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(vis[None], p, 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = (acc * corr[..., None]
                   + jnp.einsum("bqk,bkd->bqd", p.astype(vb.dtype), vb,
                                preferred_element_type=jnp.float32))
            return (m_new, l, acc), ()

        m0 = jnp.full((BH, Qc), NEG_INF, jnp.float32)
        l0 = jnp.zeros((BH, Qc), jnp.float32)
        a0 = jnp.zeros((BH, Qc, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, a0), (k5, v5, kpos))
        return None, acc / jnp.maximum(l, 1e-30)[..., None]

    _, blocks = jax.lax.scan(q_block, None, (q5, qpos))   # (nq, BH, Qc, hd)
    out = jnp.moveaxis(blocks, 0, 1).reshape(BH, nq * Qc, hd)
    return out[:, :Sq].astype(q.dtype)


def ssm_update_ref(state, layer, x, dt, dA, b, c, live):
    """Oracle of ``ssm_update``: one decode step of layer ``layer`` of a
    stacked Mamba-2 state (L, B, H, P, N) f32.  x (B, H, P), dt and dA
    (B, H), b and c (B, N), live (B,).  Live rows: h = dA h + (dt x) B,
    y = h . C; empty rows keep their state and give y = 0.  Returns
    (y (B, H, P), state)."""
    s = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    new = (s * dA[:, :, None, None]
           + (dt[:, :, None] * x)[..., None] * b[:, None, None, :])
    y = jnp.sum(new * c[:, None, None, :], axis=-1)
    on = jnp.asarray(live).astype(bool)
    new = jnp.where(on[:, None, None, None], new, s)
    y = jnp.where(on[:, None, None], y, 0.0)
    return y, jax.lax.dynamic_update_index_in_dim(state, new, layer, 0)
