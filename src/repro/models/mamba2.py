"""Mamba2 — SSD (state-space duality) blocks, chunked scan + O(1) decode.

Implements the Mamba2 layer of arXiv:2405.21060 in pure JAX:

  in_proj -> (z, x, B, C, dt);  depthwise causal conv(4) over (x, B, C);
  SSD core: chunked dual form — intra-chunk "attention-like" quadratic term
  plus an inter-chunk recurrence on the (H, P, N) state, lax.scan over
  chunks; gated RMSNorm; out_proj.

Bit fluidity applies to the in/out projections (the GEMM mass of the
layer); the associative scan itself is floating point — the paper's
bit-serial LUT walk has no analogue inside a recurrence (DESIGN.md §4).

Decode carries {"conv": (B, K-1, Cch), "ssm": (B, H, P, N)} — constant-size
state, which is why long_500k runs on this family.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm


def dims(cfg):
    d_inner = cfg.expand * cfg.d_model
    H = d_inner // cfg.ssm_head_dim
    return d_inner, H, cfg.ssm_state, cfg.ssm_head_dim


def mamba_init(key, cfg, prefix_dim: Optional[int] = None) -> dict:
    d = prefix_dim or cfg.d_model
    d_inner, H, N, P = dims(cfg)
    conv_ch = d_inner + 2 * N                       # x, B, C share the conv
    d_proj = 2 * d_inner + 2 * N + H                # z, x, B, C, dt
    ks = jax.random.split(key, 4)
    return {
        "ln": cm.norm_init(d, "rms"),
        "in_proj": cm.dense_init(ks[0], d, d_proj),
        "conv_w": (jax.random.normal(ks[1], (cfg.d_conv, conv_ch), jnp.float32)
                   * (cfg.d_conv ** -0.5)).astype(cm.DTYPE),
        "conv_b": jnp.zeros((conv_ch,), cm.DTYPE),
        "A_log": jnp.zeros((H,), jnp.float32),      # a = -exp(A_log) = -1
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.full((H,), -2.0, jnp.float32),
        "gn": cm.norm_init(d_inner, "rms"),
        "out_proj": cm.dense_init(ks[2], d_inner, d, scale=d_inner ** -0.5),
    }


def empty_state(cfg, batch: int, n_layers: int) -> dict:
    d_inner, H, N, P = dims(cfg)
    conv_ch = d_inner + 2 * N
    return {
        "conv": jnp.zeros((n_layers, batch, cfg.d_conv - 1, conv_ch), cm.DTYPE),
        "ssm": jnp.zeros((n_layers, batch, H, P, N), jnp.float32),
    }


def _split(p, xz, cfg):
    d_inner, H, N, P = dims(cfg)
    z, xBC, dt = jnp.split(xz, [d_inner, 2 * d_inner + 2 * N], axis=-1)
    return z, xBC, dt


def _causal_conv(w, b, xBC):
    """Depthwise causal conv, window K, via K shifted adds. xBC: (B,S,C);
    ``b`` may be None (no conv bias)."""
    K = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    S = xBC.shape[1]
    y = jnp.zeros_like(xBC, dtype=jnp.float32)
    for i in range(K):
        y = y + pad[:, i:i + S].astype(jnp.float32) * w[i].astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return jax.nn.silu(y).astype(xBC.dtype)


def ssd_chunked(xh, Bm, Cm, dt, a, h0, chunk: int):
    """SSD dual form.  xh (B,S,H,P); Bm/Cm (B,S,N); dt (B,S,H); a (H,)<0.
    h0: (B,H,P,N) initial state.  Returns (y (B,S,H,P), h_final)."""
    Bsz, S, H, Pd = xh.shape
    Sp = -(-S // chunk) * chunk
    if Sp != S:
        # zero-pad: dt=0 -> decay 1 and no input; B=C=0 -> no contribution
        pad = lambda t: jnp.pad(t, [(0, 0), (0, Sp - S)]
                                + [(0, 0)] * (t.ndim - 2))
        xh, Bm, Cm, dt = pad(xh), pad(Bm), pad(Cm), pad(dt)
    nc = Sp // chunk
    r = lambda t: t.reshape(Bsz, nc, chunk, *t.shape[2:])
    xh, Bm, Cm, dt = r(xh), r(Bm), r(Cm), r(dt)

    dA = a[None, None, None, :] * dt                       # (B,nc,Q,H) <= 0
    cs = jnp.cumsum(dA, axis=2)                            # within-chunk

    def chunk_step(h, inp):
        xh_c, B_c, C_c, dt_c, cs_c = inp                   # (B,Q,...) per chunk
        # intra-chunk: M[q,k] = exp(cs_q - cs_k) * (C_q.B_k) * dt_k  (q >= k)
        seg = cs_c[:, :, None, :] - cs_c[:, None, :, :]    # (B,Q,Q,H)
        iota = jnp.arange(cs_c.shape[1])
        causal = (iota[:, None] >= iota[None, :])[None, :, :, None]
        G = jnp.where(causal, jnp.exp(seg), 0.0)
        CB = jnp.einsum("bqn,bkn->bqk", C_c.astype(jnp.float32),
                        B_c.astype(jnp.float32))
        M = G * CB[:, :, :, None] * dt_c[:, None, :, :]    # (B,Q,Q,H)
        y_intra = jnp.einsum("bqkh,bkhp->bqhp", M, xh_c.astype(jnp.float32))
        # inter-chunk: contribution of carried state
        y_inter = jnp.einsum("bqn,bhpn->bqhp", C_c.astype(jnp.float32), h) \
            * jnp.exp(cs_c)[..., None]
        # state update
        decay_to_end = jnp.exp(cs_c[:, -1:, :] - cs_c)     # (B,Q,H)
        contrib = jnp.einsum("bqh,bqn,bqhp->bhpn",
                             decay_to_end * dt_c, B_c.astype(jnp.float32),
                             xh_c.astype(jnp.float32))
        h_new = h * jnp.exp(cs_c[:, -1])[:, :, None, None] + contrib
        return h_new, y_intra + y_inter

    to_scan = tuple(jnp.moveaxis(t, 1, 0) for t in (xh, Bm, Cm, dt, cs))
    h_final, ys = jax.lax.scan(chunk_step, h0, to_scan)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bsz, Sp, H, Pd)[:, :S]
    return y, h_final


def mixer_in(p, h, cfg, wbits=8, abits=8):
    """in_proj of the normed input, split: (z, xBC, dt_raw)."""
    return _split(p, cm.apply_linear(p["in_proj"], h, wbits, abits), cfg)


def split_xbc(xBC, cfg):
    """Conv output (..., S, C) -> x heads (..., S, H, P), B, C (..., S, N)."""
    d_inner, H, N, P = dims(cfg)
    xh = xBC[..., :d_inner].reshape(*xBC.shape[:-1], H, P)
    return xh, xBC[..., d_inner:d_inner + N], xBC[..., d_inner + N:]


def conv_step(p, conv_state, xBC):
    """One decode step of the causal conv: window (B, K-1, C) + the new
    input (B, 1, C) -> (activated output (B, 1, C) f32, next window)."""
    conv_in = jnp.concatenate([conv_state, xBC], axis=1)       # (B,K,C)
    y = jnp.einsum("bkc,kc->bc", conv_in.astype(jnp.float32),
                   p["conv_w"].astype(jnp.float32))
    if "conv_b" in p:
        y = y + p["conv_b"].astype(jnp.float32)
    return jax.nn.silu(y)[:, None], conv_in[:, 1:]


def mixer_out(p, y, xh, z, cfg, wbits=8, abits=8):
    """D skip, gate, grouped RMSNorm and out_proj of the SSM output
    y (B, S, H, P) f32."""
    d_inner = dims(cfg)[0]
    y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(*y.shape[:2], d_inner)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = cm.rms_norm(y.astype(cm.DTYPE), p["gn"]["scale"], cfg.norm_eps)
    return cm.apply_linear(p["out_proj"], y, wbits, abits)


def mamba_block(p, x, cfg, wbits=8, abits=8, *, state: Optional[dict] = None
                ) -> Tuple[jnp.ndarray, Optional[dict]]:
    """x: (B, S, d).

    * state=None, S>=1 .... chunked full-sequence (train); no state out.
    * state given, S>1 .... chunked prefill seeded by state; state out.
    * state given, S==1 ... single-step decode; state out.
    """
    d_inner, H, N, P = dims(cfg)
    res = x
    z, xBC, dt_raw = mixer_in(p, cm.rms_norm(x, p["ln"]["scale"],
                                             cfg.norm_eps), cfg, wbits, abits)
    a = -jnp.exp(p["A_log"])                                # (H,)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None])        # (B,S,H)

    if state is None or x.shape[1] > 1:
        xh, Bm, Cm = split_xbc(
            _causal_conv(p["conv_w"], p["conv_b"], xBC), cfg)
        h0 = (state["ssm"] if state is not None
              else jnp.zeros((x.shape[0], H, P, N), jnp.float32))
        y, h_fin = ssd_chunked(xh, Bm, Cm, dt, a, h0, cfg.ssm_chunk)
        new_state = None
        if state is not None:
            K = cfg.d_conv
            new_state = {"conv": xBC[:, x.shape[1] - (K - 1):, :],
                         "ssm": h_fin}
    else:
        # decode: roll conv window, single SSM step
        xBC1, conv = conv_step(p, state["conv"], xBC)
        xh, Bm, Cm = split_xbc(xBC1, cfg)
        dA = jnp.exp(a[None, :] * dt[:, 0])                      # (B,H)
        h = state["ssm"] * dA[..., None, None] + jnp.einsum(
            "bh,bn,bhp->bhpn", dt[:, 0], Bm[:, 0].astype(jnp.float32),
            xh[:, 0].astype(jnp.float32))
        y = jnp.einsum("bn,bhpn->bhp", Cm[:, 0].astype(jnp.float32), h)[:, None]
        new_state = {"conv": conv, "ssm": h}

    return res + mixer_out(p, y, xh, z, cfg, wbits, abits), new_state
