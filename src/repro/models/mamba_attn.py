"""Layer-pattern hybrid: Mamba-2 mixers and GQA attention in one stack
(granite-4.0-h; the block of Nemotron-H, Jamba and Falcon-H1).

The stack is ``n_layers / len(cfg.layer_pattern)`` periods of the
pattern (granite-4.0-h-micro: ``"MMMMMAMMMM"``, four periods).  Every
layer is pre-norm with its own SwiGLU MLP:

    x = x + r * mixer(norm1(x))        mixer: Mamba-2 ("M") or GQA ("A")
    x = x + r * mlp(norm2(x))

with ``r = cfg.residual_multiplier``; the embeddings are scaled by
``embedding_multiplier`` here and the logits divided by
``logits_scaling`` in ``lm.logits_fn`` (granite's muP).  The Mamba mixer
is ``models/mamba2``'s in_proj -> conv -> SSD -> gated RMSNorm ->
out_proj; attention is ``models/transformer``'s, without RoPE when
``rope_theta`` is 0.

Parameters: ``{"mamba": leaves (n_mamba, ...), "attn": leaves
(n_attn, ...)}`` in layer order; the forward scans over periods and
unrolls one period.  Bit vectors have one entry per layer (both the
mixer and the MLP of layer i run at bits[i]); the SSD scan and the state
stay floating point (DESIGN.md §4).

Cache: ``{"kv": transformer cache (n_attn, B, ...), "ssm": (n_mamba, B,
H, P, N) f32, "conv": (n_mamba, B, K-1, C), "live": (1, B) int32}``.
The state stacks ride the period scan as carries: decode updates layer
l's block in place (``kops.ssm_update``), prefill writes it with a
dynamic-update-slice.  ``live`` marks the rows holding a request
(prefill sets it; ``lm.CachePool`` clears it on reset): the decode
kernel leaves every other row's state untouched.

Right-padded prefill (``valid`` (B, S)): past its length a row adds
nothing to its state (dt = 0 and x = 0, so the decay is 1 and the input
zero), its conv window is taken from its last K-1 real positions
(zero-filled on the left for a prompt shorter than K-1), and its padded
positions stay exactly zero in the residual stream (zero embeddings,
attention outputs masked before the output projection), so per-request
activation scales see the real tokens only.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models import common as cm
from repro.models import mamba2, transformer as tf


def layout(cfg) -> Tuple[str, int, int, int]:
    """(pattern, periods, mixers per period, attention layers per period)."""
    pat = cfg.layer_pattern
    if not pat or set(pat) - {"M", "A"} or cfg.n_layers % len(pat):
        raise ValueError(f"layer_pattern {pat!r} does not tile "
                         f"{cfg.n_layers} layers with 'M'/'A' letters")
    if cfg.ssm_groups != 1:
        raise NotImplementedError("mamba_attn: ssm_groups must be 1")
    return pat, cfg.n_layers // len(pat), pat.count("M"), pat.count("A")


def n_mamba(cfg) -> int:
    _, n, m, _ = layout(cfg)
    return n * m


def n_attn(cfg) -> int:
    _, n, _, a = layout(cfg)
    return n * a


def init(key, cfg) -> dict:
    km, ka = jax.random.split(key)

    def m_layer(k):
        k1, k2 = jax.random.split(k)
        mixer = mamba2.mamba_init(k1, cfg)
        del mixer["ln"]
        if not cfg.conv_bias:
            del mixer["conv_b"]
        return {"ln1": cm.norm_init(cfg.d_model, "rms"), "mixer": mixer,
                "ln2": cm.norm_init(cfg.d_model, "rms"),
                "mlp": tf.mlp_init(k2, cfg)}

    return {"mamba": jax.vmap(m_layer)(jax.random.split(km, n_mamba(cfg))),
            "attn": jax.vmap(lambda k: tf.block_init(k, cfg))(
                jax.random.split(ka, n_attn(cfg)))}


def empty_cache(cfg, batch: int, max_len: int) -> dict:
    st = mamba2.empty_state(cfg, batch, n_mamba(cfg))
    return {"kv": tf.empty_cache(cfg, batch, max_len, n_layers=n_attn(cfg)),
            "ssm": st["ssm"], "conv": st["conv"],
            "live": jnp.zeros((1, batch), jnp.int32)}


def _residual(x, y, cfg):
    return (x.astype(jnp.float32) + y.astype(jnp.float32)
            * cfg.residual_multiplier).astype(x.dtype)


def _conv_tail(xBC, valid, K: int):
    """Each row's conv window after its last real position: the raw
    inputs at its last K-1 real positions, zeros where the row is shorter."""
    B, S, C = xBC.shape
    lens = (jnp.full((B,), S, jnp.int32) if valid is None
            else jnp.sum(valid, axis=1).astype(jnp.int32))
    padded = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    idx = lens[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]
    return jnp.take_along_axis(padded, idx[..., None], axis=1)


def _mamba(p, x, cfg, wb, ab, st, layer, valid, decode):
    """One Mamba-2 sublayer (norm, mixer, scaled residual).  ``st`` is
    None (no state: training) or the stacked {"ssm", "conv", "live"}."""
    mx = p["mixer"]
    z, xBC, dt_raw = mamba2.mixer_in(
        mx, cm.rms_norm(x, p["ln1"]["scale"], cfg.norm_eps), cfg, wb, ab)
    a = -jnp.exp(mx["A_log"])
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + mx["dt_bias"][None, None])          # (B,S,H)
    if decode:
        conv_l = jax.lax.dynamic_index_in_dim(st["conv"], layer, 0, False)
        xBC1, conv_l = mamba2.conv_step(mx, conv_l, xBC)
        # stored in bf16 as the prefill's conv output is
        xh, Bm, Cm = mamba2.split_xbc(xBC1.astype(xBC.dtype), cfg)
        dt0 = dt[:, 0]
        y, ssm = kops.ssm_update(st["ssm"], layer, xh[:, 0], dt0,
                                 jnp.exp(a[None] * dt0), Bm[:, 0], Cm[:, 0],
                                 st["live"][0])
        y = y[:, None]
        st = dict(st, ssm=ssm, conv=jax.lax.dynamic_update_index_in_dim(
            st["conv"], conv_l.astype(st["conv"].dtype), layer, 0))
    else:
        xh, Bm, Cm = mamba2.split_xbc(
            mamba2._causal_conv(mx["conv_w"], mx.get("conv_b"), xBC), cfg)
        if valid is not None:           # nothing enters past a row's end
            dt = jnp.where(valid[..., None], dt, 0.0)
            xh = jnp.where(valid[..., None, None], xh, 0).astype(xh.dtype)
        B = x.shape[0]
        _, H, N, P = mamba2.dims(cfg)
        h0 = (jnp.zeros((B, H, P, N), jnp.float32) if st is None else
              jax.lax.dynamic_index_in_dim(st["ssm"], layer, 0, False))
        y, h_fin = mamba2.ssd_chunked(xh, Bm, Cm, dt, a, h0, cfg.ssm_chunk)
        if st is not None:
            tail = _conv_tail(xBC, valid, cfg.d_conv)
            st = dict(st, ssm=jax.lax.dynamic_update_index_in_dim(
                st["ssm"], h_fin, layer, 0),
                conv=jax.lax.dynamic_update_index_in_dim(
                    st["conv"], tail.astype(st["conv"].dtype), layer, 0))
    x = _residual(x, mamba2.mixer_out(mx, y, xh, z, cfg, wb, ab), cfg)
    return _mlp(p, x, cfg, wb, ab), st


def _mlp(p, x, cfg, wb, ab):
    h = cm.rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)
    return _residual(x, tf.mlp(p["mlp"], h, cfg, wb, ab), cfg)


def _attn(p, x, cfg, wb, ab, positions, kv, t, valid):
    h, kv = tf.attention(p["attn"],
                         cm.rms_norm(x, p["ln1"]["scale"], cfg.norm_eps),
                         cfg, wb, ab, positions=positions, cache=kv, t=t,
                         valid=valid)
    x = _residual(x, h, cfg)
    return _mlp(p, x, cfg, wb, ab), kv


def forward(p, x, cfg, wvec, avec, *, positions, cache: Optional[dict] = None,
            t=None, valid=None) -> Tuple[jnp.ndarray, Optional[dict]]:
    """Embedded tokens x (B, S, d) -> (final hidden, cache).

    wvec/avec: (n_layers,) or layer-major (n_layers, B).  ``cache`` None:
    full sequence, no state (training).  With a cache, a decode step of
    one token a row when ``t`` (its positions) is given, else a prefill
    from the cache's state; ``valid`` (B, S) marks a right-padded
    prefill's real tokens."""
    pat, n_per, nm, na = layout(cfg)
    decode = cache is not None and t is not None
    x = (x.astype(jnp.float32) * cfg.embedding_multiplier).astype(x.dtype)

    def periods(tree, k):
        return jax.tree.map(lambda a: a.reshape(n_per, k, *a.shape[1:]),
                            tree)

    xs = {"i": jnp.arange(n_per, dtype=jnp.int32),
          "mamba": periods(p["mamba"], nm), "attn": periods(p["attn"], na),
          "wb": periods(jnp.asarray(wvec), len(pat)),
          "ab": periods(jnp.asarray(avec), len(pat))}
    st = None
    if cache is not None:
        xs["kv"] = periods(cache["kv"], na)
        st = {"ssm": cache["ssm"], "conv": cache["conv"],
              "live": cache["live"]}

    def period(carry, sc):
        x, st = carry
        kv_out = []
        mi = ai = 0
        for j, kind in enumerate(pat):
            wb, ab = sc["wb"][j], sc["ab"][j]
            if kind == "M":
                lp = jax.tree.map(lambda a, m=mi: a[m], sc["mamba"])
                x, st = _mamba(lp, x, cfg, wb, ab, st, sc["i"] * nm + mi,
                               valid, decode)
                mi += 1
            else:
                lp = jax.tree.map(lambda a, m=ai: a[m], sc["attn"])
                kv = (None if "kv" not in sc else
                      jax.tree.map(lambda a, m=ai: a[m], sc["kv"]))
                x, kv = _attn(lp, x, cfg, wb, ab, positions, kv, t, valid)
                kv_out.append(kv)
                ai += 1
        ys = (jax.tree.map(lambda *a: jnp.stack(a), *kv_out)
              if "kv" in sc else ())
        return (x, st), ys

    body = (jax.checkpoint(period) if cfg.remat == "full" and cache is None
            else period)
    (x, st), kv = jax.lax.scan(body, (x, st), xs)
    if cache is None:
        return x, None
    live = (st["live"] if decode
            else jnp.ones_like(st["live"]))     # a prefilled row is live
    kv = jax.tree.map(lambda a: a.reshape(n_per * na, *a.shape[2:]), kv)
    return x, {"kv": kv, "ssm": st["ssm"], "conv": st["conv"], "live": live}
