"""Encoder-decoder (seamless-m4t backbone): bidirectional encoder over
precomputed audio-frame embeddings (modality frontend is a stub per the
brief), causal decoder with per-layer cross-attention.

Decode caches: self-attention KV ring caches (transformer.empty_cache) plus
per-layer cross K/V projected once from the encoder output at prefill.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models import transformer as tf


def dec_block_init(key, cfg) -> dict:
    k1, k2 = jax.random.split(key)
    p = tf.block_init(k1, cfg)
    p["lnx"] = cm.norm_init(cfg.d_model, cfg.norm_type)
    p["xattn"] = tf.attn_init(k2, cfg)
    return p


def encdec_init(key, cfg) -> dict:
    ke, kd = jax.random.split(key)
    enc = jax.vmap(lambda k: tf.block_init(k, cfg))(
        jax.random.split(ke, cfg.n_enc_layers))
    dec = jax.vmap(lambda k: dec_block_init(k, cfg))(
        jax.random.split(kd, cfg.n_layers))
    return {"enc": enc, "enc_ln_f": cm.norm_init(cfg.d_model, cfg.norm_type),
            "dec": dec}


def encode(p, frames: jnp.ndarray, cfg, wvec, avec) -> jnp.ndarray:
    """frames: (B, F, d) stub embeddings -> encoder output (B, F, d)."""
    B, F, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(F)[None], (B, F))

    def body(x, scanned):
        lp, wb, ab = scanned
        x, _, _ = tf.block(lp, x, cfg, wb, ab, positions=positions,
                           causal=False)
        return x, ()

    body = jax.checkpoint(body) if cfg.remat == "full" else body
    n_enc = cfg.n_enc_layers
    x, _ = jax.lax.scan(body, frames, (p["enc"], wvec[:n_enc], avec[:n_enc]))
    return cm.apply_norm(p["enc_ln_f"], x, cfg.norm_type, cfg.norm_eps)


def cross_kv(p_dec, enc_out: jnp.ndarray, cfg, wvec, avec) -> dict:
    """Project encoder output to per-decoder-layer cross K/V (prefill)."""
    B, F, _ = enc_out.shape
    KV, hd = cfg.n_kv_heads, cfg.head_dim

    def one(xp, wb, ab):
        k = cm.apply_linear(xp["wk"], enc_out, wb, ab).reshape(B, F, KV, hd)
        v = cm.apply_linear(xp["wv"], enc_out, wb, ab).reshape(B, F, KV, hd)
        return k, v

    ks, vs = jax.lax.map(lambda args: one(*args),
                         (p_dec["xattn"], wvec, avec))
    return {"k": ks, "v": vs}                    # (L, B, F, KV, hd)


def decoder_block(p, x, cfg, wb, ab, *, positions, enc_kv,
                  cache: Optional[dict] = None, layer=None, t=None):
    """Self-attn + cross-attn + MLP.  enc_kv: (k, v) for this layer;
    ``cache`` the stacked self-attention cache, ``layer`` its index."""
    h, new_cache = tf.attention(
        p["attn"], cm.apply_norm(p["ln1"], x, cfg.norm_type, cfg.norm_eps),
        cfg, wb, ab, positions=positions, causal=True, cache=cache,
        layer=layer, t=t)
    x = x + h
    hx, _ = tf.attention(
        p["xattn"], cm.apply_norm(p["lnx"], x, cfg.norm_type, cfg.norm_eps),
        cfg, wb, ab, positions=positions, kv=enc_kv)
    x = x + hx
    y = tf.mlp(p["mlp"], cm.apply_norm(p["ln2"], x, cfg.norm_type, cfg.norm_eps),
               cfg, wb, ab)
    return x + y, new_cache


def decoder_forward(p, x, cfg, wvec, avec, *, positions,
                    enc_kv: dict, cache: Optional[dict] = None, t=None
                    ) -> Tuple[jnp.ndarray, Optional[dict]]:
    """x: (B, S, d) decoder-side embeddings; enc_kv stacked (L, ...)."""
    def body(x, cache, l, scanned):
        lp, wb, ab, ek, ev = scanned
        x, cache = decoder_block(lp, x, cfg, wb, ab, positions=positions,
                                 enc_kv=(ek, ev), cache=cache, layer=l, t=t)
        return x, cache, ()

    n_dec = cfg.n_layers
    wd, ad = wvec[-n_dec:], avec[-n_dec:]
    xs = (p["dec"], wd, ad, enc_kv["k"], enc_kv["v"])
    if cfg.remat == "full" and cache is None:
        body = jax.checkpoint(body)
    x, cache, _ = tf.cached_layer_scan(body, x, xs, cache)
    return x, cache
