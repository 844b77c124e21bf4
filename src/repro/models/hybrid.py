"""Zamba2-style hybrid: Mamba2 backbone + one *shared* attention block.

Structure (arXiv:2411.15242, adapted): the layer stack is grouped into
``n_layers / attn_every`` super-blocks; each super-block first runs the
globally-shared attention+MLP block (one weight set reused at every site,
specialized per site by a LoRA adapter pair), then ``attn_every`` Mamba2
layers.  The outer scan carries hidden state; Mamba params are stacked
(n_super, attn_every, ...), LoRA params (n_super, ...).

Because the shared block's base weights are one tensor reused everywhere,
its precision assignment is global — the per-layer bit vectors index
super-blocks for the LoRA/Mamba params, while the shared base uses
``wbits[0]`` (constraint recorded in DESIGN.md §4).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models import mamba2, transformer


def n_super(cfg) -> int:
    assert cfg.n_layers % cfg.attn_every == 0
    return cfg.n_layers // cfg.attn_every


def lora_init(key, cfg) -> dict:
    """Per-site LoRA on the shared block's four attention projections."""
    d, H, hd, r = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.lora_rank
    ks = jax.random.split(key, 4)

    def pair(k, d_in, d_out):
        a = (jax.random.normal(k, (d_in, r), jnp.float32) * d_in ** -0.5
             ).astype(cm.DTYPE)
        b = jnp.zeros((r, d_out), cm.DTYPE)
        return {"a": a, "b": b}

    return {"wq": pair(ks[0], d, H * hd), "wk": pair(ks[1], d, cfg.n_kv_heads * hd),
            "wv": pair(ks[2], d, cfg.n_kv_heads * hd), "wo": pair(ks[3], H * hd, d)}


def hybrid_init(key, cfg) -> dict:
    ns = n_super(cfg)
    k_shared, k_mamba, k_lora = jax.random.split(key, 3)
    shared = transformer.block_init(k_shared, cfg)
    mamba_keys = jax.random.split(k_mamba, ns * cfg.attn_every)
    mamba_keys = mamba_keys.reshape(ns, cfg.attn_every, *mamba_keys.shape[1:])
    stack = jax.vmap(jax.vmap(lambda k: mamba2.mamba_init(k, cfg)))(mamba_keys)
    lora = jax.vmap(lambda k: lora_init(k, cfg))(jax.random.split(k_lora, ns))
    return {"shared": shared, "mamba": stack, "lora": lora}


def _lora_attn_params(shared_attn: dict, lora: dict) -> dict:
    """Materialize site-specific attention weights: W + A @ B (train form),
    or attach the LoRA delta additively around the quantized base."""
    out = dict(shared_attn)
    for name in ("wq", "wk", "wv", "wo"):
        base = shared_attn[name]
        delta = (lora[name]["a"].astype(jnp.float32)
                 @ lora[name]["b"].astype(jnp.float32))
        if "w" in base:
            out[name] = dict(base, w=(base["w"].astype(jnp.float32) + delta
                                      ).astype(base["w"].dtype))
        else:   # serve form: keep int base, add fp delta via side branch
            out[name] = dict(base, lora_delta=delta.astype(cm.DTYPE))
    return out


def hybrid_forward(p, x, cfg, wbits, abits, *, positions,
                   cache: Optional[dict] = None, t=None
                   ) -> Tuple[jnp.ndarray, Optional[dict]]:
    """x: (B, S, d).  wbits/abits: (n_super,) vectors (or scalars).
    cache: {"kv": transformer cache stacked (n_super,...),
            "ssm"/"conv": mamba states stacked (n_super*attn_every,...)}."""
    ns = n_super(cfg)
    wb = jnp.broadcast_to(jnp.asarray(wbits), (ns,))
    ab = jnp.broadcast_to(jnp.asarray(abits), (ns,))
    shared = p["shared"]
    decode = cache is not None

    def super_block(x, kv, i, scanned):
        if decode:
            (mp, lora, wb_i, ab_i, m_c) = scanned
        else:
            (mp, lora, wb_i, ab_i) = scanned
            m_c = None
        attn_p = {"ln1": shared["ln1"], "ln2": shared["ln2"],
                  "mlp": shared["mlp"],
                  "attn": _lora_attn_params(shared["attn"], lora)}
        x, kv, _ = transformer.block(
            attn_p, x, cfg, wb[0], ab[0], positions=positions,
            cache=kv, layer=i, t=t)

        def mamba_layer(xc, inner):
            if decode:
                mp_i, conv_i, ssm_i = inner
                st = {"conv": conv_i, "ssm": ssm_i}
            else:
                (mp_i,) = inner
                st = None
            xc, new_st = mamba2.mamba_block(mp_i, xc, cfg, wb_i, ab_i, state=st)
            ys = (new_st["conv"], new_st["ssm"]) if decode else ()
            return xc, ys

        inner_xs = (mp, m_c["conv"], m_c["ssm"]) if decode else (mp,)
        x, m_out = jax.lax.scan(mamba_layer, x, inner_xs)
        ys = {"conv": m_out[0], "ssm": m_out[1]} if decode else ()
        return x, kv, ys

    if decode:
        ssm = jax.tree.map(
            lambda a: a.reshape(ns, cfg.attn_every, *a.shape[1:]),
            {"conv": cache["conv"], "ssm": cache["ssm"]})
        xs = (p["mamba"], p["lora"], wb, ab, ssm)
    else:
        xs = (p["mamba"], p["lora"], wb, ab)
    body = jax.checkpoint(super_block) if cfg.remat == "full" else super_block
    x, kv_new, m_new = transformer.cached_layer_scan(
        body, x, xs, cache["kv"] if decode else None)
    new_cache = None
    if decode:
        new_cache = {
            "kv": kv_new,
            "conv": m_new["conv"].reshape(cfg.n_layers, *m_new["conv"].shape[2:]),
            "ssm": m_new["ssm"].reshape(cfg.n_layers, *m_new["ssm"].shape[2:]),
        }
    return x, new_cache


def empty_hybrid_cache(cfg, batch: int, max_len: int) -> dict:
    ns = n_super(cfg)
    kv = transformer.empty_cache(cfg, batch, max_len, n_layers=ns)
    ms = mamba2.empty_state(cfg, batch, cfg.n_layers)
    return {"kv": kv, "conv": ms["conv"], "ssm": ms["ssm"]}
