"""Dense transformer blocks: GQA attention (RoPE, qk_norm, QKV bias, sliding
window), SwiGLU/GELU MLPs, KV-cache prefill/decode.

Everything is a pure function over parameter pytrees.  All GEMMs go through
``common.apply_linear`` so the per-layer (wbits, abits) runtime scalars give
bit-fluid mixed precision in both train (fake-quant STE) and serve (integer
container) modes.

Cache convention (per layer):
  {"k": (B, Sc, KV, hd), "v": (B, Sc, KV, hd), "kpos": (B, Sc) int32}
``Sc`` is the cache capacity — ``min(max_len, window)`` for sliding-window
models, so a 500k-token starcoder2 decode keeps a 4k ring buffer.  Slot
``t % Sc`` is overwritten at step t; ``kpos`` records, *per batch row*,
the absolute position held by each slot (``EMPTY_POS`` = +2^30 = empty /
padded — never visible, since visibility is ``kpos <= t``) and drives the
visibility mask, which makes full-window, ring-buffer, and per-row
continuous-batching attention the same code path.  ``t`` may be a scalar
(lock-step batch) or a ``(B,)`` vector (per-row decode positions for the
slot pool in serve/engine.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import dist
from repro.kernels import ops as kops
from repro.models import common as cm

EMPTY_POS = 2 ** 30          # "no token here": fails kpos <= t forever


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def attn_init(key, cfg) -> dict:
    ks = jax.random.split(key, 4)
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": cm.dense_init(ks[0], d, H * hd, bias=cfg.qkv_bias),
        "wk": cm.dense_init(ks[1], d, KV * hd, bias=cfg.qkv_bias),
        "wv": cm.dense_init(ks[2], d, KV * hd, bias=cfg.qkv_bias),
        "wo": cm.dense_init(ks[3], H * hd, d,
                            scale=(H * hd) ** -0.5 / max(cfg.n_layers, 1) ** 0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((hd,), cm.DTYPE)}
        p["k_norm"] = {"scale": jnp.ones((hd,), cm.DTYPE)}
    return p


def mlp_init(key, cfg, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_type == "swiglu":
        return {"wg": cm.dense_init(ks[0], d, f),
                "wu": cm.dense_init(ks[1], d, f),
                "wd": cm.dense_init(ks[2], f, d, scale=f ** -0.5)}
    return {"wi": cm.dense_init(ks[0], d, f, bias=cfg.norm_type == "layer"),
            "wd": cm.dense_init(ks[1], f, d, bias=cfg.norm_type == "layer",
                                scale=f ** -0.5)}


def block_init(key, cfg) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "ln1": cm.norm_init(cfg.d_model, cfg.norm_type),
        "attn": attn_init(k1, cfg),
        "ln2": cm.norm_init(cfg.d_model, cfg.norm_type),
        "mlp": mlp_init(k2, cfg),
    }


def empty_cache(cfg, batch: int, max_len: int, n_layers: Optional[int] = None,
                dtype=cm.DTYPE) -> dict:
    """Stacked (n_layers, ...) cache pytree for the decode scan.

    kv_cache_bits == 8 stores int8 keys/values with per-(token, head)
    scales — half the HBM traffic per decoded token, and the QK/PV dots
    run on the int8 MXU path (2x peak)."""
    L = n_layers if n_layers is not None else cfg.n_layers
    Sc = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    kv = (L, batch, Sc, cfg.n_kv_heads, cfg.head_dim)
    out = {"kpos": jnp.full((L, batch, Sc), EMPTY_POS, jnp.int32)}
    if cfg.kv_cache_bits == 8:
        out.update({
            "k": jnp.zeros(kv, jnp.int8),
            "v": jnp.zeros(kv, jnp.int8),
            "ks": jnp.zeros(kv[:-1], cm.DTYPE),
            "vs": jnp.zeros(kv[:-1], cm.DTYPE),
        })
    else:
        out.update({"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)})
    return out


def _quant_heads(x: jnp.ndarray):
    """(B, S, KV, hd) -> int8 values + per-(token, head) scale."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    s = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127
                 ).astype(jnp.int8)
    return q, s[..., 0].astype(cm.DTYPE)


def _row_insert(buf: jnp.ndarray, new: jnp.ndarray, slot: jnp.ndarray
                ) -> jnp.ndarray:
    """Write ``new`` (B, 1, ...) into ``buf`` (B, Sc, ...) at per-row ring
    slot ``slot`` (B,) — the continuous-batching cache insert, where each
    row sits at its own decode position."""
    return jax.vmap(lambda b, n, s: jax.lax.dynamic_update_slice(
        b, n, (s,) + (0,) * (b.ndim - 1)))(buf, new, slot)


def _softmax_scale(cfg, hd: int) -> float:
    """The scores' scale: ``attention_multiplier`` where the config
    states one (granite's muP), else 1/sqrt(head_dim)."""
    return cfg.attention_multiplier or hd ** -0.5


def _sdpa_int8(q, kq, ks, vq, vs, bias, cfg):
    """Decode attention on the int8 cache: scores = (q_q . k_q) sq ks.

    q: (B,1,H,hd) bf16; kq/vq: (B,Sc,KV,hd) int8; ks/vs: (B,Sc,KV)."""
    B, Sq, H, hd = q.shape
    KV = kq.shape[2]
    G = H // KV
    qq, qs = _quant_heads(q)
    qg = qq.reshape(B, Sq, KV, G, hd)
    acc = jnp.einsum("bqkgd,bskd->bkgqs", qg, kq,
                     preferred_element_type=jnp.int32)
    qs_g = qs.reshape(B, Sq, KV, G).transpose(0, 2, 3, 1)[..., None]
    scores = (acc.astype(jnp.float32) * qs_g.astype(jnp.float32)
              * ks.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, None, :])
    scores = scores * _softmax_scale(cfg, hd) + bias[:, None, None]
    probs = jax.nn.softmax(scores, axis=-1)
    # fold v scales into probs, quantize probs to int8 (p in [0,1])
    pv = probs * vs.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, None, :]
    pmax = jnp.max(pv, axis=-1, keepdims=True) + 1e-9
    p_q = jnp.clip(jnp.round(pv / pmax * 127.0), 0, 127).astype(jnp.int8)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p_q, vq,
                     preferred_element_type=jnp.int32)
    out = out.astype(jnp.float32) * (pmax.transpose(0, 3, 1, 2, 4) / 127.0)
    return out.reshape(B, Sq, H * hd).astype(cm.DTYPE)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _qkv(p, x, cfg, wbits, abits):
    B, S = x.shape[:2]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = cm.apply_linear(p["wq"], x, wbits, abits).reshape(B, S, H, hd)
    k = cm.apply_linear(p["wk"], x, wbits, abits).reshape(B, S, KV, hd)
    v = cm.apply_linear(p["wv"], x, wbits, abits).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = cm.rms_norm(q, p["q_norm"]["scale"], cfg.norm_eps)
        k = cm.rms_norm(k, p["k_norm"]["scale"], cfg.norm_eps)
    return q, k, v


def _sdpa(q, k, v, bias, cfg):
    """q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd); bias: (Sq,Sk) or (B,Sq,Sk).

    Grouped-query einsum; used for decode (Sq==1) and short sequences,
    where the scores tensor is small.  Long sequences take _flash (the
    kernel-layer flash dispatcher)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores * _softmax_scale(cfg, hd)
    if bias.ndim == 2:
        scores = scores + bias[None, None, None]
    else:
        scores = scores + bias[:, None, None]
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(k.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H * hd).astype(cm.DTYPE)


FLASH_THRESHOLD = 2048


def _flash(q, k, v, cfg, causal: bool):
    """Long-sequence attention via the kernel-layer flash dispatcher.

    q: (B,Sq,H,hd); k,v: (B,Sk,KV,hd) — KV heads are expanded to H flat
    heads so the `model` axis shards the head dim of every intermediate
    (Megatron semantics), then heads flatten into the batch dim of
    ``ops.flash_attention`` (Pallas kernel on TPU, blockwise online-softmax
    ref elsewhere — the scores tensor never materializes beyond one tile).
    Positions are lock-step 0..S-1 by construction on this path (ragged
    prefill is capped at FLASH_THRESHOLD upstream); the sliding-window
    band applies only to causal self-attention.
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    if cfg.attention_multiplier:              # the kernel scales by hd**-.5
        q = q * (cfg.attention_multiplier * hd ** 0.5)
    if G > 1:                                 # expand GQA to flat heads
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    k = dist.constrain(k, ("dp", None, "tp", None))
    v = dist.constrain(v, ("dp", None, "tp", None))
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd).astype(cm.DTYPE)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd).astype(cm.DTYPE)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, hd).astype(cm.DTYPE)
    window = cfg.sliding_window if causal else 0
    out = kops.flash_attention(qf, kf, vf, causal=causal, window=window)
    out = out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
    return out.reshape(B, Sq, H * hd).astype(cm.DTYPE)


def attention(p, x, cfg, wbits=8, abits=8, *, positions, causal: bool = True,
              kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              cache: Optional[dict] = None, t=None,
              valid: Optional[jnp.ndarray] = None):
    """Self- or cross-attention with optional cache update.

    positions: (B, S) absolute positions of x's tokens (for RoPE + mask).
    kv:        precomputed (k, v) for cross-attention (RoPE skipped).
    cache/t:   decode path — insert this step's k/v at slot t % Sc.
    valid:     (B, S) real tokens of a right-padded batch; padded
               positions give the output projection a zero input, so
               its per-request activation scale sees real tokens only.
    Returns (out, new_cache).
    """
    q, k_new, v_new = _qkv(p, x, cfg, wbits, abits)
    if cfg.rope_theta > 0 and kv is None:
        q = cm.apply_rope(q, positions, cfg.rope_theta)
        k_new = cm.apply_rope(k_new, positions, cfg.rope_theta)

    new_cache = None
    out = None
    if kv is not None:                                   # cross-attention
        k, v = kv
        if q.shape[1] * k.shape[1] > FLASH_THRESHOLD ** 2:
            out = _flash(q, k, v, cfg, causal=False)
        else:
            bias = jnp.zeros((q.shape[1], k.shape[1]), jnp.float32)
            out = _sdpa(q, k, v, bias, cfg)
    elif cache is not None and x.shape[1] == 1:          # decode (S == 1)
        # consistent head/hd sharding across q, k/v inserts, and the cache:
        # the KV head count decides the axis for *all* of q/k/v
        use_head = k_new.shape[2] % dist.api.tp_size() == 0
        q = dist.constrain_heads(q, 2, 3, use_head)
        k_new = dist.constrain_heads(k_new, 2, 3, use_head)
        v_new = dist.constrain_heads(v_new, 2, 3, use_head)
        B = x.shape[0]
        Sc = cache["k"].shape[1]
        t_b = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))
        slot = (t_b % Sc).astype(jnp.int32)
        kpos = jax.vmap(lambda kp, tv, sl: jax.lax.dynamic_update_slice(
            kp, tv[None], (sl,)))(cache["kpos"], t_b, slot)
        visible = kpos <= positions[:, -1:]              # (B, Sc)
        if cfg.sliding_window:
            visible &= kpos > positions[:, -1:] - cfg.sliding_window
        bias = jnp.where(visible, 0.0, -jnp.inf)[:, None, :].astype(jnp.float32)
        bias = bias.reshape(B, 1, Sc)                    # (B, Sq=1, Sc)
        if "ks" in cache:                                # int8 cache path
            kq_n, ks_n = _quant_heads(k_new)
            vq_n, vs_n = _quant_heads(v_new)
            k = _row_insert(cache["k"], kq_n, slot)
            v = _row_insert(cache["v"], vq_n, slot)
            ks = _row_insert(cache["ks"], ks_n, slot)
            vs = _row_insert(cache["vs"], vs_n, slot)
            new_cache = {"k": k, "v": v, "ks": ks, "vs": vs, "kpos": kpos}
            out = _sdpa_int8(q, k, ks, v, vs, bias, cfg)
        else:
            k = _row_insert(cache["k"], k_new, slot)
            v = _row_insert(cache["v"], v_new, slot)
            new_cache = {"k": k, "v": v, "kpos": kpos}
            out = _sdpa(q, k, v, bias, cfg)
    elif cache is not None and t is not None:            # chunked decode
        # Speculative verify: U consecutive token positions per row in ONE
        # forward.  Writes land in the same ring slots sequential decode
        # would use; each query position sees exactly the kpos <= pos
        # prefix, so the chunk is bit-identical to U single-token steps
        # (the draft's stale entries past each query are masked, and
        # rejected slots are rolled back to EMPTY_POS by the caller).
        use_head = k_new.shape[2] % dist.api.tp_size() == 0
        q = dist.constrain_heads(q, 2, 3, use_head)
        k_new = dist.constrain_heads(k_new, 2, 3, use_head)
        v_new = dist.constrain_heads(v_new, 2, 3, use_head)
        B, U = x.shape[:2]
        Sc = cache["k"].shape[1]
        pos = positions.astype(jnp.int32)                # (B, U)
        slots = (pos % Sc).astype(jnp.int32)             # (B, U)
        scatter = jax.vmap(lambda b, n, s: b.at[s].set(n))
        kpos = scatter(cache["kpos"], pos, slots)
        visible = kpos[:, None, :] <= pos[:, :, None]    # (B, U, Sc)
        if cfg.sliding_window:
            visible &= kpos[:, None, :] > pos[:, :, None] - cfg.sliding_window
        bias = jnp.where(visible, 0.0, -jnp.inf).astype(jnp.float32)
        if "ks" in cache:                                # int8 cache path
            kq_n, ks_n = _quant_heads(k_new)
            vq_n, vs_n = _quant_heads(v_new)
            k = scatter(cache["k"], kq_n, slots)
            v = scatter(cache["v"], vq_n, slots)
            ks = scatter(cache["ks"], ks_n, slots)
            vs = scatter(cache["vs"], vs_n, slots)
            new_cache = {"k": k, "v": v, "ks": ks, "vs": vs, "kpos": kpos}
            out = _sdpa_int8(q, k, ks, v, vs, bias, cfg)
        else:
            k = scatter(cache["k"], k_new, slots)
            v = scatter(cache["v"], v_new, slots)
            new_cache = {"k": k, "v": v, "kpos": kpos}
            out = _sdpa(q, k, v, bias, cfg)
    else:                                                # full sequence
        pos1 = positions[0]
        k, v = k_new, v_new
        if x.shape[1] > FLASH_THRESHOLD:
            out = _flash(q, k, v, cfg, causal=causal)
        elif causal and cache is not None and positions.shape[0] > 1:
            # ragged serving prefill: rows carry different valid lengths
            # (padded positions == EMPTY_POS), so the mask is per-row;
            # lock-step prefill passes (1, S) positions and keeps the
            # shared (S, S) mask below
            bias = cm.causal_mask_bias_batched(positions, positions,
                                               cfg.sliding_window)
            out = _sdpa(q, k, v, bias, cfg)
        else:
            bias = (cm.causal_mask_bias(pos1, pos1, cfg.sliding_window)
                    if causal
                    else jnp.zeros((x.shape[1], x.shape[1]), jnp.float32))
            out = _sdpa(q, k, v, bias, cfg)
        if cache is not None:                            # prefill: fill cache
            new_cache = prefill_cache_insert(cache, k_new, v_new, positions)

    if valid is not None:
        out = jnp.where(valid[..., None], out, 0).astype(out.dtype)
    y = cm.apply_linear(p["wo"], out, wbits, abits)
    return y, new_cache


def prefill_cache_insert(cache_layer: dict, k: jnp.ndarray, v: jnp.ndarray,
                         positions: jnp.ndarray) -> dict:
    """Write a full prefill's k/v (B,S,KV,hd) into a fresh layer cache.

    ``positions`` (B, S) or (1, S) may differ per row: padded tokens at
    EMPTY_POS land in the cache as EMPTY_POS slots, which the decode
    visibility mask (kpos <= t) never exposes — padding is masked, not
    special-cased.  When the prompt buffer exceeds the ring capacity,
    each row keeps its own last ``Sc`` *valid* tokens (a per-row gather —
    a uniform tail slice would keep only padding for short rows)."""
    Sc = cache_layer["k"].shape[1]
    B, S = k.shape[0], k.shape[1]
    keep = min(S, Sc)
    positions = jnp.broadcast_to(positions, (B, S)).astype(jnp.int32)
    if keep == S:                                        # whole buffer fits
        kpos_new, k_keep, v_keep = positions, k, v
    else:
        n_valid = jnp.sum(positions < EMPTY_POS, axis=1)        # (B,)
        shift = jnp.maximum(n_valid - keep, 0)                  # (B,)
        idx = jnp.minimum(shift[:, None] + jnp.arange(keep)[None], S - 1)
        kpos_new = jnp.take_along_axis(positions, idx, axis=1)
        k_keep = jnp.take_along_axis(k, idx[..., None, None], axis=1)
        v_keep = jnp.take_along_axis(v, idx[..., None, None], axis=1)
    kpos = jax.lax.dynamic_update_slice(cache_layer["kpos"], kpos_new,
                                        (0, 0))
    if "ks" in cache_layer:                              # int8 cache
        kq, ks = _quant_heads(k_keep)
        vq, vs = _quant_heads(v_keep)
        return {
            "k": jax.lax.dynamic_update_slice(cache_layer["k"], kq,
                                              (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache_layer["v"], vq,
                                              (0, 0, 0, 0)),
            "ks": jax.lax.dynamic_update_slice(cache_layer["ks"], ks,
                                               (0, 0, 0)),
            "vs": jax.lax.dynamic_update_slice(cache_layer["vs"], vs,
                                               (0, 0, 0)),
            "kpos": kpos,
        }
    ck = jax.lax.dynamic_update_slice(
        cache_layer["k"], k_keep, (0, 0, 0, 0))
    cv = jax.lax.dynamic_update_slice(
        cache_layer["v"], v_keep, (0, 0, 0, 0))
    return {"k": ck, "v": cv, "kpos": kpos}


# ---------------------------------------------------------------------------
# MLP + block
# ---------------------------------------------------------------------------

def mlp(p, x, cfg, wbits=8, abits=8):
    if cfg.mlp_type == "swiglu":
        g = cm.apply_linear(p["wg"], x, wbits, abits)
        u = cm.apply_linear(p["wu"], x, wbits, abits)
        h = jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
        return cm.apply_linear(p["wd"], h.astype(cm.DTYPE), wbits, abits)
    h = cm.apply_linear(p["wi"], x, wbits, abits)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(cm.DTYPE)
    return cm.apply_linear(p["wd"], h, wbits, abits)


def block(p, x, cfg, wbits=8, abits=8, *, positions, causal=True,
          cache=None, t=None, mlp_fn=None):
    """Pre-norm residual block.  Returns (x, new_cache, aux)."""
    h, new_cache = attention(p["attn"], cm.apply_norm(p["ln1"], x, cfg.norm_type,
                                                      cfg.norm_eps),
                             cfg, wbits, abits, positions=positions,
                             causal=causal, cache=cache, t=t)
    x = x + h
    fn = mlp_fn if mlp_fn is not None else mlp
    out = fn(p["mlp"], cm.apply_norm(p["ln2"], x, cfg.norm_type, cfg.norm_eps),
             cfg, wbits, abits)
    if isinstance(out, tuple):                    # MoE returns (y, aux)
        y, aux = out
    else:
        y, aux = out, jnp.zeros((), jnp.float32)
    return x + y, new_cache, aux
