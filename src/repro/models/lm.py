"""LM wiring: embeddings, per-family stacks, loss, prefill/decode, and the
train/serve parameter forms.

Public API (all pure functions, plus the stateful CachePool):
  init_params(cfg, key)                      -> train-form pytree (bf16)
  quantize_params(params, cfg, container)    -> serve-form (int8/int4 + scales)
  init_serve_params(cfg, key, container)     -> serve-form straight from a seed
  train_loss(params, batch, cfg, wvec, avec) -> (loss, metrics)
  prefill(params, batch, cfg, wvec, avec, cache, lengths=None)
                                             -> (last_logits, cache)
  decode_step(params, tok, t, cache, cfg, wvec, avec) -> (logits, cache)
  empty_cache(cfg, batch, max_len)           -> family-specific cache pytree
  CachePool(cfg, n_slots, max_len)           -> slot-based persistent cache
                                                (alloc / free / reset_slot)

``wvec``/``avec`` are per-layer bit vectors (runtime tensors — core/policy):
``(n_layers,)`` shared across the batch, or ``(B, n_layers)`` matrices for
per-request precision (families in PER_ROW_BIT_FAMILIES only); per-family
semantics documented in DESIGN.md §4, serving semantics in §6.
``t`` in decode_step is a scalar (lock-step batch) or ``(B,)`` vector
(per-row positions — continuous batching).  ``lengths`` in prefill marks
per-row valid prompt lengths; padded positions are masked via EMPTY_POS.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import dist
from repro.core import bitfluid as bf
from repro.kernels import ops as kops
from repro.models import common as cm
from repro.models import (encdec, hybrid, mamba2, mamba_attn, moe,
                          transformer as tf)
from repro.models.config import ModelConfig

MOE_AUX_COEF = 0.01

# Families whose layer stacks accept (B, n_layers) per-request bit
# matrices.  MoE resolves a *per-expert* axis instead (DESIGN.md §4);
# hybrid shares one attention block batch-wide; encdec shares the encoder.
PER_ROW_BIT_FAMILIES = ("dense", "vlm", "ssm", "mamba_attn")
# Families whose prefill supports ragged per-row prompt lengths (attention
# masks padding out; mamba_attn masks its state updates past each row's
# length; the plain ssm/hybrid recurrences would consume the pad tokens).
RAGGED_PREFILL_FAMILIES = ("dense", "vlm", "mamba_attn")
# Families whose cache holds a recurrent state per row: it cannot be cut
# back to a prefix, so prefix-cache installs and rollbacks are refused.
RECURRENT_STATE_FAMILIES = ("mamba_attn",)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def n_bit_slots(cfg: ModelConfig) -> int:
    """Length of the per-layer bit vectors for this family."""
    if cfg.family == "encdec":
        return cfg.n_enc_layers + cfg.n_layers
    if cfg.family == "hybrid":
        return hybrid.n_super(cfg)
    return cfg.n_layers


def layer_gemm_dims(cfg: ModelConfig):
    """Per-bit-slot serve GEMV dims: one tuple of (K, N) pairs per slot.

    Each pair is a serve-form linear a single token flows through at that
    slot's precision; ``apsim.metrics.price_bit_vector`` turns these plus
    a resolved (wbits, abits) vector into AP cycles/energy — the serve
    engine's per-request EDP accounting (paper Table 7, live).  Hybrid /
    enc-dec entries are first-order: the shared attention block and the
    cross-attention projections are charged at their slot's bits.
    """
    d = cfg.d_model
    attn = ((d, cfg.n_heads * cfg.head_dim),
            (d, cfg.n_kv_heads * cfg.head_dim),
            (d, cfg.n_kv_heads * cfg.head_dim),
            (cfg.n_heads * cfg.head_dim, d))

    def mlp(f):
        if cfg.mlp_type == "swiglu":
            return ((d, f), (d, f), (f, d))
        return ((d, f), (f, d))

    if cfg.family in ("dense", "vlm"):
        return (attn + mlp(cfg.d_ff),) * cfg.n_layers
    if cfg.family == "moe":
        per = attn + cfg.experts_per_token * mlp(cfg.d_ff)
        if cfg.n_shared_experts:
            per = per + mlp(cfg.d_ff * cfg.n_shared_experts)
        return (per,) * cfg.n_layers
    d_inner, H, N, _ = mamba2.dims(cfg)
    mam = ((d, 2 * d_inner + 2 * N + H), (d_inner, d))    # in/out proj
    if cfg.family == "ssm":
        return (mam,) * cfg.n_layers
    if cfg.family == "mamba_attn":
        per = {"M": mam + mlp(cfg.d_ff), "A": attn + mlp(cfg.d_ff)}
        return tuple(per[k] for k in cfg.layer_pattern) * (
            cfg.n_layers // len(cfg.layer_pattern))
    if cfg.family == "hybrid":
        per = attn + mlp(cfg.d_ff) + mam * cfg.attn_every
        return (per,) * hybrid.n_super(cfg)
    if cfg.family == "encdec":
        enc = attn + mlp(cfg.d_ff)
        dec = attn + attn + mlp(cfg.d_ff)                 # self + cross
        return (enc,) * cfg.n_enc_layers + (dec,) * cfg.n_layers
    raise ValueError(cfg.family)


def head_gemm_dims(cfg: ModelConfig):
    """(K, N) of the per-token logits GEMM (priced at the last slot's
    bits, mirroring logits_fn's _last_layer_bits rule)."""
    return (cfg.d_model, cfg.padded_vocab)


def init_params(cfg: ModelConfig, key) -> dict:
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    p = {"emb": (jax.random.normal(k_emb, (cfg.padded_vocab, cfg.d_model),
                                   jnp.float32) * 0.02).astype(cm.DTYPE),
         "ln_f": cm.norm_init(cfg.d_model, cfg.norm_type)}
    if cfg.family in ("dense", "vlm"):
        p["layers"] = jax.vmap(lambda k: tf.block_init(k, cfg))(
            jax.random.split(k_layers, cfg.n_layers))
    elif cfg.family == "moe":
        def one(k):
            k1, k2 = jax.random.split(k)
            blk = tf.block_init(k1, cfg)
            del blk["mlp"]
            blk["mlp"] = moe.moe_init(k2, cfg)
            return blk
        p["layers"] = jax.vmap(one)(jax.random.split(k_layers, cfg.n_layers))
    elif cfg.family == "ssm":
        p["layers"] = jax.vmap(lambda k: mamba2.mamba_init(k, cfg))(
            jax.random.split(k_layers, cfg.n_layers))
    elif cfg.family == "hybrid":
        p["layers"] = hybrid.hybrid_init(k_layers, cfg)
    elif cfg.family == "mamba_attn":
        p["layers"] = mamba_attn.init(k_layers, cfg)
    elif cfg.family == "encdec":
        p["layers"] = encdec.encdec_init(k_layers, cfg)
    else:
        raise ValueError(cfg.family)
    if not cfg.tie_embeddings:
        p["head"] = cm.dense_init(k_head, cfg.d_model, cfg.padded_vocab,
                                  scale=cfg.d_model ** -0.5)
    return p


# ---------------------------------------------------------------------------
# Serve-form quantization (rule-based traversal)
# ---------------------------------------------------------------------------

_EXPERT_KEYS = ("wg", "wu", "wd")
_FP_SUBTREES = ("router", "lora")        # precision-sensitive: keep bf16
_SKIP_ARRAYS = ("emb",)                  # gather tables stay bf16


@jax.jit
def _quantize_weight(w: jnp.ndarray, bits):
    """Per-out-channel symmetric quantization of one (..., K, N) weight
    onto the ``bits`` grid (int8 container).

    Compiled per leaf: the f32 cast fuses into the scale reduction and
    the rounding, so no f32 copy of a large stacked leaf is ever held
    (qwen3-4b's (36, 2560, 9728) MLP stack would be ~3.6 GB in f32).
    ``bits`` is traced, so the scale stays a true division by qmax and
    the result matches the eager computation bit for bit."""
    w = w.astype(jnp.float32)
    s = bf.symmetric_scale(w, bits, axis=-2)
    return bf.quantize(w, s, bits), s


def quantize_params(params: dict, cfg: ModelConfig,
                    container: str = "int8") -> dict:
    """Train-form -> serve-form.  Every linear {"w": (..., K, N)} becomes
    {"q"/"q4", "s"} (per-out-channel scales, stacked dims preserved);
    MoE expert stacks (E, d, f) quantize per expert."""

    def q_linear(p: dict) -> dict:
        if container == "int4":
            q, s = _quantize_weight(p["w"], 4)
            out = {"q4": bf.pack_int4_halves(q), "s": s}
        else:
            q, s = _quantize_weight(p["w"], 8)
            out = {"q": q, "s": s}
        if "b" in p:
            out["b"] = p["b"]
        return out

    def q_expert(w: jnp.ndarray) -> dict:
        q, s = _quantize_weight(w, 8)
        return {"q": q, "s": s}

    def rec(node, path):
        if isinstance(node, dict):
            if "w" in node and path[-1] not in _FP_SUBTREES:
                return q_linear(node)
            out = {}
            for k, v in node.items():
                if k in _FP_SUBTREES:
                    out[k] = v
                elif (k in _EXPERT_KEYS and not isinstance(v, dict)
                        and getattr(v, "ndim", 0) == 3):
                    out[k] = q_expert(v)
                else:
                    out[k] = rec(v, path + (k,))
            return out
        return node

    return rec(params, ("",))


def init_serve_params(cfg: ModelConfig, key, container: str = "int8"
                      ) -> dict:
    """Serve-form params straight from a seed.

    Initialization and quantization compile into ONE program, so the bf16
    train-form tree only ever exists leaf by leaf as a temporary (qwen3-4b:
    ~4.1 GB of int8 output plus ~3.3 GB of temporaries, against ~8 GB for
    the bf16 tree alone)."""
    return jax.jit(lambda k: quantize_params(init_params(cfg, k), cfg,
                                             container))(key)


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------

def _dense_stack(layers, x, cfg, wvec, avec, positions, cache=None, t=None,
                 mlp_fn=None):
    def body(carry, scanned):
        x = carry
        if cache is not None:
            lp, wb, ab, cl = scanned
        else:
            lp, wb, ab = scanned
            cl = None
        x, new_cl, aux = tf.block(lp, x, cfg, wb, ab, positions=positions,
                                  cache=cl, t=t, mlp_fn=mlp_fn)
        x = dist.constrain(x, ("dp", None, None))
        return x, ((new_cl, aux) if cache is not None else aux)

    if cfg.remat == "full" and cache is None:
        body = jax.checkpoint(body)
    xs = (layers, wvec, avec) + ((cache,) if cache is not None else ())
    x, ys = jax.lax.scan(body, x, xs)
    if cache is not None:
        new_cache, aux = ys
        return x, new_cache, jnp.mean(aux)
    return x, None, jnp.mean(ys)


def _ssm_stack(layers, x, cfg, wvec, avec, cache=None):
    def body(carry, scanned):
        x = carry
        if cache is not None:
            lp, wb, ab, conv, ssm = scanned
            st = {"conv": conv, "ssm": ssm}
        else:
            lp, wb, ab = scanned
            st = None
        x, new_st = mamba2.mamba_block(lp, x, cfg, wb, ab, state=st)
        x = dist.constrain(x, ("dp", None, None))
        return x, ((new_st["conv"], new_st["ssm"]) if cache is not None else ())

    if cfg.remat == "full" and cache is None:
        body = jax.checkpoint(body)
    xs = (layers, wvec, avec)
    if cache is not None:
        xs = xs + (cache["conv"], cache["ssm"])
    x, ys = jax.lax.scan(body, x, xs)
    if cache is not None:
        return x, {"conv": ys[0], "ssm": ys[1]}, jnp.zeros((), jnp.float32)
    return x, None, jnp.zeros((), jnp.float32)


def _layer_major(vec, family: str):
    """Normalize a bit table for the layer scan: (L,) stays; a per-request
    (B, L) matrix transposes to (L, B) so each scanned layer sees a (B,)
    per-row bit vector (the apply_linear vmap path)."""
    v = jnp.asarray(vec)
    if v.ndim == 2:
        if family not in PER_ROW_BIT_FAMILIES:
            raise NotImplementedError(
                f"per-request (B, n_layers) bit matrices are not supported "
                f"for family {family!r} (see DESIGN.md §4)")
        return v.T
    return v


def forward_hidden(params, x, cfg: ModelConfig, wvec, avec, *, positions,
                   cache=None, t=None, enc_out=None, valid=None):
    """Embedded inputs -> final hidden states.  Returns (h, cache, aux).
    ``valid`` (B, S): real tokens of a right-padded prefill (mamba_attn
    masks its state updates with it)."""
    fam = cfg.family
    wvec = _layer_major(wvec, fam)
    avec = _layer_major(avec, fam)
    if fam in ("dense", "vlm"):
        return _dense_stack(params["layers"], x, cfg, wvec, avec, positions,
                            cache, t)
    if fam == "moe":
        return _dense_stack(params["layers"], x, cfg, wvec, avec, positions,
                            cache, t, mlp_fn=moe.apply_moe)
    if fam == "ssm":
        return _ssm_stack(params["layers"], x, cfg, wvec, avec, cache)
    if fam == "mamba_attn":
        h, new_cache = mamba_attn.forward(
            params["layers"], x, cfg, wvec, avec, positions=positions,
            cache=cache, t=t, valid=valid)
        return h, new_cache, jnp.zeros((), jnp.float32)
    if fam == "hybrid":
        h, new_cache = hybrid.hybrid_forward(
            params["layers"], x, cfg, wvec, avec, positions=positions,
            cache=cache, t=t)
        return h, new_cache, jnp.zeros((), jnp.float32)
    if fam == "encdec":
        kv_cache = cache["self"] if cache is not None else None
        if cache is not None and "cross" in cache:
            xkv = cache["cross"]
        else:
            xkv = encdec.cross_kv(params["layers"]["dec"], enc_out, cfg,
                                  wvec[-cfg.n_layers:], avec[-cfg.n_layers:])
        h, new_self = encdec.decoder_forward(
            params["layers"], x, cfg, wvec, avec, positions=positions,
            enc_kv=xkv, cache=kv_cache, t=t)
        new_cache = ({"self": new_self, "cross": xkv}
                     if cache is not None else None)
        return h, new_cache, jnp.zeros((), jnp.float32)
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# Embedding / logits / loss
# ---------------------------------------------------------------------------

def embed(params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(params["emb"], tokens, axis=0)


def logits_fn(params, h: jnp.ndarray, cfg: ModelConfig, wb=8, ab=8):
    h = cm.apply_norm(params["ln_f"], h, cfg.norm_type, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", h.astype(jnp.float32),
                            params["emb"].astype(jnp.float32))
    else:
        logits = cm.apply_linear(params["head"], h, wb, ab
                                 ).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.padded_vocab != cfg.vocab_size:       # mask padding ids
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    return logits


def _xent(logits: jnp.ndarray, targets: jnp.ndarray, mask: jnp.ndarray):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    zloss = jnp.sum((logz * mask) ** 2) / denom
    return jnp.sum(nll) / denom, zloss


def train_loss(params, batch: dict, cfg: ModelConfig, wvec, avec
               ) -> Tuple[jnp.ndarray, dict]:
    tokens = batch["tokens"]
    B, S = tokens.shape
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    mask = jnp.asarray(batch.get("loss_mask", jnp.ones_like(tgt)),
                       jnp.float32)

    x = embed(params, inp)
    enc_out = None
    if cfg.family == "vlm":
        prefix = batch["prefix"].astype(cm.DTYPE)       # (B, P, d) stub
        x = jnp.concatenate([prefix, x], axis=1)
        mask = jnp.concatenate(
            [jnp.zeros((B, prefix.shape[1]), jnp.float32), mask], axis=1)
        tgt = jnp.concatenate(
            [jnp.zeros((B, prefix.shape[1]), tgt.dtype), tgt], axis=1)
    elif cfg.family == "encdec":
        enc_out = encdec.encode(params["layers"], batch["frames"].astype(cm.DTYPE),
                                cfg, wvec, avec)
    x = dist.constrain(x, ("dp", None, None))
    positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                                 (B, x.shape[1]))
    h, _, aux = forward_hidden(params, x, cfg, wvec, avec,
                               positions=positions, enc_out=enc_out)
    logits = logits_fn(params, h, cfg, _last_layer_bits(wvec),
                       _last_layer_bits(avec))
    logits = dist.constrain(logits, ("dp", None, "tp"))
    loss, zloss = _xent(logits, tgt, mask)
    total = loss + 1e-4 * zloss + MOE_AUX_COEF * aux
    return total, {"loss": loss, "zloss": zloss, "moe_aux": aux}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def empty_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    if cfg.family in ("dense", "vlm", "moe"):
        return tf.empty_cache(cfg, batch, max_len)
    if cfg.family == "ssm":
        return mamba2.empty_state(cfg, batch, cfg.n_layers)
    if cfg.family == "hybrid":
        return hybrid.empty_hybrid_cache(cfg, batch, max_len)
    if cfg.family == "mamba_attn":
        return mamba_attn.empty_cache(cfg, batch, max_len)
    if cfg.family == "encdec":
        frames = max(max_len // cfg.frames_ratio, 1)
        return {
            "self": tf.empty_cache(cfg, batch, max_len),
            "cross": {
                "k": jnp.zeros((cfg.n_layers, batch, frames, cfg.n_kv_heads,
                                cfg.head_dim), cm.DTYPE),
                "v": jnp.zeros((cfg.n_layers, batch, frames, cfg.n_kv_heads,
                                cfg.head_dim), cm.DTYPE),
            },
        }
    raise ValueError(cfg.family)


def _last_layer_bits(vec):
    """Bits for the head GEMM: scalar for (L,) tables, (B,) for (B, L)."""
    return jnp.asarray(vec)[..., -1]


def prefill(params, batch: dict, cfg: ModelConfig, wvec, avec, cache: dict,
            lengths=None) -> Tuple[jnp.ndarray, dict]:
    """Full-context forward filling ``cache``; returns last-token logits.

    ``lengths`` (B,) marks per-row valid prompt lengths for right-padded
    batches (continuous batching): padded positions take EMPTY_POS (never
    visible to real queries, never visible in the cache), and the returned
    logits are gathered at each row's own last real token."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = embed(params, tokens)
    enc_out = None
    prefix_len = 0
    if cfg.family == "vlm":
        prefix_len = batch["prefix"].shape[1]
        x = jnp.concatenate([batch["prefix"].astype(cm.DTYPE), x], axis=1)
    elif cfg.family == "encdec":
        enc_out = encdec.encode(params["layers"], batch["frames"].astype(cm.DTYPE),
                                cfg, wvec, avec)
        cache = {"self": cache["self"]}        # cross is rebuilt from enc_out
    Sx = x.shape[1]
    valid = None
    if lengths is None:
        # (1, Sx): rows share positions, so attention keeps its shared
        # (S, S) mask instead of materializing a (B, S, S) batched one
        positions = jnp.arange(Sx, dtype=jnp.int32)[None]
    else:
        if cfg.family not in RAGGED_PREFILL_FAMILIES:
            raise NotImplementedError(
                f"ragged (per-row lengths) prefill is not supported for "
                f"family {cfg.family!r} (see DESIGN.md §6)")
        if Sx > tf.FLASH_THRESHOLD:
            raise NotImplementedError(
                "ragged prefill uses the masked-SDPA path; keep the padded "
                f"prompt length <= {tf.FLASH_THRESHOLD}")
        lens = jnp.asarray(lengths, jnp.int32).reshape(B) + prefix_len
        pos = jnp.arange(Sx, dtype=jnp.int32)[None]
        valid = pos < lens[:, None]                       # (B, Sx)
        positions = jnp.where(valid, pos, tf.EMPTY_POS)
        # zero pad embeddings so per-row dynamic activation scales see only
        # real tokens (keeps ragged rows numerically close to standalone)
        x = jnp.where(valid[..., None], x, 0).astype(x.dtype)
    h, new_cache, _ = forward_hidden(
        params, x, cfg, wvec, avec, positions=positions, cache=cache,
        enc_out=enc_out,
        valid=valid if cfg.family in RECURRENT_STATE_FAMILIES else None)
    if lengths is None:
        h_last = h[:, -1:]
    else:
        idx = jnp.maximum(lens - 1, 0)[:, None, None]
        h_last = jnp.take_along_axis(h, idx, axis=1)
    return (logits_fn(params, h_last, cfg, _last_layer_bits(wvec),
                      _last_layer_bits(avec)), new_cache)


def decode_step(params, tok: jnp.ndarray, t, cache: dict, cfg: ModelConfig,
                wvec, avec) -> Tuple[jnp.ndarray, dict]:
    """One decode step: tok (B, 1) int32, t scalar or (B,) positions.
    Returns (logits (B, 1, V), new_cache)."""
    B = tok.shape[0]
    x = embed(params, tok)
    t = jnp.asarray(t, jnp.int32)
    positions = jnp.broadcast_to(t, (B,))[:, None]        # (B, 1)
    h, new_cache, _ = forward_hidden(params, x, cfg, wvec, avec,
                                     positions=positions, cache=cache, t=t)
    return (logits_fn(params, h, cfg, _last_layer_bits(wvec),
                      _last_layer_bits(avec)), new_cache)


# Families whose decode supports chunked (multi-position) steps — the
# speculative-verify forward.  Attention masks future positions exactly;
# SSM/hybrid recurrences have no per-position rollback.
SPEC_CHUNK_FAMILIES = ("dense", "vlm")


def decode_chunk(params, toks: jnp.ndarray, t, cache: dict, cfg: ModelConfig,
                 wvec, avec) -> Tuple[jnp.ndarray, dict]:
    """Decode U consecutive positions per row in ONE forward.

    ``toks`` (B, U) int32 with ``toks[:, i]`` at position ``t + i``
    (``t`` scalar or (B,)).  This is the speculative-verify step: the
    chunked attention branch writes the same ring slots sequential decode
    would, each query sees exactly its ``kpos <= pos`` prefix, and
    activations quantize under per-token scales (``kops.token_scale_mode``)
    — so on the per-row bit-matrix path the returned logits are
    bit-identical to U sequential :func:`decode_step` calls (the verify
    invariant; DESIGN.md §11).  Returns (logits (B, U, V), new_cache).
    """
    if cfg.family not in SPEC_CHUNK_FAMILIES:
        raise NotImplementedError(
            f"chunked decode is implemented for the attention families "
            f"{SPEC_CHUNK_FAMILIES}, not {cfg.family!r}")
    B, U = toks.shape
    x = embed(params, toks)
    t = jnp.asarray(t, jnp.int32)
    positions = (jnp.broadcast_to(t, (B,))[:, None]
                 + jnp.arange(U, dtype=jnp.int32)[None])   # (B, U)
    with kops.token_scale_mode():
        h, new_cache, _ = forward_hidden(params, x, cfg, wvec, avec,
                                         positions=positions, cache=cache,
                                         t=t)
        logits = logits_fn(params, h, cfg, _last_layer_bits(wvec),
                           _last_layer_bits(avec))
    return logits, new_cache


# ---------------------------------------------------------------------------
# Slot-based persistent cache pool (continuous batching)
# ---------------------------------------------------------------------------

class CachePool:
    """A persistent, slot-based KV/SSM cache for continuous batching.

    The pool owns ONE device cache pytree of batch capacity ``n_slots``
    that lives across requests: ``alloc()`` hands out a free slot,
    ``write_row`` installs a freshly prefilled single-row cache into it
    (a traced-index dynamic_update_slice — slot churn never retraces),
    ``free``/``reset_slot`` recycle it.  Per-slot valid lengths live
    host-side in ``lengths``; visibility inside attention is carried by
    the per-row ``kpos`` columns, so a reset slot is invisible by
    construction (EMPTY_POS) rather than by zeroing data.
    """

    def __init__(self, cfg: ModelConfig, n_slots: int, max_len: int,
                 shardings=None):
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.cache = empty_cache(cfg, n_slots, max_len)
        if shardings is not None:
            self.cache = jax.device_put(self.cache, shardings)
        self.lengths = np.zeros((n_slots,), np.int64)
        self._free = list(range(n_slots - 1, -1, -1))

        def write_row(pool, row, slot):
            return jax.tree.map(
                lambda p, r: jax.lax.dynamic_update_slice(
                    p, r.astype(p.dtype),
                    (0, slot) + (0,) * (p.ndim - 2)),
                pool, row)

        def install_row(pool, row, slot, keep):
            # write_row + a visibility clamp: positions >= keep in the
            # incoming row are masked EMPTY, so a cached row installs as
            # exactly its first ``keep`` tokens (partial-prefix hits)
            def leaf(path, p, r):
                r = r.astype(p.dtype)
                if path and path[-1] == "kpos":
                    r = jnp.where(r >= keep, tf.EMPTY_POS, r)
                return jax.lax.dynamic_update_slice(
                    p, r, (0, slot) + (0,) * (p.ndim - 2))
            return jax.tree_util.tree_map_with_path(
                lambda path, p, r: leaf(tuple(
                    str(getattr(k, "key", k)) for k in path), p, r),
                pool, row)

        def copy_row(pool, src, dst):
            def leaf(p):
                row = jax.lax.dynamic_slice(
                    p, (0, src) + (0,) * (p.ndim - 2),
                    (p.shape[0], 1) + p.shape[2:])
                return jax.lax.dynamic_update_slice(
                    p, row, (0, dst) + (0,) * (p.ndim - 2))
            return jax.tree.map(leaf, pool)

        def reset_row(pool, slot):
            # kpos -> EMPTY_POS masks the KV ring; a recurrent row's
            # state and conv window are zeroed and its live flag cleared
            def leaf(p, path):
                fill = {"kpos": tf.EMPTY_POS, "live": 0, "ssm": 0,
                        "conv": 0}.get(path[-1] if path else "")
                if fill is not None:
                    empty = jnp.full((p.shape[0], 1) + p.shape[2:], fill,
                                     p.dtype)
                    return jax.lax.dynamic_update_slice(
                        p, empty, (0, slot) + (0,) * (p.ndim - 2))
                return p
            return jax.tree_util.tree_map_with_path(
                lambda path, p: leaf(p, tuple(
                    str(getattr(k, "key", k)) for k in path)), pool)

        def rollback_rows(pool, keeps):
            # speculative-decode rejection: entries past keeps[slot] go
            # invisible (kpos -> EMPTY_POS); K/V payloads stay in place,
            # masked by kpos exactly like reset_slot.  kpos leaves are
            # (L, n_slots, Sc); rows outside the spec round pass
            # keep >= EMPTY_POS and are untouched.
            def leaf(path, p):
                if path and path[-1] == "kpos":
                    return jnp.where(p > keeps[None, :, None],
                                     tf.EMPTY_POS, p)
                return p
            return jax.tree_util.tree_map_with_path(
                lambda path, p: leaf(tuple(
                    str(getattr(k, "key", k)) for k in path), p), pool)

        self._write = jax.jit(write_row, donate_argnums=(0,))
        self._install = jax.jit(install_row, donate_argnums=(0,))
        self._copy = jax.jit(copy_row, donate_argnums=(0,))
        self._reset = jax.jit(reset_row, donate_argnums=(0,))
        self._rollback = jax.jit(rollback_rows, donate_argnums=(0,))

    @property
    def free_slots(self) -> int:
        return len(self._free)

    def alloc(self) -> Optional[int]:
        """Claim a free slot (None when the pool is full)."""
        return self._free.pop() if self._free else None

    def free(self, slot: int) -> None:
        """Return a slot to the pool and mask its cache row."""
        if slot in self._free:
            raise ValueError(f"slot {slot} double-freed")
        self.reset_slot(slot)
        self._free.append(slot)

    def reset_slot(self, slot: int) -> None:
        """Mask a slot's cache row (kpos -> EMPTY_POS) and zero its length."""
        self.lengths[slot] = 0
        self.cache = self._reset(self.cache, jnp.asarray(slot, jnp.int32))

    def _check_install(self, slot: int, length: int) -> None:
        """Guard every row install: silent corruption otherwise (an
        out-of-range length poisons the host-side length table, and a
        write into an unallocated slot is clobbered by the next
        ``alloc`` — double-free is caught, so double-install must be
        too)."""
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.n_slots})")
        if slot in self._free:
            raise ValueError(f"slot {slot} is free — alloc() it before "
                             f"installing a row")
        if not 0 <= length <= self.max_len:
            raise ValueError(f"row length {length} not in "
                             f"[0, max_len={self.max_len}]")

    def _refuse_recurrent(self, what: str) -> None:
        if self.cfg.family in RECURRENT_STATE_FAMILIES:
            raise NotImplementedError(
                f"{what} cuts a row back to fewer tokens; family "
                f"{self.cfg.family!r} keeps a recurrent state per row, "
                f"which holds every token it has seen")

    def write_row(self, row_cache, slot: int, length: int) -> None:
        """Install a prefilled single-row cache into ``slot``."""
        self._check_install(slot, length)
        self.lengths[slot] = length
        self.cache = self._write(self.cache, row_cache,
                                 jnp.asarray(slot, jnp.int32))

    def install_prefix(self, row_cache, slot: int, keep: int) -> None:
        """Install the first ``keep`` tokens of a cached single-row
        cache into ``slot`` (the prefix-cache hit path): positions
        >= ``keep`` are masked EMPTY on the way in, and the source row
        is copied, never donated — the cache tier keeps its entry."""
        self._refuse_recurrent("a prefix install")
        self._check_install(slot, keep)
        self.lengths[slot] = keep
        self.cache = self._install(self.cache, row_cache,
                                   jnp.asarray(slot, jnp.int32),
                                   jnp.asarray(keep, jnp.int32))

    def rollback(self, keeps) -> None:
        """Mask every cache entry past ``keeps[slot]`` per slot (the
        speculative-decode rejection path): ``kpos > keep`` becomes
        EMPTY_POS across all layers.  ``keeps`` is an ``(n_slots,)``
        int32 vector of last-kept absolute positions; slots not in a
        speculative round pass any value >= EMPTY_POS (no-op).  Runs as
        one jitted donate-in-place masking — no retrace across rounds."""
        self._refuse_recurrent("a rollback")
        self.cache = self._rollback(self.cache,
                                    jnp.asarray(keeps, jnp.int32))

    def copy_row(self, src: int, dst: int,
                 length: Optional[int] = None) -> None:
        """Duplicate one resident row into another allocated slot
        (traced-index gather + write — no retrace, no host copy)."""
        if src in self._free:
            raise ValueError(f"source slot {src} is free — nothing to "
                             f"copy")
        self._check_install(dst, int(self.lengths[src]
                                     if length is None else length))
        self.lengths[dst] = (self.lengths[src] if length is None
                             else length)
        self.cache = self._copy(self.cache, jnp.asarray(src, jnp.int32),
                                jnp.asarray(dst, jnp.int32))
