"""Plain reference of a dense GQA decoder (Qwen3: RMSNorm, QK-norm, RoPE,
SwiGLU, tied embeddings) served in int8 containers at per-layer bits.

It states the served numerics once, in float32 and plain ``jax.numpy``,
with no kernel, cache, padding or batching, and imports nothing of the
program:

* weights: int8 container ``q`` with per-output-channel scale ``s``; at
  ``b`` bits a layer uses ``round_half_away(q / 2**(8-b))`` clipped to
  ``+-(2**(b-1) - 1)`` with scale ``s * 2**(8-b)``;
* activations: symmetric, ``abits``-bit, one scale per request for the
  prompt (prefill quantizes the whole prompt at once) and one per token
  for every generated token (decode sees one token at a time);
* integer products are summed exactly (int32), then scaled in float32;
* arithmetic in float32 (``highest`` matmul precision), with values
  rounded to bfloat16 where the served model stores them: the residual
  stream, every norm, linear, RoPE and attention output, and the
  attention probabilities.  At 4-bit activations one grid step is a
  quarter of a standard deviation or more, so an input that is not
  rounded as the server stores it lands on a different grid point, and
  a float32-only reference would differ from any bf16 server by whole
  grid steps.

:func:`token_gaps` runs the model once over a prompt and the tokens the
server returned for it, and gives, at each served position, by how much
the served token's logit lies below the reference's best, in units of
the standard deviation of the reference's logits at that position (so
one limit reads the same at any width).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def bf16(x):
    """Round to bfloat16, keep computing in float32 (``reduce_precision``,
    which the compiler keeps: a convert pair it may drop as excess
    precision)."""
    return jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits=8,
                                    mantissa_bits=7)


def _round_half_away(x):
    return jnp.sign(x) * jnp.floor(jnp.abs(x) + 0.5)


def pow2(n):
    """2**n for integer n, exactly (a float power may be approximated)."""
    return jnp.left_shift(1, jnp.asarray(n, jnp.int32)).astype(jnp.float32)


def grid_max(bits):
    """Largest magnitude on a signed ``bits`` grid: 2**(bits-1) - 1."""
    return pow2(jnp.asarray(bits, jnp.int32) - 1) - 1.0


def weight_at_bits(q, s, bits):
    """(int8 values on the ``bits`` grid as int32, float32 scale)."""
    step = pow2(8 - jnp.asarray(bits, jnp.int32))
    lim = grid_max(bits)
    w = jnp.clip(_round_half_away(q.astype(jnp.float32) / step), -lim, lim)
    return w.astype(jnp.int32), s.astype(jnp.float32) * step


def act_quant(x, abits, group):
    """Symmetric ``abits`` quantization of x (T, K) with one scale per
    group: ``group`` (T,) is 0 for prompt rows (one shared scale) and
    1 + t for generated row t (a scale of its own); rows < 0 are padding
    and never set a scale."""
    amax_row = jnp.max(jnp.abs(x), axis=-1)                         # (T,)
    prompt = group == 0
    amax_prompt = jnp.max(jnp.where(prompt, amax_row, 0.0))
    amax = jnp.where(prompt, amax_prompt, amax_row)
    lim = grid_max(abits)
    scale = jnp.maximum(amax, 1e-8) / lim                           # (T,)
    xq = jnp.clip(jnp.round(x / scale[:, None]), -lim, lim)
    return xq.astype(jnp.int32), scale


def linear(x, p, wb, ab, group):
    w, ws = weight_at_bits(p["q"], p["s"], wb)
    xq, xs = act_quant(x, ab, group)
    acc = jnp.dot(xq, w, preferred_element_type=jnp.int32)
    return bf16(acc.astype(jnp.float32) * xs[:, None] * ws.reshape(1, -1))


def rms_norm(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return bf16(x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32))


def rope(x, pos, theta):
    """x (T, heads, hd), pos (T,): rotate-half RoPE."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None]            # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return bf16(jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                                -1))


def forward(params, m: dict, tokens, n_prompt, n_real, wbits, abits):
    """Logits (T, vocab) at every position of one sequence.

    tokens (T,) int32, padded past ``n_real``; the first ``n_prompt`` are
    the prompt.  wbits/abits (n_layers,) int32."""
    T = tokens.shape[0]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps, theta = m["norm_eps"], m["rope_theta"]
    pos = jnp.arange(T, dtype=jnp.int32)
    group = jnp.where(pos < n_prompt, 0, pos + 1)
    group = jnp.where(pos < n_real, group, -1)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_real)
    x = params["emb"][tokens].astype(jnp.float32)

    def layer(x, lp):
        p, wb, ab = lp
        a = p["attn"]
        h = rms_norm(x, p["ln1"]["scale"], eps)
        q = linear(h, a["wq"], wb, ab, group).reshape(T, H, hd)
        k = linear(h, a["wk"], wb, ab, group).reshape(T, KV, hd)
        v = linear(h, a["wv"], wb, ab, group).reshape(T, KV, hd)
        q = rope(rms_norm(q, a["q_norm"]["scale"], eps), pos, theta)
        k = rope(rms_norm(k, a["k_norm"]["scale"], eps), pos, theta)
        qg = q.reshape(T, KV, H // KV, hd)
        s = jnp.einsum("qkgd,skd->kgqs", qg, k) * hd ** -0.5
        s = jnp.where(mask[None, None], s, -jnp.inf)
        pr = bf16(jax.nn.softmax(s, axis=-1))
        o = bf16(jnp.einsum("kgqs,skd->qkgd", pr, v))
        x = bf16(x + linear(o.reshape(T, H * hd), a["wo"], wb, ab, group))
        mp = p["mlp"]
        h = rms_norm(x, p["ln2"]["scale"], eps)
        g = linear(h, mp["wg"], wb, ab, group)
        u = linear(h, mp["wu"], wb, ab, group)
        x = bf16(x + linear(bf16(jax.nn.silu(g) * u), mp["wd"], wb, ab,
                            group))
        return x, None

    x, _ = jax.lax.scan(layer, x, (params["layers"], wbits, abits))
    h = rms_norm(x, params["ln_f"]["scale"], eps)
    emb = params["emb"][:m["vocab_size"]].astype(jnp.float32)
    return jnp.einsum("td,vd->tv", h, emb)


@functools.partial(jax.jit, static_argnums=(1,))
def _gaps(params, mkey, tokens, n_prompt, n_real, wbits, abits):
    m = dict(mkey)
    with jax.default_matmul_precision("highest"):
        logits = forward(params, m, tokens, n_prompt, n_real, wbits, abits)
    nxt = jnp.roll(tokens, -1)                     # token served after t
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.std(logits, axis=-1)


@functools.partial(jax.jit, static_argnums=(1,))
def _control_gaps(params, mkey, tokens, n_prompt, n_real, wbits, abits,
                  wbits_low, abits_low):
    m = dict(mkey)
    with jax.default_matmul_precision("highest"):
        ref = forward(params, m, tokens, n_prompt, n_real, wbits, abits)
        low = forward(params, m, tokens, n_prompt, n_real, wbits_low,
                      abits_low)
    pick = jnp.argmax(low, axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.std(ref, axis=-1)


def _pack(m, prompt, served, length):
    seq = np.zeros((length,), np.int32)
    full = np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(served, np.int32)])
    seq[:full.shape[0]] = full
    return seq, int(len(prompt)), int(full.shape[0])


def _served_slice(gaps, n_prompt, n_served):
    """Gaps at the positions whose next token the server produced: the
    last prompt position (first served token) through the position
    before the last served token."""
    return np.asarray(gaps)[n_prompt - 1:n_prompt - 1 + n_served]


def token_gaps(params, m: dict, prompt, served, wbits, abits,
               length: int) -> np.ndarray:
    """Per served token: reference best logit minus the reference logit
    of the token the server returned (0 where they agree), over the
    standard deviation of the reference logits there."""
    seq, P, n = _pack(m, prompt, served, length)
    g = _gaps(params, tuple(sorted(m.items())), jnp.asarray(seq),
              jnp.int32(P), jnp.int32(n), jnp.asarray(wbits, jnp.int32),
              jnp.asarray(abits, jnp.int32))
    return _served_slice(g, P, len(served))


def control_gaps(params, m: dict, prompt, served, wbits, abits,
                 wbits_low, abits_low, length: int) -> np.ndarray:
    """Per served position: reference best logit minus the reference
    logit of the token the lower-precision model ranks first, over the
    standard deviation of the reference logits there."""
    seq, P, n = _pack(m, prompt, served, length)
    g = _control_gaps(params, tuple(sorted(m.items())), jnp.asarray(seq),
                      jnp.int32(P), jnp.int32(n),
                      jnp.asarray(wbits, jnp.int32),
                      jnp.asarray(abits, jnp.int32),
                      jnp.asarray(wbits_low, jnp.int32),
                      jnp.asarray(abits_low, jnp.int32))
    return _served_slice(g, P, len(served))
