"""Plain reference of granite-4.0-h (``granitemoehybrid`` with no experts):
Mamba-2 mixers and GQA attention layers in the published ``layer_types``
order, each followed by a SwiGLU MLP, muP multipliers, tied embeddings;
served in int8 containers at per-layer bits.

One sequence at a time, float32 with ``highest`` matmul precision, no
kernel, cache, padding or batching, nothing imported from the program.
The served numerics (weights at ``b`` bits, per-request prompt and
per-token activation scales, exact integer sums, bf16 roundings where
the server stores bf16) are ``bench/reference/lm.py``'s, whose helpers
this file uses.  Per layer, with ``r`` the residual multiplier:

    x = x + r * mixer(rmsnorm(x));  x = x + r * mlp(rmsnorm(x))

* Mamba-2 mixer: ``in_proj`` -> (z, xBC, dt); depthwise causal conv of
  width K with bias, then SiLU; dt = softplus(dt + dt_bias); the state
  (H, P, N) advances one position at a time,
  ``h_t = exp(a dt_t) h_{t-1} + dt_t B_t (outer) x_t``,
  ``y_t = C_t . h_t + D x_t``; ``y * silu(z)``, RMSNorm over the whole
  inner width (one group), ``out_proj``.
* attention: no position embedding, softmax scale
  ``attention_multiplier``, causal.
* embeddings times ``embedding_multiplier``; logits over the tied
  embedding divided by ``logits_scaling``.

Departures from the Hugging Face implementation: the state stays float32
throughout; the conv output, like every activation the server stores, is
rounded to bfloat16; there is no ``time_step_limit`` clamp (the config's
is (0, inf)); linears run at the served int8 numerics above, not in
bfloat16 floating point.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.lm import (_pack, _served_slice, bf16, linear,
                                rms_norm)


def _mlp(x, p, wb, ab, group, eps, r):
    h = rms_norm(x, p["ln2"]["scale"], eps)
    g = linear(h, p["mlp"]["wg"], wb, ab, group)
    u = linear(h, p["mlp"]["wu"], wb, ab, group)
    y = linear(bf16(jax.nn.silu(g) * u), p["mlp"]["wd"], wb, ab, group)
    return bf16(x + r * y)


def _ssm(xh, dt, Bm, Cm, a):
    """Sequential recurrence: xh (T, H, P), dt (T, H), Bm/Cm (T, N)."""
    H, P = xh.shape[1:]
    N = Bm.shape[1]

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (jnp.exp(a * dt_t)[:, None, None] * h
             + (dt_t[:, None] * x_t)[..., None] * b_t[None, None, :])
        return h, jnp.sum(h * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (xh, dt, Bm, Cm))
    return y                                                  # (T, H, P)


def mamba_layer(x, p, m, wb, ab, group):
    T = x.shape[0]
    H, P, N, K = m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"], \
        m["conv_kernel"]
    d_inner = H * P
    mx = p["mixer"]
    h = rms_norm(x, p["ln1"]["scale"], m["norm_eps"])
    zxd = linear(h, mx["in_proj"], wb, ab, group)
    z, xbc, dt = (zxd[:, :d_inner], zxd[:, d_inner:2 * d_inner + 2 * N],
                  zxd[:, 2 * d_inner + 2 * N:])
    w = mx["conv_w"].astype(jnp.float32)                      # (K, C)
    pad = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = sum(pad[i:i + T] * w[i] for i in range(K))
    conv = bf16(jax.nn.silu(conv + mx["conv_b"].astype(jnp.float32)))
    xh = conv[:, :d_inner].reshape(T, H, P)
    Bm, Cm = conv[:, d_inner:d_inner + N], conv[:, d_inner + N:]
    dt = jax.nn.softplus(dt + mx["dt_bias"])
    a = -jnp.exp(mx["A_log"])
    y = _ssm(xh, dt, Bm, Cm, a) + mx["D"][None, :, None] * xh
    y = bf16(y.reshape(T, d_inner) * jax.nn.silu(z))
    y = rms_norm(y, mx["gn"]["scale"], m["norm_eps"])
    out = linear(y, mx["out_proj"], wb, ab, group)
    x = bf16(x + m["residual_multiplier"] * out)
    return _mlp(x, p, wb, ab, group, m["norm_eps"], m["residual_multiplier"])


def attn_layer(x, p, m, wb, ab, group, mask):
    T = x.shape[0]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = p["attn"]
    h = rms_norm(x, p["ln1"]["scale"], m["norm_eps"])
    q = linear(h, a["wq"], wb, ab, group).reshape(T, KV, H // KV, hd)
    k = linear(h, a["wk"], wb, ab, group).reshape(T, KV, hd)
    v = linear(h, a["wv"], wb, ab, group).reshape(T, KV, hd)
    s = jnp.einsum("qkgd,skd->kgqs", q, k) * m["attention_multiplier"]
    s = jnp.where(mask[None, None], s, -jnp.inf)
    pr = bf16(jax.nn.softmax(s, axis=-1))
    o = bf16(jnp.einsum("kgqs,skd->qkgd", pr, v))
    out = linear(o.reshape(T, H * hd), a["wo"], wb, ab, group)
    x = bf16(x + m["residual_multiplier"] * out)
    return _mlp(x, p, wb, ab, group, m["norm_eps"], m["residual_multiplier"])


def _runs(layer_types):
    """(kind, first, end) of each maximal run of one layer kind."""
    out = []
    for i, k in enumerate(layer_types):
        if out and out[-1][0] == k:
            out[-1][2] = i + 1
        else:
            out.append([k, i, i + 1])
    return [tuple(r) for r in out]


def forward(params, m: dict, tokens, n_prompt, n_real, wbits, abits):
    """Logits (T, vocab) at every position of one sequence: tokens (T,)
    int32 padded past ``n_real``, the first ``n_prompt`` the prompt;
    wbits/abits (n_layers,) int32."""
    T = tokens.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    group = jnp.where(pos < n_prompt, 0, pos + 1)
    group = jnp.where(pos < n_real, group, -1)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < n_real)
    x = bf16(params["emb"][tokens].astype(jnp.float32)
             * m["embedding_multiplier"])
    lays = params["layers"]
    mi = ai = 0
    for kind, lo, hi in _runs(m["layer_types"]):
        if kind == "mamba":         # a run of mixers: one scan over them
            def body(x, lp, mi=mi, lo=lo):
                p, wb, ab = lp
                return mamba_layer(x, p, m, wb, ab, group), None
            sl = jax.tree.map(lambda a: a[mi:mi + hi - lo], lays["mamba"])
            x, _ = jax.lax.scan(body, x, (sl, wbits[lo:hi], abits[lo:hi]))
            mi += hi - lo
        else:
            for i in range(lo, hi):
                p = jax.tree.map(lambda a: a[ai], lays["attn"])
                x = attn_layer(x, p, m, wbits[i], abits[i], group, mask)
                ai += 1
    h = rms_norm(x, params["ln_f"]["scale"], m["norm_eps"])
    emb = params["emb"][:m["vocab_size"]].astype(jnp.float32)
    return jnp.einsum("td,vd->tv", h, emb) / m["logits_scaling"]


def _key(m: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


def _unkey(mkey) -> dict:
    return {k: list(v) if isinstance(v, tuple) else v for k, v in mkey}


@functools.partial(jax.jit, static_argnums=(1,))
def _gaps(params, mkey, tokens, n_prompt, n_real, wbits, abits):
    m = _unkey(mkey)
    with jax.default_matmul_precision("highest"):
        logits = forward(params, m, tokens, n_prompt, n_real, wbits, abits)
    nxt = jnp.roll(tokens, -1)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, nxt[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.std(logits, axis=-1)


@functools.partial(jax.jit, static_argnums=(1,))
def _control_gaps(params, mkey, tokens, n_prompt, n_real, wbits, abits,
                  wbits_low, abits_low):
    m = _unkey(mkey)
    with jax.default_matmul_precision("highest"):
        ref = forward(params, m, tokens, n_prompt, n_real, wbits, abits)
        low = forward(params, m, tokens, n_prompt, n_real, wbits_low,
                      abits_low)
    pick = jnp.argmax(low, axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.std(ref, axis=-1)


@functools.partial(jax.jit, static_argnums=(1,))
def logits(params, mkey, tokens, n_prompt, n_real, wbits, abits):
    """Reference logits (T, vocab) of one packed sequence (tests)."""
    with jax.default_matmul_precision("highest"):
        return forward(params, _unkey(mkey), tokens, n_prompt, n_real,
                       wbits, abits)


def token_gaps(params, m: dict, prompt, served, wbits, abits,
               length: int) -> np.ndarray:
    """Per served token: reference best logit minus the reference logit
    of the token the server returned, over the sd of the reference
    logits there (``bench/reference/lm.py``'s reading)."""
    seq, P, n = _pack(m, prompt, served, length)
    g = _gaps(params, _key(m), jnp.asarray(seq), jnp.int32(P), jnp.int32(n),
              jnp.asarray(wbits, jnp.int32), jnp.asarray(abits, jnp.int32))
    return _served_slice(g, P, len(served))


def control_gaps(params, m: dict, prompt, served, wbits, abits,
                 wbits_low, abits_low, length: int) -> np.ndarray:
    """Per served position: the gap of the token a lower-precision
    reference ranks first."""
    seq, P, n = _pack(m, prompt, served, length)
    g = _control_gaps(params, _key(m), jnp.asarray(seq), jnp.int32(P),
                      jnp.int32(n), jnp.asarray(wbits, jnp.int32),
                      jnp.asarray(abits, jnp.int32),
                      jnp.asarray(wbits_low, jnp.int32),
                      jnp.asarray(abits_low, jnp.int32))
    return _served_slice(g, P, len(served))
