"""Plain reference of a ResNet served at per-image, per-layer bits.

Convolutions are written as patch matrices times weight matrices
(im2col), the way the layer table describes them, in plain ``jax.numpy``
and float32, importing nothing of the program:

* weights: per-output-channel symmetric int8 quantization of the float
  weights (``s = max|w| / 127``, ``q = round(w / s)``); at ``b`` bits a
  layer uses ``round_half_away(q / 2**(8-b))`` clipped to
  ``+-(2**(b-1) - 1)`` with scale ``s * 2**(8-b)``;
* activations: symmetric ``abits`` quantization with one scale per image
  and layer;
* integer products summed exactly (int32), scaled in float32; values
  rounded to bfloat16 where the served model stores them (every conv,
  ReLU, residual sum and pooled map), as in :mod:`bench.reference.lm`.

:func:`logits` gives (B, classes) for images at (B, n_gemm) bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.lm import bf16, grid_max, weight_at_bits


def quantize_weight(w):
    """Float (K, N) -> (int8 values, (1, N) scale), per output channel, in
    numpy's IEEE float32 (a compiled division may be rewritten as a
    multiplication by the reciprocal, which moves single values)."""
    w = np.asarray(w, np.float32)
    s = np.maximum(np.abs(w).max(axis=0, keepdims=True),
                   np.float32(1e-8)) / np.float32(127.0)
    return np.clip(np.round(w / s), -127, 127).astype(np.int8), s


def _act_quant(x, abits):
    """One scale per image: x (B, ..., K), abits (B,)."""
    axes = tuple(range(1, x.ndim))
    lim = grid_max(abits).reshape((-1,) + (1,) * (x.ndim - 1))
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / lim
    return jnp.clip(jnp.round(x / scale), -lim, lim).astype(jnp.int32), scale


def _linear(x, p, wb, ab):
    """x (B, ..., K) at per-image bits wb/ab (B,); p: {"q", "s", "b"}."""
    q, s = p["q"], p["s"]

    def one(xi, wbi, abi):
        w, ws = weight_at_bits(q, s, wbi)
        xq, xs = _act_quant(xi[None], abi[None])
        acc = jnp.dot(xq[0], w, preferred_element_type=jnp.int32)
        return acc.astype(jnp.float32) * xs[0] * ws.reshape(-1)

    y = jax.vmap(one)(x, wb, ab) + p["b"].astype(jnp.float32)
    return bf16(y)


def _patches(x, k, stride, pad):
    """NHWC -> (N, Ho, Wo, k*k*C), tap-major, channel-minor."""
    N, H, W, C = x.shape
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    Ho = (H + 2 * pad - k) // stride + 1
    Wo = (W + 2 * pad - k) // stride + 1
    cols = [xp[:, i:i + stride * (Ho - 1) + 1:stride,
               j:j + stride * (Wo - 1) + 1:stride, :]
            for i in range(k) for j in range(k)]
    return jnp.concatenate(cols, axis=-1)


def forward(params, layers, x, wbits, abits):
    """Logits (B, classes) for images x (B, H, W, 3)."""
    x = bf16(x.astype(jnp.float32))
    gi = 0
    block_in = residual = None
    for l in layers:
        kind = l["kind"]
        if kind == "conv":
            if block_in is None:
                block_in = x
            src = block_in if l["name"].endswith("_down") else x
            y = _linear(_patches(src, l["hk"], l["stride"], l["pad"]),
                        params[l["name"]], wbits[:, gi], abits[:, gi])
            if l["relu"]:
                y = jnp.maximum(y, 0.0)
            gi += 1
            if l["name"].endswith("_down"):
                residual = y
            else:
                x = y
        elif kind == "maxpool":
            k, s, p = l["hk"], l["stride"], l["pad"]
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, k, k, 1), (1, s, s, 1),
                                      ((0, 0), (p, p), (p, p), (0, 0)))
            block_in = None
        elif kind == "avgpool":
            x = bf16(jnp.mean(x, axis=(1, 2)))
            block_in = None
        elif kind == "add":
            skip = residual if residual is not None else block_in
            x = jnp.maximum(bf16(x + skip), 0.0)
            block_in = residual = None
        elif kind == "fc":
            x = _linear(x.reshape(x.shape[0], -1), params[l["name"]],
                        wbits[:, gi], abits[:, gi])
            if l["relu"]:
                x = jnp.maximum(x, 0.0)
            gi += 1
    return x


@functools.partial(jax.jit, static_argnums=(1,))
def _logits(params, layers_key, x, wbits, abits):
    layers = [dict(l) for l in layers_key]
    with jax.default_matmul_precision("highest"):
        return forward(params, layers, x, wbits, abits)


def quantize(params):
    """The float weights ``{name: {"w", "b"}}`` as int8 values and scales."""
    out = {}
    for name, p in params.items():
        q, sc = quantize_weight(p["w"])
        out[name] = {"q": q, "s": sc, "b": np.asarray(p["b"], np.float32)}
    return out


def logits(params, layers, x, wbits, abits):
    """``params``: float weights, or their :func:`quantize` form."""
    if any("w" in p for p in params.values()):
        params = quantize(params)
    key = tuple(tuple(sorted(l.items())) for l in layers)
    return _logits(params, key, x, jnp.asarray(wbits, jnp.int32),
                   jnp.asarray(abits, jnp.int32))
