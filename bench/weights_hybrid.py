"""Serve-form weights of a Mamba-2 + GQA layer-pattern hybrid (granite-4.0-h),
made from ``--seed`` by the benchmark, on the device, in one jitted call.

Linears are int8 containers with per-output-channel float32 scales, as in
``bench/weights.py`` (same container spread, scales at fan-in**-0.5, the
config's ``out_scale`` on the projections that write the residual
stream: ``out_proj``, ``wo``, ``wd``).  The mixer's own parameters follow
the Mamba-2 reference initialisation: ``A = U(1, 16)`` (``A_log`` its
log), ``dt`` log-uniform in [0.001, 0.1] (``dt_bias`` its inverse
softplus), ``D = 1``, conv weights N(0, 1/K) and bias U(-K**-.5, K**-.5);
norm gains are one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.weights import Q_SIGMA, key_from_seed

DT_MIN, DT_MAX = 1e-3, 1e-1
A_MIN, A_MAX = 1.0, 16.0


def shapes(m: dict) -> dict:
    """Serve-form tree of (shape, dtype) for the hybrid ``m``
    (``bench/kinds/lm_hybrid.model_dims``)."""
    d, f, V = m["d_model"], m["d_ff"], m["padded_vocab"]
    H, P, N, K = m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"], \
        m["conv_kernel"]
    Lm = m["layer_types"].count("mamba")
    La = m["layer_types"].count("attention")
    d_inner, C = H * P, H * P + 2 * N
    q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]

    def lin(L, k, n):
        return {"q": ((L, k, n), jnp.int8), "s": ((L, 1, n), jnp.float32)}

    def norm(L, n=d):
        return {"scale": ((L, n), jnp.bfloat16)}

    def mlp(L):
        return {"wg": lin(L, d, f), "wu": lin(L, d, f), "wd": lin(L, f, d)}

    return {
        "emb": ((V, d), jnp.bfloat16),
        "ln_f": {"scale": ((d,), jnp.bfloat16)},
        "layers": {
            "mamba": {
                "ln1": norm(Lm), "ln2": norm(Lm), "mlp": mlp(Lm),
                "mixer": {
                    "in_proj": lin(Lm, d, 2 * d_inner + 2 * N + H),
                    "conv_w": ((Lm, K, C), jnp.bfloat16),
                    "conv_b": ((Lm, C), jnp.bfloat16),
                    "A_log": ((Lm, H), jnp.float32),
                    "D": ((Lm, H), jnp.float32),
                    "dt_bias": ((Lm, H), jnp.float32),
                    "gn": norm(Lm, d_inner),
                    "out_proj": lin(Lm, d_inner, d)}},
            "attn": {
                "ln1": norm(La), "ln2": norm(La), "mlp": mlp(La),
                "attn": {"wq": lin(La, d, q), "wk": lin(La, d, kv),
                         "wv": lin(La, d, kv), "wo": lin(La, q, d)}},
        },
    }


def _fan_in(m: dict, name: str) -> int:
    d, f = m["d_model"], m["d_ff"]
    return {"in_proj": d, "out_proj": m["ssm_heads"] * m["ssm_head_dim"],
            "wq": d, "wk": d, "wv": d, "wo": m["n_heads"] * m["head_dim"],
            "wg": d, "wu": d, "wd": f}[name]


def serve_params(m: dict, seed: int):
    """The serve-form weights of hybrid ``m`` from ``seed``, on the device."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes(m), is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))
    K = m["conv_kernel"]

    def names(path):
        return tuple(str(getattr(k, "key", k)) for k in path)

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, (shape, dtype)) in zip(keys, flat):
            p = names(path)
            if p[-1] == "q":
                z = jax.random.normal(k, shape, jnp.float32) * Q_SIGMA
                out.append(jnp.clip(jnp.round(z), -127, 127).astype(dtype))
            elif p[-1] == "s":
                std = _fan_in(m, p[-2]) ** -0.5
                if p[-2] in ("out_proj", "wo", "wd"):
                    std *= m["out_scale"]
                u = jax.random.uniform(k, shape, jnp.float32, 0.9, 1.1)
                out.append(u * (std / Q_SIGMA))
            elif p[-1] == "scale" or p[-1] == "D":
                out.append(jnp.ones(shape, dtype))
            elif p[-1] == "conv_w":
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * K ** -0.5).astype(dtype))
            elif p[-1] == "conv_b":
                out.append(jax.random.uniform(k, shape, jnp.float32,
                                              -K ** -0.5, K ** -0.5
                                              ).astype(dtype))
            elif p[-1] == "A_log":
                out.append(jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, A_MIN, A_MAX)))
            elif p[-1] == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, jnp.log(DT_MIN), jnp.log(DT_MAX)))
                out.append(dt + jnp.log(-jnp.expm1(-dt)))   # softplus^-1
            else:                                            # embedding
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * m["emb_std"]).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key_from_seed(seed, 1))
