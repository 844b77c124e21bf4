"""Weights and inputs made from ``--seed``, by the benchmark and not by the
program: the reference reads the same arrays the server is handed, and
nothing the server derived from them.

LM weights are made directly in the serve form the engine takes (int8
containers ``q`` with per-output-channel float32 scales ``s``; bf16
embedding and norm gains), on the device, in one jitted call.  Container
values are a rounded normal clipped to the int8 grid, so each output
channel spans the grid the way a per-channel max-scaled quantization of a
normal weight would.  CNN weights are made in train form (float ``w``,
``b``): the CNN server quantizes them itself.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Q_SIGMA = 32.0          # std of the int8 container values (|q| <= 127)


def key_from_seed(seed: int, stream: int = 0):
    """A jax key from any non-negative seed, also one above 2**31."""
    state = np.random.SeedSequence([int(seed), int(stream)]).generate_state(1)
    return jax.random.PRNGKey(int(state[0]) & 0x7FFFFFFF)


def lm_shapes(m: dict) -> dict:
    """Serve-form tree of shapes and dtypes for a dense GQA model ``m``
    (the keys of ``bench/configs/<name>.json``'s ``model``)."""
    L, d, H, KV = m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd, f, V = m["head_dim"], m["d_ff"], m["padded_vocab"]

    def lin(k, n):
        return {"q": ((L, k, n), jnp.int8), "s": ((L, 1, n), jnp.float32)}

    return {
        "emb": ((V, d), jnp.bfloat16),
        "ln_f": {"scale": ((d,), jnp.bfloat16)},
        "layers": {
            "ln1": {"scale": ((L, d), jnp.bfloat16)},
            "ln2": {"scale": ((L, d), jnp.bfloat16)},
            "attn": {"wq": lin(d, H * hd), "wk": lin(d, KV * hd),
                     "wv": lin(d, KV * hd), "wo": lin(H * hd, d),
                     "q_norm": {"scale": ((L, hd), jnp.bfloat16)},
                     "k_norm": {"scale": ((L, hd), jnp.bfloat16)}},
            "mlp": {"wg": lin(d, f), "wu": lin(d, f), "wd": lin(f, d)},
        },
    }


def _leaf_std(m: dict, path: tuple) -> float:
    """Effective weight std of one linear: fan-in**-0.5, times the
    config's ``out_scale`` on the two projections that write the
    residual stream (``wo``, ``wd``)."""
    d, f = m["d_model"], m["d_ff"]
    fan_in = {"wq": d, "wk": d, "wv": d, "wg": d, "wu": d,
              "wo": m["n_heads"] * m["head_dim"], "wd": f}[path[-1]]
    std = fan_in ** -0.5
    if path[-1] in ("wo", "wd"):
        std *= m["out_scale"]
    return std


def lm_serve_params(m: dict, seed: int):
    """The serve-form weights of model ``m`` from ``seed``, on the device."""
    shapes = lm_shapes(m)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))

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
                # per-channel spread of +-10% around the target std
                u = jax.random.uniform(k, shape, jnp.float32, 0.9, 1.1)
                out.append(u * (_leaf_std(m, p[:-1]) / Q_SIGMA))
            elif p[-1] == "scale":
                out.append(jnp.ones(shape, dtype))
            else:                                   # embedding
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * m["emb_std"]).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key_from_seed(seed, 1))


def cnn_params(layers: list, seed: int, num_classes: int = 1000):
    """Train-form ResNet weights ``{name: {"w" (fk, cout), "b" (cout,)}}``
    (bf16, He-normal, zero bias) for the layer table of
    ``bench/configs/<name>.json``, on the device, in one jitted call."""
    gemm = [l for l in layers if l["kind"] in ("conv", "fc")]

    def make(key):
        keys = jax.random.split(key, len(gemm))
        out = {}
        for k, l in zip(keys, gemm):
            if l["kind"] == "conv":
                fan_in = l["hk"] * l["hk"] * l["cin"]
            else:
                fan_in = l["cin"]
            w = jax.random.normal(k, (fan_in, l["cout"]), jnp.float32)
            out[l["name"]] = {
                "w": (w * (2.0 / fan_in) ** 0.5).astype(jnp.bfloat16),
                "b": jnp.zeros((l["cout"],), jnp.bfloat16)}
        return out

    return jax.jit(make)(key_from_seed(seed, 1))
