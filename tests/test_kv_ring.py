"""The KV ring is written one column a row per layer, and nothing else.

One decode step (or one speculative-verify chunk of U positions) on a
pool cache whose rows sit at different positions must change, in every
layer l, exactly row b's ring slots ``(t_b + i) % Sc`` of ``k``, ``v``,
``kpos`` (and the int8 cache's ``ks``/``vs``); every other entry of the
stack stays bit for bit.  Covers the dense bf16 cache, the dense int8 KV
cache and the Mamba-2 + GQA hybrid's ring, at smoke widths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import lm
from repro.models.transformer import EMPTY_POS

T = np.asarray([4, 21, 9])          # mixed row positions; row 1 has wrapped
MAX_LEN = 16


def _filled_ring(kv: dict, rng) -> dict:
    """Random k/v (and scales) with each row's last Sc positions visible."""
    L, B, KV, Sc, hd = kv["k"].shape
    out = {}
    for name, a in kv.items():
        if name == "kpos":
            continue
        vals = rng.standard_normal(a.shape).astype(np.float32)
        if a.dtype == jnp.int8:
            vals = rng.integers(-127, 128, a.shape)
        elif name in ("ks", "vs"):
            vals = np.abs(vals) + 0.01
        out[name] = jnp.asarray(vals, a.dtype)
    kpos = np.full((L, B, Sc), EMPTY_POS, np.int32)
    for b, t in enumerate(T):
        for p in range(max(t - Sc, 0), t):
            kpos[:, b, p % Sc] = p
    out["kpos"] = jnp.asarray(kpos)
    return out


def _check_columns(before: dict, after: dict, u: int):
    """Only row b's slots (T[b] + i) % Sc, i < u, changed, in every layer."""
    L, B, KV, Sc, hd = before["k"].shape
    written = np.zeros((L, B, Sc), bool)
    for b, t in enumerate(T):
        written[:, b, (t + np.arange(u)) % Sc] = True
    for name in before:
        a, c = np.asarray(before[name]), np.asarray(after[name])
        if name != "kpos":                          # (L, B, KV, Sc, ...)
            a, c = np.moveaxis(a, 3, 2), np.moveaxis(c, 3, 2)
        np.testing.assert_array_equal(c[~written], a[~written], err_msg=name)
        if name == "kpos":
            want = (T[None, :, None] + np.arange(u)[None, None]).repeat(L, 0)
            got = np.stack([c[:, b, (t + np.arange(u)) % Sc]
                            for b, t in enumerate(T)], axis=1)
            np.testing.assert_array_equal(got, want)
        else:                           # each layer's column rewritten
            for b, t in enumerate(T):
                for s in (t + np.arange(u)) % Sc:
                    moved = (c[:, b, s] != a[:, b, s]).reshape(L, -1)
                    assert moved.any(axis=1).all(), (name, b, s)


CASES = {
    "dense_bf16": ("qwen3_4b", 0, 1),
    "dense_int8": ("qwen3_4b", 8, 1),
    "mamba_attn": ("granite_4_0_h_micro", 0, 1),
    "chunk_bf16": ("qwen3_4b", 0, 3),
    "chunk_int8": ("qwen3_4b", 8, 3),
}


@pytest.mark.parametrize("case", list(CASES))
def test_decode_writes_one_column_per_layer(case):
    arch, kv_bits, u = CASES[case]
    cfg = configs.get_smoke(arch).with_(kv_cache_bits=kv_bits)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    bits = jnp.full((lm.n_bit_slots(cfg),), 8, jnp.int32)
    cache = lm.empty_cache(cfg, len(T), MAX_LEN)
    rng = np.random.default_rng(0)
    if cfg.family == "mamba_attn":
        cache = dict(cache, kv=_filled_ring(cache["kv"], rng),
                     live=jnp.ones_like(cache["live"]))
        ring = lambda c: c["kv"]                    # noqa: E731
    else:
        cache = _filled_ring(cache, rng)
        ring = lambda c: c                          # noqa: E731
    before = jax.tree.map(np.asarray, ring(cache))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (len(T), u)),
                       jnp.int32)
    t = jnp.asarray(T, jnp.int32)
    if u == 1:
        logits, new = lm.decode_step(params, toks, t, cache, cfg, bits, bits)
    else:
        logits, new = lm.decode_chunk(params, toks, t, cache, cfg, bits,
                                      bits)
    assert logits.shape[:2] == (len(T), u)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    _check_columns(before, ring(new), u)
