"""Per-arch smoke tests: reduced config, one fwd/train step on CPU,
shape + no-NaN asserts (the brief's required per-arch smoke)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core import bitfluid as bf
from repro.models import lm

B, S = 2, 32
KEY = jax.random.PRNGKey(0)


def _batch(cfg):
    batch = {"tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["prefix"] = jax.random.normal(
            KEY, (B, cfg.n_prefix_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            KEY, (B, S // cfg.frames_ratio, cfg.d_model), jnp.float32)
    return batch


@pytest.fixture(scope="module")
def smoke_state():
    return {}


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_train_step_smoke(arch):
    cfg = configs.get_smoke(arch)
    params = lm.init_params(cfg, KEY)
    n = lm.n_bit_slots(cfg)
    wvec = avec = jnp.full((n,), 8, jnp.int32)
    batch = _batch(cfg)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: lm.train_loss(p, batch, cfg, wvec, avec),
        has_aux=True))(params)
    assert np.isfinite(float(loss)) and float(loss) > 0
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in flat)
    assert any(float(jnp.abs(g.astype(jnp.float32)).max()) > 0 for g in flat)


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_serve_smoke(arch):
    cfg = configs.get_smoke(arch)
    params = lm.init_params(cfg, KEY)
    qparams = lm.quantize_params(params, cfg)
    n = lm.n_bit_slots(cfg)
    wvec = avec = jnp.full((n,), 8, jnp.int32)
    batch = _batch(cfg)
    cache = lm.empty_cache(cfg, B, 64)
    logits, cache = jax.jit(
        lambda q, b, c: lm.prefill(q, b, cfg, wvec, avec, c))(
        qparams, batch, cache)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    t0 = S + (cfg.n_prefix_tokens if cfg.family == "vlm" else 0)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    logits2, _ = jax.jit(
        lambda q, tk, t, c: lm.decode_step(q, tk, t, c, cfg, wvec, avec))(
        qparams, tok, jnp.asarray(t0), cache)
    assert logits2.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits2, np.float32)).all()


@pytest.mark.parametrize("container", ["int8", "int4"])
@pytest.mark.parametrize("arch", ["qwen3_4b", "kimi_k2_1t_a32b"])
def test_init_serve_params_matches_quantize(arch, container):
    """Serve form straight from the seed (one fused program) == the
    train-form tree quantized leaf by leaf.  Fused, the compiler may turn
    the division by the constant qmax into a reciprocal multiply: scales
    then differ in the last ulp and a value on a rounding edge moves by
    one quantization step, never more."""
    cfg = configs.get_smoke(arch)
    want = lm.quantize_params(lm.init_params(cfg, KEY), cfg, container)
    got = lm.init_serve_params(cfg, KEY, container)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        if path[-1].key == "q4":
            g, w = bf.unpack_int4_halves(g), bf.unpack_int4_halves(w)
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if path[-1].key in ("q", "q4"):
            assert np.abs(g - w).max() <= 1
            assert np.mean(g != w) < 1e-2
        else:
            np.testing.assert_allclose(g, w, rtol=1e-6)


@pytest.mark.parametrize("arch", ["qwen3_4b", "kimi_k2_1t_a32b",
                                  "mamba2_1_3b"])
def test_bit_vector_is_runtime_data(arch):
    """One jitted program serves different precision configs (bit fluidity:
    no recompilation when the per-layer bit vector changes)."""
    cfg = configs.get_smoke(arch)
    params = lm.init_params(cfg, KEY)
    n = lm.n_bit_slots(cfg)
    batch = _batch(cfg)

    calls = {"n": 0}

    def loss(p, wv, av):
        calls["n"] += 1
        return lm.train_loss(p, batch, cfg, wv, av)[0]

    jitted = jax.jit(loss)
    l8 = jitted(params, jnp.full((n,), 8, jnp.int32),
                jnp.full((n,), 8, jnp.int32))
    l4 = jitted(params, jnp.full((n,), 4, jnp.int32),
                jnp.full((n,), 8, jnp.int32))
    lmix = jitted(params,
                  jnp.where(jnp.arange(n) % 2 == 0, 4, 8).astype(jnp.int32),
                  jnp.full((n,), 8, jnp.int32))
    assert calls["n"] == 1                      # traced exactly once
    assert len({float(l8), float(l4), float(lmix)}) == 3  # bits matter


def test_decode_matches_prefill_qwen3():
    """Teacher-forced prefill logits == step-by-step decode logits."""
    cfg = configs.get_smoke("qwen3_4b")
    params = lm.init_params(cfg, KEY)
    n = lm.n_bit_slots(cfg)
    wvec = avec = jnp.full((n,), 8, jnp.int32)
    toks = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)

    cache = lm.empty_cache(cfg, 1, 16)
    logits_p, cache_p = lm.prefill(params, {"tokens": toks}, cfg, wvec, avec,
                                   cache)

    cache = lm.empty_cache(cfg, 1, 16)
    for t in range(8):
        logits_d, cache = lm.decode_step(params, toks[:, t:t + 1],
                                         jnp.asarray(t), cache, cfg,
                                         wvec, avec)
    # per-tensor dynamic activation scales differ between the batched
    # prefill and single-token decode, so compare distributions, not raw
    # logits: total variation of the next-token softmax
    pp = jax.nn.softmax(logits_p[:, -1].astype(jnp.float32), -1)
    pd = jax.nn.softmax(logits_d[:, -1].astype(jnp.float32), -1)
    tv = float(jnp.abs(pp - pd).sum(-1).max()) * 0.5
    assert tv < 0.12, tv


def test_sliding_window_ring_buffer():
    """starcoder2 smoke: decode beyond the window keeps a bounded cache and
    still produces finite logits (ring-buffer slot reuse)."""
    cfg = configs.get_smoke("starcoder2_15b")     # window = 8
    params = lm.init_params(cfg, KEY)
    n = lm.n_bit_slots(cfg)
    wvec = avec = jnp.full((n,), 8, jnp.int32)
    cache = lm.empty_cache(cfg, 1, 64)
    assert cache["k"].shape[3] == cfg.sliding_window   # bounded!
    tok = jnp.zeros((1, 1), jnp.int32)
    for t in range(20):                                # > 2x window
        logits, cache = lm.decode_step(params, tok, jnp.asarray(t), cache,
                                       cfg, wvec, avec)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
