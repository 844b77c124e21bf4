"""The Mamba-2 + GQA layer-pattern hybrid (family ``mamba_attn``,
granite-4.0-h-micro at smoke widths) against its plain reference
(``bench/reference/granite_hybrid.py``: float32, a sequential per-position
recurrence, no cache or padding) on seeded weights, on the CPU.

Logits are compared, in units of the standard deviation of the
reference's logits at each position; a planted fault in each mechanism
the serving path adds (the float32 state, the length mask, the conv
window, the D skip) must read above its tolerance.  Also the
``ssm_update`` kernel in interpret mode against its oracle, and the
refusals of what a recurrent state cannot do."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.kernels import ops, ref as kref
from repro.models import lm, mamba2, mamba_attn
from repro.serve.engine import ServeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench import weights_hybrid  # noqa: E402
from bench.reference import granite_hybrid as ref  # noqa: E402

# one period of the smoke configuration (5 mixers, attention, 4 mixers)
CFG = configs.get_smoke("granite_4_0_h_micro").with_(n_layers=10)
SEED = 3_000_000_017
PLEN, MAXLEN = 32, 64
# Program against reference, in sd of the reference's logits: on the CPU
# the program differs only in the order of its f32 sums (the prompt's
# state by the chunked dual form, the reference by the recurrence),
# 3.9e-7 at most over (a)'s cases; the planted faults of (d) read 0.169
# (state held in bf16), 3.2 (conv window), 3.5 (length mask) and 5.4 (D
# skip).  1e-3 sits 2,500x above the one and 169x below the other.
TOL = 1e-3


def dims(cfg) -> dict:
    """The reference's size block for a ModelConfig of this family."""
    _, H, N, P = mamba2.dims(cfg)
    types = [{"M": "mamba", "A": "attention"}[k] for k in cfg.layer_pattern]
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "layer_types": types * (cfg.n_layers // len(types)),
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "padded_vocab": cfg.padded_vocab,
            "norm_eps": cfg.norm_eps, "ssm_heads": H, "ssm_head_dim": P,
            "ssm_state": N, "conv_kernel": cfg.d_conv,
            "attention_multiplier": cfg.attention_multiplier,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "out_scale": 1.0, "emb_std": 0.02}


M = dims(CFG)


@pytest.fixture(scope="module")
def qp():
    params = weights_hybrid.serve_params(M, SEED)
    want = jax.eval_shape(lambda k: lm.init_serve_params(CFG, k),
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(want) == jax.tree.structure(params)
    return params


def bits(rows=None):
    v = jnp.full((CFG.n_layers,), 8, jnp.int32)
    return v if rows is None else jnp.broadcast_to(v, (rows, CFG.n_layers))


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab_size, n)


def ref_logits(qp, toks, n_prompt):
    seq = np.zeros((MAXLEN,), np.int32)
    seq[:len(toks)] = toks
    out = ref.logits(qp, ref._key(M), jnp.asarray(seq), jnp.int32(n_prompt),
                     jnp.int32(len(toks)), bits(), bits())
    return np.asarray(out)[:len(toks)]


class Programs:
    """The jitted prefill and decode step, traced once per shape (a fault
    planted by monkeypatch needs a fresh instance to be traced in)."""

    def __init__(self):
        self.prefill = jax.jit(lambda q, t, c, n, w: lm.prefill(
            q, {"tokens": t}, CFG, w, w, c, lengths=n))
        self.step = jax.jit(lambda q, tk, t, c, w: lm.decode_step(
            q, tk, t, c, CFG, w, w))


PROG = Programs()


def prefill_rows(qp, rows, lengths, prog=PROG):
    pad = np.zeros((len(rows), PLEN), np.int32)
    for i, r in enumerate(rows):
        pad[i, :len(r)] = r
    return prog.prefill(qp, jnp.asarray(pad),
                        lm.empty_cache(CFG, len(rows), MAXLEN),
                        jnp.asarray(lengths, jnp.int32), bits(len(rows)))


def served_logits(qp, toks, n_prompt, steps=8, slot=1, n_slots=3,
                  prog=PROG):
    """Prefill a row, install it in a pool slot beside empty ones, decode
    ``steps`` tokens of ``toks`` through the pool; logits per position."""
    logits, row = prefill_rows(qp, [toks[:n_prompt]], [n_prompt], prog)
    pool = lm.CachePool(CFG, n_slots, MAXLEN)
    assert pool.alloc() == 0 and pool.alloc() == slot
    pool.write_row(row, slot, n_prompt)
    out = [np.asarray(logits)[0, -1]]
    for i in range(n_prompt, n_prompt + steps):
        tk = np.zeros((n_slots, 1), np.int32)
        tk[slot] = toks[i]
        t = np.zeros((n_slots,), np.int32)
        t[slot] = i
        lg, pool.cache = prog.step(qp, jnp.asarray(tk), jnp.asarray(t),
                                   pool.cache, bits(n_slots))
        out.append(np.asarray(lg)[slot, -1])
    return np.stack(out)[:, :CFG.vocab_size], pool


def gap(got, want):
    """Largest |program - reference| logit, in sd of the reference."""
    return float(np.max(np.abs(got - want) / want.std(axis=-1,
                                                      keepdims=True)))


def prefill_then_decode_gap(qp, P=13, steps=8, prog=PROG):
    toks = tokens(P + steps, seed=P)
    got, _ = served_logits(qp, toks, P, steps, prog=prog)
    want = ref_logits(qp, toks, P)[P - 1:P + steps]
    return gap(got, want)


@pytest.mark.parametrize("P", [2, 13, 32])
def test_prefill_then_decode_matches_reference(qp, P):
    """(a) A prompt shorter than the conv window, one padded in its row,
    one filling it; then 8 decode steps through the pool."""
    assert prefill_then_decode_gap(qp, P) < TOL


def test_ragged_prefill_equals_each_row_alone(qp):
    """(b) Three rows of different lengths in one padded batch: each row's
    last logits, SSM state and conv window equal the row prefilled alone
    in its own padded row (rows share nothing; that a padded
    row equals the unpadded prompt is (a)'s comparison with the
    reference, and (d) plants the faults that would break it)."""
    lens = [32, 11, 2]
    rows = [tokens(n, seed=10 + n) for n in lens]
    lg, cache = prefill_rows(qp, rows, lens)
    for i, r in enumerate(rows):
        lg1, c1 = prefill_rows(qp, [r], [len(r)])
        for a, b in ((lg[i], lg1[0]), (cache["ssm"][:, i], c1["ssm"][:, 0]),
                     (cache["conv"][:, i], c1["conv"][:, 0])):
            # the batch size may change a dot's summation order: f32
            # rounding only
            b = np.asarray(b, np.float32)
            np.testing.assert_allclose(np.asarray(a, np.float32), b,
                                       rtol=1e-5,
                                       atol=1e-6 * np.abs(b).max())
    assert np.asarray(cache["live"]).tolist() == [[1, 1, 1]]


def test_engine_request_admitted_mid_flight_matches_reference(qp):
    """(c) A request submitted while others decode, through
    ``ServeEngine.submit``: each served token is the reference's best, up
    to the tolerance (the logit of the token served, against the
    reference's best, in sd)."""
    eng = ServeEngine(CFG, qp, max_len=MAXLEN, n_slots=3, prefill_len=PLEN,
                      decode_block=4, seed=SEED)
    for n, m in ((20, 12), (5, 9)):
        eng.submit(tokens(n, seed=n), max_new_tokens=m)
    eng.sched_tick()
    prompt = tokens(17, seed=99)
    rid = eng.submit(prompt, max_new_tokens=10)
    eng.submit(tokens(3, seed=3), max_new_tokens=6)
    while eng.queued or eng._has_active():
        eng.sched_tick()
    served = eng.requests[rid].tokens
    assert len(served) == 10 and eng.stats.state_rows
    g = ref.token_gaps(qp, M, prompt, served, bits(), bits(), MAXLEN)
    assert float(np.max(g)) < TOL


def _bf16_state(real):
    def fault(state, *a, **k):
        y, st = real(state, *a, **k)
        return y, st.astype(jnp.bfloat16).astype(jnp.float32)
    return fault


def test_planted_faults_exceed_their_tolerance(qp, monkeypatch):
    """(d) Each fault alone moves (a)'s comparison past its tolerance: the
    state held in bf16, the D skip dropped, the length mask dropped (the
    padded row's state takes in its padding), the conv window taken from
    the padded row's last positions."""
    real_mixer_out = mamba2.mixer_out
    real_mamba = mamba_attn._mamba
    real_tail = mamba_attn._conv_tail
    faults = {
        (mamba_attn.kops, "ssm_update"): _bf16_state(ops.ssm_update),
        (mamba2, "mixer_out"): lambda p, *a, **k: real_mixer_out(
            dict(p, D=p["D"] * 0.0), *a, **k),
        (mamba_attn, "_mamba"): lambda p, x, cfg, wb, ab, st, layer, valid,
        decode: real_mamba(p, x, cfg, wb, ab, st, layer, None, decode),
        (mamba_attn, "_conv_tail"): lambda xbc, valid, K: real_tail(
            xbc, None, K),
    }
    for (mod, name), fault in faults.items():
        with monkeypatch.context() as mp:
            mp.setattr(mod, name, fault)
            assert prefill_then_decode_gap(qp, 13, prog=Programs()) > TOL, \
                name


@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0], [0, 0, 1, 0, 0],
                                  [0, 0, 0, 0, 0], [1, 1, 1, 1, 1]])
def test_ssm_update_kernel_matches_oracle(live):
    """The Pallas kernel (interpret mode) against the jnp oracle: live
    slots updated at the given layer, empty slots' state bit for bit
    untouched and their y zero, every other layer untouched."""
    k = jax.random.split(jax.random.PRNGKey(5), 6)
    L, B, H, P, N = 3, 5, 4, 8, 128
    state = jax.random.normal(k[0], (L, B, H, P, N))
    x = jax.random.normal(k[1], (B, H, P))
    dt = jax.random.uniform(k[2], (B, H), minval=1e-3, maxval=0.1)
    dA = jnp.exp(-4.0 * dt)
    b = jax.random.normal(k[3], (B, N))
    c = jax.random.normal(k[4], (B, N))
    live = jnp.asarray(live, jnp.int32)
    y0, s0 = kref.ssm_update_ref(state, 1, x, dt, dA, b, c, live)
    y1, s1 = ops.ssm_update(state, 1, x, dt, dA, b, c, live, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=1e-6,
                               atol=1e-6)
    dead = ~np.asarray(live).astype(bool)
    assert np.array_equal(np.asarray(s1[1])[dead], np.asarray(state[1])[dead])
    assert not np.asarray(y1)[dead].any()
    for other in (0, 2):
        assert np.array_equal(np.asarray(s1[other]), np.asarray(state[other]))


def test_pool_row_operations_carry_the_state(qp):
    """write_row installs a row's state and live flag, copy_row copies
    them, reset_slot zeroes them; prefix installs and rollbacks, which
    would cut a recurrent state, are refused."""
    _, row = prefill_rows(qp, [tokens(9)], [9])
    pool = lm.CachePool(CFG, 3, MAXLEN)
    a, b = pool.alloc(), pool.alloc()
    pool.write_row(row, a, 9)
    pool.copy_row(a, b)
    for s in (a, b):
        np.testing.assert_array_equal(np.asarray(pool.cache["ssm"][:, s]),
                                      np.asarray(row["ssm"][:, 0]))
    assert np.asarray(pool.cache["live"]).tolist() == [[1, 1, 0]]
    pool.free(b)
    assert not np.asarray(pool.cache["ssm"][:, b]).any()
    assert not np.asarray(pool.cache["conv"][:, b].astype(jnp.float32)).any()
    assert np.asarray(pool.cache["live"]).tolist() == [[1, 0, 0]]
    with pytest.raises(NotImplementedError, match="recurrent state"):
        pool.install_prefix(row, a, 4)
    with pytest.raises(NotImplementedError, match="recurrent state"):
        pool.rollback(np.zeros((3,), np.int32))


def test_prefix_cache_and_speculation_refused(qp):
    from repro.serve.prefix_cache import PrefixCache
    with pytest.raises(ValueError, match="recurrent state"):
        ServeEngine(CFG, qp, prefix_cache=PrefixCache(capacity=4))
    with pytest.raises(ValueError, match="speculative decoding"):
        ServeEngine(CFG, qp, spec_k=2)
    eng = ServeEngine(CFG, qp, max_len=MAXLEN, prefill_len=PLEN)
    with pytest.raises(ValueError, match="speculative decoding"):
        eng.submit(tokens(4), max_new_tokens=4, draft_k=2)
