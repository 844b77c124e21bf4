"""Continuous-batching serve stack: per-request bit fluidity, slot pool
reuse, scan-fused decode, per-request sampling — all zero-retrace."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.core import policy as pol
from repro.models import lm
from repro.serve.engine import ServeEngine

KEY = jax.random.PRNGKey(2)


@pytest.fixture(scope="module")
def served():
    """One quantized smoke model + controller shared by the module."""
    cfg = configs.get_smoke("qwen3_4b")
    params = lm.init_params(cfg, KEY)
    qparams = lm.quantize_params(params, cfg)
    n = lm.n_bit_slots(cfg)
    ctrl = pol.BudgetController(
        {"int4": pol.fixed(4),
         "mixed": pol.per_layer([8, 4], name="mixed"),
         "int8": pol.fixed(8)},
        {"int4": 0.5, "mixed": 0.75, "int8": 1.0}, n)
    return cfg, qparams, ctrl


def _engine(served, **kw):
    cfg, qparams, ctrl = served
    kw.setdefault("max_len", 64)
    return ServeEngine(cfg, qparams, controller=ctrl, **kw)


def test_per_request_bits_are_row_exact(served):
    """A mixed-budget batch serves each row EXACTLY as a uniform-budget
    batch would serve it: per-request precision decouples rows."""
    eng = _engine(served)
    batch = {"tokens": jax.random.randint(KEY, (2, 8), 0,
                                          served[0].vocab_size)}
    eng.set_budget(jnp.asarray([10.0, 0.4]))        # int8 row, int4 row
    mixed = np.asarray(eng.generate(batch, steps=4))
    eng.set_budget(jnp.asarray([10.0, 10.0]))
    all8 = np.asarray(eng.generate(batch, steps=4))
    eng.set_budget(jnp.asarray([0.4, 0.4]))
    all4 = np.asarray(eng.generate(batch, steps=4))
    np.testing.assert_array_equal(mixed[0], all8[0])
    np.testing.assert_array_equal(mixed[1], all4[1])
    assert not (mixed[1] == all8[1]).all()          # bits really differ
    assert eng.stats.prefill_traces == 1
    assert eng.stats.decode_traces == 1


def test_continuous_batching_slot_reuse_zero_retrace(served):
    """More requests than slots: the scheduler streams them through freed
    slots; prefill/decode each compile exactly once for the whole run."""
    eng = _engine(served, n_slots=2, prefill_len=8, decode_block=4)
    rng = np.random.default_rng(0)
    budgets = [10.0, 0.4, 0.75, 10.0, 0.4]
    rids = [eng.submit(rng.integers(0, served[0].vocab_size, (4 + i % 4,)),
                       max_new_tokens=5, budget_s=b)
            for i, b in enumerate(budgets)]
    res = eng.run()
    assert sorted(res) == sorted(rids)
    slots = set()
    for rid, b in zip(rids, budgets):
        st = res[rid]
        assert st.done and st.n_tokens == 5
        assert all(0 <= t < served[0].vocab_size for t in st.tokens)
        slots.add(st.slot)
        wv, _ = eng.controller.resolve(jnp.asarray(b, jnp.float32))
        assert st.mean_wbits == pytest.approx(
            float(jnp.mean(wv.astype(jnp.float32))))
    assert slots == {0, 1}                          # both slots recycled
    assert eng.stats.admitted == eng.stats.completed == 5
    assert eng.stats.prefill_traces == 1            # (1, prefill_len) once
    assert eng.stats.decode_traces == 1             # one fused block once
    assert eng.pool.free_slots == 2                 # pool fully reclaimed


@pytest.mark.parametrize("decode_block", [1, 4])
def test_continuous_matches_whole_batch_greedy(served, decode_block):
    """A request served through the slot pool produces the same greedy
    continuation as the standalone ragged prefill + decode path.  At
    ``decode_block=1`` each dispatch is one decode step, so the two cases
    together check that scan fusion is pure scheduling."""
    cfg, qparams, ctrl = served
    prompt = np.asarray([5, 9, 2, 7, 3], np.int64)
    eng = _engine(served, n_slots=2, prefill_len=8,
                  decode_block=decode_block)
    rid = eng.submit(prompt, max_new_tokens=4, budget_s=10.0)
    got = eng.run()[rid].tokens

    n = lm.n_bit_slots(cfg)
    wv = jnp.full((n,), 8, jnp.int32)
    toks = np.zeros((1, 8), np.int32)
    toks[0, :5] = prompt
    cache = lm.empty_cache(cfg, 1, 64)
    logits, cache = lm.prefill(qparams, {"tokens": jnp.asarray(toks)}, cfg,
                               wv, wv, cache, lengths=jnp.asarray([5]))
    want = [int(jnp.argmax(logits[0, -1]))]
    t = 5
    for _ in range(3):
        tok = jnp.asarray([[want[-1]]], jnp.int32)
        logits, cache = lm.decode_step(qparams, tok, jnp.asarray([t]),
                                       cache, cfg, wv, wv)
        want.append(int(jnp.argmax(logits[0, -1])))
        t += 1
    assert got == want


def test_per_request_sampling_params(served):
    """Greedy rows are deterministic; temperature/top-k rows sample within
    the top-k support — in the same fused decode program."""
    cfg = served[0]
    eng = _engine(served, n_slots=3, prefill_len=8, decode_block=4, seed=3)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, (6,))
    r_greedy = eng.submit(prompt, max_new_tokens=8, budget_s=10.0)
    r_hot = eng.submit(prompt, max_new_tokens=8, budget_s=10.0,
                       temperature=1.5, top_k=4)
    res = eng.run()

    eng2 = _engine(served, n_slots=3, prefill_len=8, decode_block=4, seed=99)
    r2 = eng2.submit(prompt, max_new_tokens=8, budget_s=10.0)
    res2 = eng2.run()
    # greedy is seed-independent
    assert res[r_greedy].tokens == res2[r2].tokens
    # sampled row differs from greedy (V=512, k=4, T=1.5: overwhelmingly)
    assert res[r_hot].tokens != res[r_greedy].tokens


def test_eos_stops_early(served):
    eng = _engine(served, n_slots=1, prefill_len=8, decode_block=4)
    prompt = np.asarray([1, 2, 3], np.int64)
    rid = eng.submit(prompt, max_new_tokens=16, budget_s=10.0)
    full = eng.run()[rid].tokens
    eos = full[2]                       # force an eos hit at position 2
    eng2 = _engine(served, n_slots=1, prefill_len=8, decode_block=4)
    eng2.eos_id = eos
    rid2 = eng2.submit(prompt, max_new_tokens=16, budget_s=10.0)
    got = eng2.run()[rid2].tokens
    assert got == full[:3]
    assert eng2.pool.free_slots == 1


def test_cache_pool_alloc_free_cycle(served):
    cfg = served[0]
    pool = lm.CachePool(cfg, n_slots=2, max_len=16)
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == {0, 1} and pool.alloc() is None
    row = lm.empty_cache(cfg, 1, 16)
    pool.write_row(row, a, 7)
    assert pool.lengths[a] == 7
    pool.free(a)
    assert pool.free_slots == 1 and pool.lengths[a] == 0
    with pytest.raises(ValueError):
        pool.free(a)
    assert pool.alloc() == a            # LIFO recycle


def test_sliding_window_ragged_prefill_keeps_real_tokens():
    """A short prompt padded past the ring capacity must keep its real
    tokens (per-row gather), not the uniform padding tail: the continuous
    path matches the exact-length whole-batch path token-for-token."""
    cfg = configs.get_smoke("starcoder2_15b")       # sliding_window == 8
    params = lm.init_params(cfg, KEY)
    qparams = lm.quantize_params(params, cfg)
    n = lm.n_bit_slots(cfg)
    ctrl = pol.BudgetController({"int8": pol.fixed(8)}, {"int8": 1.0}, n)
    prompt = np.asarray([3, 1, 4, 1], np.int64)

    # prefill_len=16 > ring capacity Sc=8: the padded buffer overflows
    eng = ServeEngine(cfg, qparams, max_len=64, controller=ctrl,
                      n_slots=1, prefill_len=16, decode_block=4)
    rid = eng.submit(prompt, max_new_tokens=8, budget_s=10.0)
    eng.step()                                      # still in flight
    kpos0 = np.asarray(eng.pool.cache["kpos"][0, 0])
    assert (kpos0 < 2 ** 30).sum() >= 4             # real tokens survived
    got = eng.run()[rid].tokens[:4]

    eng2 = ServeEngine(cfg, qparams, max_len=64, controller=ctrl)
    want = np.asarray(eng2.generate(
        {"tokens": jnp.asarray(prompt[None], jnp.int32)}, steps=4))[0]
    assert got == want.tolist()


def test_vlm_continuous_serving():
    """vlm requests stream through the pool with their prefix embeddings."""
    cfg = configs.get_smoke("internvl2_1b")
    params = lm.init_params(cfg, KEY)
    qparams = lm.quantize_params(params, cfg)
    n = lm.n_bit_slots(cfg)
    ctrl = pol.BudgetController(
        {"int4": pol.fixed(4), "int8": pol.fixed(8)},
        {"int4": 0.5, "int8": 1.0}, n)
    eng = ServeEngine(cfg, qparams, max_len=64, controller=ctrl,
                      n_slots=2, prefill_len=8, decode_block=4)
    with pytest.raises(ValueError):                 # prefix is required
        eng.submit(np.asarray([1, 2, 3]), budget_s=1.0)
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.integers(0, cfg.vocab_size, (5,)),
                       max_new_tokens=5, budget_s=b,
                       prefix=rng.standard_normal(
                           (cfg.n_prefix_tokens, cfg.d_model)))
            for b in (2.0, 0.4, 2.0)]
    res = eng.run()
    for rid in rids:
        assert res[rid].done and res[rid].n_tokens == 5
    assert eng.stats.prefill_traces == 1
    assert eng.stats.decode_traces == 1


def test_unsupported_family_and_topk_rejected(served):
    cfg = configs.get_smoke("mamba2_1_3b")          # ssm: no ragged prefill
    params = lm.init_params(cfg, KEY)
    qparams = lm.quantize_params(params, cfg)
    eng = ServeEngine(cfg, qparams, max_len=64)
    with pytest.raises(NotImplementedError):
        eng.submit(np.asarray([1, 2, 3]))
    eng2 = _engine(served, n_slots=1, prefill_len=8)
    with pytest.raises(ValueError):
        eng2.submit(np.asarray([1, 2, 3]), top_k=10_000)


def test_per_request_edp_accounting(served):
    """Every request's resolved bit vector is priced into AP cycles/energy
    (apsim.costmodel), so RequestStats reports per-request latency/EDP —
    the Table 7 accuracy-vs-EDP trade-off at request granularity."""
    eng = _engine(served, n_slots=2, prefill_len=8, decode_block=4)
    prompt = np.asarray([3, 1, 4, 1, 5], np.int64)
    r8 = eng.submit(prompt, max_new_tokens=4, budget_s=10.0)
    r4 = eng.submit(prompt, max_new_tokens=4, budget_s=0.4)
    res = eng.run()
    s8, s4 = res[r8], res[r4]
    assert s4.ap_energy_per_token_j < s8.ap_energy_per_token_j
    assert s4.ap_cycles_per_token < s8.ap_cycles_per_token
    assert 0 < s4.edp < s8.edp
    assert s8.latency_s > 0 and s4.latency_s > 0
    # per-layer breakdown: one entry per bit slot + the logits head
    assert len(s8.ap_cost.per_layer_cycles) == eng.n_layers + 1
    assert s8.ap_latency_s == pytest.approx(
        s8.processed_tokens * s8.ap_cycles_per_token / s8.ap_cost.freq_hz)
    assert s8.ap_energy_j == pytest.approx(
        s8.processed_tokens * s8.ap_energy_per_token_j)
    # identical bit vectors hit the pricing cache (one object, shared)
    r8b = eng.submit(prompt, max_new_tokens=2, budget_s=10.0)
    assert eng.run()[r8b].ap_cost is s8.ap_cost


def test_engine_families_follow_controller(served):
    """The grouped dispatch family set is derived from the controller's
    registered configurations (4- and 8-bit here)."""
    eng = _engine(served)
    assert eng._families == (4, 8)


@pytest.mark.parametrize("arch,linears", [("qwen3_4b", 7),
                                          ("granite_4_0_h_micro", 52)])
def test_layer_scans_feed_the_gemm_whole_stacks(arch, linears):
    """Both benchmark cells' models (smoke widths, the int8 menu): every
    serve linear their decode and row-prefill programs trace takes the
    layer stack whole with its layer index, none a per-layer 2-D copy
    (qwen3-4b: seven linears a layer body; granite: a period unrolled,
    nine Mamba-2 layers of five and one attention layer of seven)."""
    cfg = configs.get_smoke(arch)
    qparams = lm.init_serve_params(cfg, KEY)
    ctrl = pol.BudgetController({"int8": pol.per_layer([8], [8], "int8")},
                                {"int8": 1.0}, cfg.n_layers)
    eng = ServeEngine(cfg, qparams, controller=ctrl, max_len=32, n_slots=2,
                      prefill_len=8, decode_block=2)
    rng = np.random.default_rng(0)
    rids = [eng.submit(rng.integers(0, cfg.vocab_size, (n,)),
                       max_new_tokens=3, budget_s=10.0) for n in (3, 6)]
    res = eng.run()
    assert all(len(res[r].tokens) == 3 for r in rids)
    assert eng.stats.weight_feeds == {"prefill": (linears, 0),
                                      "decode": (linears, 0)}
