"""Speculative decoding (DESIGN.md §11): greedy bit-identity against
vanilla decode, post-rejection cache exactness vs a never-drafted run,
zero-retrace program counters, and the fluid controller's draft ledger
(planned-charge / actual-reconcile, early-eos refund)."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.apsim import metrics as apm
from repro.core import policy as pol
from repro.models import lm
from repro.models.transformer import EMPTY_POS
from repro.serve import accounting as acct
from repro.serve.engine import SPEC_K_MAX, ServeEngine
from repro.serve.prefix_cache import PrefixCache

KEY = jax.random.PRNGKey(7)

# full-LM spec rounds are too slow through interpret-mode Pallas; the
# rollback/ledger semantics are covered there by the pure tests below
INTERP = os.environ.get("REPRO_PALLAS", "").lower() == "interpret"
heavy = pytest.mark.skipif(INTERP, reason="pure rollback/ledger tests cover "
                                          "spec decode under interpret "
                                          "Pallas")

PROMPTS = ([3, 1, 4, 1], [3, 1, 4, 1], [2, 7, 1])   # repeat -> cache hit
MAX_NEW = 10


@pytest.fixture(scope="module")
def served():
    cfg = configs.get_smoke("qwen3_4b")
    params = lm.init_params(cfg, KEY)
    return cfg, lm.quantize_params(params, cfg), lm.n_bit_slots(cfg)


def _ctrl(n):
    return pol.BudgetController(
        {"int4": pol.fixed(4), "int8": pol.fixed(8)},
        {"int4": 1.0, "int8": 2.0}, n)


def _engine(served, **kw):
    cfg, qparams, n = served
    return ServeEngine(cfg, qparams, max_len=64,
                       controller=kw.pop("controller", _ctrl(n)),
                       n_slots=2, prefill_len=4, decode_block=4,
                       seed=0, **kw)


def _serve(eng, *, draft_ks=None, max_new=MAX_NEW):
    rids = [eng.submit(p, max_new_tokens=max_new,
                       draft_k=None if draft_ks is None else draft_ks[i])
            for i, p in enumerate(PROMPTS)]
    eng.run()
    return {r: eng.requests[r].tokens for r in rids}


@pytest.fixture(scope="module")
def vanilla_tokens(served):
    """Greedy reference stream from a never-drafting engine."""
    if INTERP:
        pytest.skip("full-LM engine under interpret Pallas")
    return _serve(_engine(served))


# ---------------------------------------------------------------------------
# greedy bit-identity + zero retrace
# ---------------------------------------------------------------------------

@heavy
@pytest.mark.parametrize("hit_policy", [None, "exact", "at_least"])
@pytest.mark.parametrize("draft_ks", [None, [0, 2, SPEC_K_MAX]])
def test_greedy_spec_matches_vanilla(served, vanilla_tokens, hit_policy,
                                     draft_ks):
    """Every (k, per-request override, prefix-cache policy) combination
    emits the exact vanilla greedy stream: each token is a verify-bits
    argmax, rejected drafts roll back invisibly."""
    cache = (None if hit_policy is None
             else PrefixCache(chunk=2, capacity=8, hit_policy=hit_policy))
    eng = _engine(served, spec_k=4, draft_budget_s=1.0,   # int4 drafts
                  prefix_cache=cache)
    got = _serve(eng, draft_ks=draft_ks)
    assert list(got.values()) == list(vanilla_tokens.values())
    if cache is not None:
        assert cache.ledger.hits >= 1       # the repeat prompt actually hit


@heavy
def test_zero_retrace_and_counters(served, vanilla_tokens):
    """Mixed depths across slot churn compile ONE draft and ONE verify
    program, and the per-request spec counters obey the round algebra."""
    eng = _engine(served, spec_k=0, draft_budget_s=1.0)
    got = _serve(eng, draft_ks=[SPEC_K_MAX, 2, 4])
    assert got == vanilla_tokens
    assert eng.stats.traces["draft"] == 1
    assert eng.stats.traces["verify"] == 1
    for rec in eng.requests.values():
        if rec.spec_k == 0:
            assert rec.spec_rounds == rec.draft_units == 0
            continue
        assert rec.draft_units == rec.spec_k * rec.spec_rounds
        assert rec.verify_units == (rec.spec_k + 1) * rec.spec_rounds
        assert 0 <= rec.accepted_units <= rec.draft_units
        # every round delivers accepted drafts + one verified token
        assert rec.spec_tokens == rec.accepted_units + rec.spec_rounds
        assert rec.spec_tokens <= len(rec.tokens)
    agg = acct.aggregate(eng.requests.values())
    assert agg["spec_rounds"] == sum(r.spec_rounds
                                     for r in eng.requests.values())
    assert 0.0 <= agg["spec_accept_rate"] <= 1.0


@heavy
def test_submit_guards(served):
    eng = _engine(served, spec_k=4, draft_budget_s=1.0)
    with pytest.raises(ValueError):
        eng.submit([1, 2], max_new_tokens=4, draft_k=SPEC_K_MAX + 1)
    with pytest.raises(ValueError):
        # 4 + 52 + SPEC_K_MAX > max_len=64: the draft scan could overrun
        eng.submit([1, 2, 3, 4], max_new_tokens=53)
    # the same request is admissible with drafting off
    vane = _engine(served)
    vane.submit([1, 2, 3, 4], max_new_tokens=53)


# ---------------------------------------------------------------------------
# post-rejection cache state: bit-exact vs never drafted
# ---------------------------------------------------------------------------

@heavy
def test_post_rejection_cache_bitexact(served):
    """Draft wrong tokens at low bits, roll back, decode the true
    continuation: the pool is bit-exact vs a run that never drafted —
    kpos identical everywhere, K/V identical at every visible entry."""
    cfg, qparams, n = served
    wv, av = pol.fixed(8).vectors(n)
    dwv, dav = pol.fixed(4).vectors(n)
    prompt = jnp.asarray([[3, 1, 4, 1]], jnp.int32)
    S, max_len = prompt.shape[1], 16

    def prefilled():
        pool = lm.CachePool(cfg, 1, max_len)
        slot = pool.alloc()
        logits, row = lm.prefill(qparams, {"tokens": prompt}, cfg, wv, av,
                                 lm.empty_cache(cfg, 1, max_len))
        pool.write_row(row, slot, S)
        return pool, int(jnp.argmax(logits[0, -1]))

    pool_a, tok = prefilled()
    pool_b, tok_b = prefilled()
    assert tok == tok_b

    # A: three junk drafts at draft bits into positions S..S+2, rejected
    cache = pool_a.cache
    for i, junk in enumerate((7, 9, 11)):
        _, cache = lm.decode_step(qparams, jnp.asarray([[junk]], jnp.int32),
                                  S + i, cache, cfg, dwv, dav)
    pool_a.cache = cache
    pool_a.rollback(np.asarray([S - 1]))    # keep only the prompt

    # both pools now decode the true greedy continuation at target bits
    def continue_greedy(pool, tok, steps=3):
        cache, out = pool.cache, []
        for i in range(steps):
            logits, cache = lm.decode_step(
                qparams, jnp.asarray([[tok]], jnp.int32), S + i, cache,
                cfg, wv, av)
            tok = int(jnp.argmax(logits[0, -1]))
            out.append(tok)
        pool.cache = cache
        return out

    assert continue_greedy(pool_a, tok) == continue_greedy(pool_b, tok)
    kpos_a = np.asarray(pool_a.cache["kpos"])
    kpos_b = np.asarray(pool_b.cache["kpos"])
    np.testing.assert_array_equal(kpos_a, kpos_b)   # rollback left no trace
    visible = kpos_a != EMPTY_POS                   # (L, 1, Sc)
    for leaf in ("k", "v"):                         # (L, 1, KV, Sc, hd)
        a = np.moveaxis(np.asarray(pool_a.cache[leaf]), 3, 2)
        b = np.moveaxis(np.asarray(pool_b.cache[leaf]), 3, 2)
        np.testing.assert_array_equal(a[visible], b[visible])


def test_rollback_masks_only_past_keep(served):
    """Pure pool semantics: kpos > keep goes EMPTY for that slot only;
    a slot passing keep >= EMPTY_POS is untouched (the non-spec rows)."""
    cfg, _, _ = served
    pool = lm.CachePool(cfg, 2, 16)
    kp = np.full_like(np.asarray(pool.cache["kpos"]), EMPTY_POS)
    kp[:, :, :6] = np.arange(6)
    pool.cache = dict(pool.cache, kpos=jnp.asarray(kp))
    pool.rollback(np.asarray([3, EMPTY_POS]))
    out = np.asarray(pool.cache["kpos"])
    assert (out[:, 0, :4] == np.arange(4)).all()
    assert (out[:, 0, 4:] == EMPTY_POS).all()
    np.testing.assert_array_equal(out[:, 1], kp[:, 1])


# ---------------------------------------------------------------------------
# fluid-controller draft ledger
# ---------------------------------------------------------------------------

def test_draft_depth_from_headroom():
    ctrl = pol.FluidController({"int8": pol.fixed(8)}, {"int8": 1.0}, 1,
                               budget_axis="edp", slo=100.0, window=64)
    for spent, k in ((0.0, 8), (60.0, 4), (85.0, 2), (95.0, 0)):
        ctrl.spent = spent
        assert ctrl.draft_depth() == k
    assert pol.FluidController({"int8": pol.fixed(8)}, {"int8": 1.0},
                               1).draft_depth() == 8      # slo=inf


def test_spec_ledger_plan_vs_actual():
    """axis_planned swaps planned spec tokens for draft+verify pricing;
    axis_actual re-prices what ran; charge + reconcile leaves the
    controller holding exactly the actual spend."""
    rec = acct.RequestStats(
        rid=0, budget_s=None, prompt_len=4,
        ap_cost=apm.BitVectorCost((10.0,), (2.0,)),
        draft_cost=apm.BitVectorCost((4.0,), (0.5,)),
        verify_cost=apm.BitVectorCost((12.0,), (2.5,)),
        spec_k=4, planned_units=13, planned_spec_rounds=2,
        planned_spec_tokens=8)
    planned = rec.axis_planned("energy")
    # 5 non-spec units at 2.0 J + 8 drafts at 0.5 J + 2 verifies at 2.5 J
    assert planned == pytest.approx(5 * 2.0 + 8 * 0.5 + 2 * 2.5)
    # the request finished early: one round, 3 of 4 drafts accepted
    rec.tokens = [1] * 5                   # prompt 4 + 5 emitted = 9 units
    rec.spec_rounds, rec.draft_units = 1, 4
    rec.accepted_units, rec.spec_tokens = 3, 4
    actual = rec.axis_actual("energy")
    assert actual == pytest.approx(5 * 2.0 + 4 * 0.5 + 1 * 2.5)
    ctrl = pol.FluidController({"int8": pol.fixed(8)}, {"int8": 1.0}, 1,
                               budget_axis="energy", slo=1e9, window=64)
    ctrl.charge(planned)
    ctrl.reconcile(actual - planned)
    assert ctrl.spent == pytest.approx(actual)


@heavy
def test_fluid_eos_ledger_reconciliation(served, vanilla_tokens):
    """Admissions charge their PLAN; finishes reconcile to what ran.
    An eos-truncated vanilla request refunds its unused decode units;
    a drafting sibling whose acceptance diverged from the full-accept
    plan settles the difference (either direction); the controller ends
    the stream holding exactly the sum of actual spends."""
    cfg, qparams, n = served
    ref = list(vanilla_tokens.values())[-1]        # PROMPTS[-1]'s stream
    eos = ref[len(ref) // 2]
    cfgs = {"int4": pol.fixed(4), "int8": pol.fixed(8)}
    ctrl = pol.FluidController(cfgs, {"int4": 1.0, "int8": 2.0}, n,
                               budget_axis="edp", slo=1e6, window=64)
    eng = ServeEngine(cfg, qparams, max_len=64, controller=ctrl,
                      n_slots=2, prefill_len=4, decode_block=4, seed=0,
                      eos_id=eos, spec_k=0, draft_budget_s=1.0)
    rid0 = eng.submit(PROMPTS[-1], max_new_tokens=MAX_NEW, draft_k=0)
    rid8 = eng.submit(PROMPTS[-1], max_new_tokens=MAX_NEW,
                      draft_k=SPEC_K_MAX)
    eng.run()
    rec0, rec8 = eng.requests[rid0], eng.requests[rid8]
    for rec in (rec0, rec8):                       # greedy: same stream
        assert rec.tokens[-1] == eos and len(rec.tokens) < MAX_NEW
    # the never-drafting request was charged max_new planned units and
    # used fewer: a pure refund
    assert rec0.axis_actual("edp") < rec0.axis_planned("edp")
    assert rec8.spec_rounds >= 1
    spent = rec0.axis_actual("edp") + rec8.axis_actual("edp")
    assert ctrl.spent == pytest.approx(spent)      # plan fully reconciled
