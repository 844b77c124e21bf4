"""The serving engine's host spans and counters (``RuntimeStats``): the
span tree of a tick, its coverage, the prefill counters with and without
prefix-cache hits, the ring's bound, retrace marks, the speculative
path's names, and the jitted programs' names the benchmark reads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import policy as pol
from repro.models import lm
from repro.serve import accounting as acct
from repro.serve.engine import ServeEngine
from repro.serve.prefix_cache import PrefixCache

KEY = jax.random.PRNGKey(13)

PARENT = {"tick": None, "request.submit": None,
          "admit": "tick", "decode": "tick",
          "admit.request": "admit", "admit.plan": "admit.request",
          "prefill.dispatch": "admit.request", "admit.sync": "admit.request",
          "decode.dispatch": "decode", "decode.sync": "decode",
          "decode.harvest": "decode"}
CHILDREN = {"admit.request": ["admit.plan", "prefill.dispatch",
                              "admit.sync"],
            "decode": ["decode.dispatch", "decode.sync", "decode.harvest"]}


@pytest.fixture(scope="module")
def served():
    cfg = configs.get_smoke("qwen3_4b")
    qparams = lm.quantize_params(lm.init_params(cfg, KEY), cfg)
    ctrl = pol.BudgetController(
        {"int4": pol.fixed(4), "int8": pol.fixed(8)},
        {"int4": 1.0, "int8": 2.0}, lm.n_bit_slots(cfg))
    return cfg, qparams, ctrl


def _engine(served, **kw):
    cfg, qparams, ctrl = served
    kw.setdefault("n_slots", 2)
    kw.setdefault("prefill_len", 8)
    return ServeEngine(cfg, qparams, controller=ctrl, max_len=64,
                       decode_block=4, seed=0, **kw)


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lengths]


def _spans(eng):
    return [e for e in eng.stats.events if e.t1_ns > e.t0_ns]


@pytest.mark.parametrize("spec_k", [None, 2])
def test_tick_span_tree(served, spec_k):
    """Names, parentage and order of a few ticks' spans; one
    ``admit.request`` per admitted rid and one ``request.submit`` per
    submitted one; the speculative round emits the same ``decode.*``."""
    eng = _engine(served, spec_k=spec_k, draft_budget_s=1.0)
    rids = [eng.submit(p, max_new_tokens=6)
            for p in _prompts(served[0], (5, 8, 3))]
    while eng.queued or eng._has_active():
        eng.sched_tick()
    spans = _spans(eng)
    by_seq = {e.seq: e for e in spans}
    for e in spans:
        assert (by_seq[e.parent].name if e.parent >= 0 else None) \
            == PARENT[e.name], e
        if e.parent >= 0:
            assert by_seq[e.parent].tick == e.tick
            assert by_seq[e.parent].t0_ns <= e.t0_ns <= e.t1_ns \
                <= by_seq[e.parent].t1_ns
    assert [e.tick for e in spans if e.name == "tick"] \
        == list(range(eng.stats.ticks))
    assert [e.rid for e in spans if e.name == "request.submit"] == rids
    admitted = sorted(r.rid for r in eng.requests.values()
                      if r.admitted_tick >= 0)
    assert sorted(e.rid for e in spans if e.name == "admit.request") \
        == admitted == rids
    for parent, names in CHILDREN.items():
        for p in (e for e in spans if e.name == parent):
            kids = sorted((e for e in spans if e.parent == p.seq),
                          key=lambda e: e.t0_ns)
            assert [e.name for e in kids] == names
    assert any(e.name == "decode" for e in spans)
    assert (eng.stats.draft_traces > 0) == (spec_k is not None)
    # one scheduler clock: the runtime's tick is the one spans carry,
    # also when a caller sets it (as the CNN traffic loop does)
    assert eng._tick == eng.stats.clock == eng.stats.ticks
    eng._tick = 40
    eng.stats.mark("x")
    assert eng.stats.events[-1].tick == 40


def test_children_cover_their_parents(served):
    """Every span with children (``tick``, ``admit``, ``admit.request``,
    ``decode``) spends little time outside them."""
    eng = _engine(served)
    for p in _prompts(served[0], (4, 7, 8, 2)):
        eng.submit(p, max_new_tokens=5)
    eng.run()
    spans = _spans(eng)
    parents = {e.parent for e in spans}
    checked = set()
    for p in spans:
        if p.seq not in parents:
            continue
        kids = sum(e.t1_ns - e.t0_ns for e in spans if e.parent == p.seq)
        self_ns = (p.t1_ns - p.t0_ns) - kids
        assert 0 <= self_ns <= 2_000_000 + 0.02 * (p.t1_ns - p.t0_ns), p
        checked.add(p.name)
    assert checked == {"tick", "admit", "admit.request", "decode"}


@pytest.mark.parametrize("cached", [False, True])
def test_prefill_counters(served, cached):
    """The counters are the sums over the prompts: a miss prefills its
    whole prompt, a partial hit the tokens past the cached prefix, a
    full hit nothing; every prefilled row runs ``prefill_len``
    positions.  One ``*.sync`` span per admission and per decode block."""
    cfg = served[0]
    base, other = _prompts(cfg, (8, 6), seed=3)
    ext = np.concatenate([base[:4], other[:3]])     # shares 4 tokens
    eng = _engine(served, prefix_cache=(
        PrefixCache(chunk=4, capacity=8, hit_policy="at_least")
        if cached else None))
    for p in (base, base, ext):                     # miss, full, partial
        eng.submit(p, max_new_tokens=4)
        eng.run()
    st = eng.stats
    if cached:
        rows, tokens = 2, len(base) + (len(ext) - 4)
    else:
        rows, tokens = 3, 2 * len(base) + len(ext)
    per = st.prefill_by_rid.values()
    assert (len(per), sum(t for t, _ in per), sum(p for _, p in per)) \
        == (rows, tokens, rows * eng.prefill_len)
    P = eng.prefill_len
    assert st.prefill_by_rid == ({0: (8, P), 2: (3, P)} if cached else
                                 {0: (8, P), 1: (8, P), 2: (7, P)})
    names = [e.name for e in st.events]
    syncs = sum(1 for n in names if n.endswith(".sync"))
    assert syncs == st.admitted + names.count("decode") \
        == 3 + names.count("decode")


def test_ring_bound_and_wrap(monkeypatch):
    assert acct.RuntimeStats().events.maxlen == acct.SPAN_RING == 65_536
    monkeypatch.setattr(acct, "SPAN_RING", 8)
    st = acct.RuntimeStats()
    with st.span("tick"):
        for i in range(3):
            with st.span("admit.request", rid=i) as sp:
                st.mark("trace.prefill", rid=i)
            assert sp.rid == i
        assert st.spans_dropped == 0                # 6 recorded, 1 open
    assert st.spans_dropped == 0 and len(st.events) == 7
    assert st.events[-1].name == "tick"
    for _ in range(4):
        st.mark("x")
    assert len(st.events) == 8 and st.spans_dropped == 3
    assert sorted(e.seq for e in st.events) == [0, 3, 5, 6, 7, 8, 9, 10]


def test_retrace_leaves_a_mark_with_its_tick(served):
    """Each trace of a program records ``trace.<program>`` at the tick
    in progress, inside the span that dispatched it."""
    eng = _engine(served)
    eng.submit(_prompts(served[0], (5,))[0], max_new_tokens=12)
    eng.sched_tick()
    eng.sched_tick()
    eng.decode_block = 2                # a new key count: decode retraces
    eng.sched_tick()
    marks = [e for e in eng.stats.events if e.name == "trace.decode"]
    assert [m.tick for m in marks] == [0, 2]
    assert eng.stats.decode_traces == 2
    by_seq = {e.seq: e for e in eng.stats.events}
    assert all(by_seq[m.parent].name == "decode.dispatch" for m in marks)
    [p] = [e for e in eng.stats.events if e.name == "trace.prefill"]
    assert p.tick == 0 and by_seq[p.parent].name == "prefill.dispatch"


@pytest.mark.parametrize("program", ["_prefill_row", "_decode_scan"])
def test_programs_keep_their_names(served, program):
    """The benchmark finds the device time of these programs by module
    name (``prefill_ms``, ``decode_step_ms``, ``decode_roofline``)."""
    eng = _engine(served)
    pool = eng._ensure_pool()
    B, L = eng.n_slots, lm.n_bit_slots(served[0])
    with eng.compute_ctx():
        if program == "_prefill_row":
            wv, av = eng.controller.resolve(jnp.asarray(2.0, jnp.float32))
            low = eng._prefill_row.lower(
                eng.qparams, jnp.zeros((1, eng.prefill_len), jnp.int32),
                jnp.asarray([3], jnp.int32), wv, av)
        else:
            wv, av = eng._batch_bits()
            assert wv.shape == (B, L)
            low = eng._decode_scan.lower(
                eng.qparams, jnp.zeros((B, 1), jnp.int32),
                jnp.zeros((B,), jnp.int32), pool.cache, wv, av,
                jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
                eng._split_key(eng.decode_block))
    assert f"module @jit_{program}" in low.as_text()
