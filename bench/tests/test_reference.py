"""The plain references against the program at smoke widths on the CPU:
same bench-made weights, same per-row bits, logits compared directly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights
from bench.kinds import cnn as cnn_kind
from bench.kinds import lm as lm_kind
from bench.reference import cnn as rcnn
from bench.reference import lm as rlm
from bench.tests import smoke


@pytest.mark.parametrize("P", [20, 32])
def test_lm_prefill_and_decode_match(P):
    """W8A8 rows, prompt padded in its prefill row (P=20) or filling it.

    W4A4 rows are not compared here: the program's compiled arithmetic
    keeps some bf16 intermediates in float32, and a padded row's
    activations enter the prompt's shared activation scale, and at 4-bit
    activations either moves values by whole quantization steps (PERF.md,
    Open questions)."""
    from repro.kernels import ops
    from repro.models import lm

    m = lm_kind.model_dims(smoke.lm_config(layers=3))
    cfg = lm_kind.model_config(m)
    qp = weights.lm_serve_params(m, 3_000_000_007)
    wb, ab = harness.expand_menu(smoke.load("traffic", "poisson_int8")["menu"],
                              m["n_layers"])["int8"]
    T = P + 6
    toks = np.random.default_rng(0).integers(0, m["vocab_size"], T)
    wv, av = jnp.asarray(wb), jnp.asarray(ab)
    with ops.bit_families((4, 8)):
        pad = np.zeros((1, 32), np.int32)
        pad[0, :P] = toks[:P]
        logits, cache = lm.prefill(qp, {"tokens": jnp.asarray(pad)}, cfg, wv,
                                   av, lm.empty_cache(cfg, 1, 64),
                                   lengths=jnp.asarray([P]))
        got = [np.asarray(logits)[0, -1]]
        for t in range(P, T):       # decode through the cache, per-row bits
            lg, cache = lm.decode_step(qp, jnp.asarray([[toks[t]]]),
                                       jnp.asarray([t]), cache, cfg,
                                       wv[None], av[None])
            got.append(np.asarray(lg)[0, -1])
    seq = np.zeros((64,), np.int32)
    seq[:T] = toks
    ref = np.asarray(rlm.forward(qp, m, jnp.asarray(seq), P, T, wv, av))
    ref = ref[P - 1:T]
    err = np.max(np.abs(np.stack(got)[:, :m["vocab_size"]] - ref), axis=1)
    # bf16 storage and op order move single logits by ~0.1 sd at most
    assert np.all(err / ref.std(axis=1) < 0.3), err


def test_cnn_forward_matches():
    from repro.core import policy as pol
    from repro.serve.cnn import CNNServeEngine

    c = smoke.cnn_config(32)
    menu = smoke.load("traffic", "hawq_batches")["menu"]
    bits = harness.expand_menu(menu, 21)
    params = weights.cnn_params(c["layers"], 5)
    layers = cnn_kind.program_layers(c["layers"])
    ctrl = pol.BudgetController(
        {k: pol.per_layer(w, a, name=k) for k, (w, a) in bits.items()},
        {k: float(i) for i, k in enumerate(sorted(bits))}, 21,
        budget_axis="edp")
    eng = CNNServeEngine(params, layers, controller=ctrl, max_batch=5)
    names = sorted(bits)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 32, 32, 3))
    got, stats = eng.serve(x, [float(i) for i in range(5)])
    wb = np.asarray([bits[n][0] for n in names])
    ab = np.asarray([bits[n][1] for n in names])
    assert [list(s.wbits) for s in stats] == wb.tolist()
    ref = np.asarray(rcnn.logits(params, c["layers"], x, wb, ab))
    # bit for bit at every configuration, W4A4 layers included
    assert np.array_equal(np.asarray(got, np.float32), ref)
