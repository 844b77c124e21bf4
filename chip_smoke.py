#!/usr/bin/env python3
"""Smoke test of the bit-fluid serving path on a TPU.

Drives the main serving path once, through the entry points a user calls,
at published widths with random weights from seed 0:

  * LM server: qwen3-4b (36 layers, d_model 2560, GQA 32/8, d_ff 9728,
    vocab 151936) in int8 containers behind ``ServeEngine`` with the serve
    CLI's three-config controller (int4 / mixed / int8).  8 requests of 128
    prompt and 32 new tokens on 4 slots (``max_len`` 512), budgets cycling
    2.0 / 0.75 / 0.5 so that one decode batch holds all three precisions.
  * CNN server: ResNet18 at 224x224 (the paper's HAWQ-V3 workload) behind
    ``CNNServeEngine`` with ``max_batch`` 8; one batch whose per-image EDP
    budgets pick different HAWQ-V3 configurations.

Each phase checks its outputs, that every step program compiled exactly
once, and that the Pallas kernels agree with the XLA reference on the same
chip: int32 accumulators bit for bit, logits within ``LOGIT_TOL``.

    python3 chip_smoke.py               # one chip: the two phases above
    python3 chip_smoke.py --chips 4     # four chips: the scale-out phase only

``--chips 4`` serves the same LM requests and CNN images on one device and
on a four-device data mesh with ``plan="auto"`` (weights replicated,
request rows split across chips), four times the smoke load so that each
chip holds the one-device shapes, and requires identical greedy tokens and
identical logits.

The script refuses to run without a TPU, with a test-only Pallas switch
(``REPRO_PALLAS``), or away from the repository.  Its last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failed check exits non-zero before that line is printed.  Wall times it
prints are smoke timings of one cold run, compilation included, not
benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

LOGIT_TOL = 1e-2            # Pallas vs XLA ref: max|d| <= LOGIT_TOL * max|ref|
PEAK_LIMIT = 14 * 2 ** 30   # LM-phase HBM peak must leave 2 GiB of 16 free

# the LM workload (one chip and the scale-out phase alike)
LM_ARCH = "qwen3_4b"
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 8, 128, 32
N_SLOTS, MAX_LEN = 4, 512
BUDGETS = (2.0, 0.75, 0.5)                  # -> int8, mixed, int4
CNN_BATCH, CNN_IMAGE = 8, 224


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileClock:
    """Seconds the XLA backend spends compiling, summed from jax's own
    monitoring events (tracing and lowering are not counted: nested
    traces report overlapping durations)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.total = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.total += duration


# ---------------------------------------------------------------------------
# Pallas vs XLA reference
# ---------------------------------------------------------------------------

def _reference(fn):
    """Run ``fn`` with every dispatcher forced onto the XLA reference."""
    from repro.kernels import ops
    ops.set_force_pallas(False)
    try:
        return fn()
    finally:
        ops.set_force_pallas(None)


def _fresh_jit(fn):
    """A new jit wrapper around a new function object, so the dispatch
    flags are read by a fresh trace."""
    import jax
    return jax.jit(lambda *a: fn(*a))


def compare_logits(name: str, got, ref) -> dict:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(np.isfinite(got).all() and np.isfinite(ref).all(),
          f"{name}: non-finite logits")
    rel = float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))
    same_argmax = bool((got.argmax(-1) == ref.argmax(-1)).all())
    log(f"{name}: Pallas vs XLA ref max|d|/max|ref| = {rel!r} "
        f"(tolerance {LOGIT_TOL}), argmax equal: {same_argmax}")
    check(rel <= LOGIT_TOL, f"{name}: Pallas and XLA ref disagree "
                            f"({rel!r} > {LOGIT_TOL})")
    return {"rel_max_abs_diff": rel, "argmax_equal": same_argmax}


def exact_accumulators(name: str, x_q, w_q, planes=(8, 4)) -> dict:
    """int8 x int8 -> int32 through the Pallas kernels and the XLA ref at
    each plane count (and the packed-int4 kernel at 4 bits); every
    accumulator must match bit for bit."""
    import jax.numpy as jnp

    from repro.core import bitfluid as bf
    from repro.kernels import ops

    out = {}
    for p in planes:
        w = w_q if p == 8 else bf.requant_shift(w_q, p)
        got = np.asarray(ops.bitplane_matmul(x_q, w, n_planes=p))
        ref = np.asarray(_reference(
            lambda: ops.bitplane_matmul(x_q, w, n_planes=p)))
        out[f"bitplane_{p}"] = bool(np.array_equal(got, ref))
    packed = bf.pack_int4_halves(bf.requant_shift(w_q, 4))
    ones = jnp.ones((1, w_q.shape[1]), jnp.float32)
    got = np.asarray(ops.int4_matmul(x_q, packed, ones))
    ref = np.asarray(_reference(lambda: ops.int4_matmul(x_q, packed, ones)))
    out["int4"] = bool(np.array_equal(got, ref))
    log(f"{name}: int32 accumulators Pallas == XLA ref "
        f"(M={x_q.shape[0]}, K={w_q.shape[0]}, N={w_q.shape[1]}): {out}")
    check(all(out.values()), f"{name}: accumulators differ: {out}")
    return out


# ---------------------------------------------------------------------------
# LM server
# ---------------------------------------------------------------------------

def lm_config():
    from repro import configs
    cfg = configs.get(LM_ARCH)
    check((cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
           cfg.vocab_size) == (36, 2560, 32, 8, 9728, 151936),
          f"{LM_ARCH} is not at its published widths: {cfg}")
    return cfg


def smoke_prompt(cfg, i: int, prompt_len: int = PROMPT_LEN):
    """The i-th smoke prompt (the serve CLI's synthetic prompt stream)."""
    from repro.data.pipeline import make_batch
    return make_batch(7, i, 1, prompt_len, cfg.vocab_size)["tokens"][0]


def serve_lm(cfg, qparams, *, mesh=None, plan=None, n_requests=N_REQUESTS,
             prompt_len=PROMPT_LEN, new_tokens=NEW_TOKENS, n_slots=N_SLOTS,
             max_len=MAX_LEN, budgets=BUDGETS):
    """Serve the smoke requests; returns (engine, [rid], seconds).

    The first ``len(budgets)`` requests arrive at tick 0 and the rest at
    tick 1, so the first decode batch holds one row per configuration
    (the admission scheduler would otherwise admit the cheapest first)."""
    from repro.launch.serve import default_controller
    from repro.models import lm
    from repro.serve.engine import ServeEngine

    eng = ServeEngine(cfg, qparams, max_len=max_len,
                      controller=default_controller(lm.n_bit_slots(cfg)),
                      n_slots=n_slots, prefill_len=prompt_len, mesh=mesh,
                      plan=plan)
    rids = []

    def arrival(i):
        rids.append(eng.submit(np.asarray(smoke_prompt(cfg, i, prompt_len)),
                               max_new_tokens=new_tokens,
                               budget_s=budgets[i % len(budgets)]))

    for i in range(n_requests):
        eng.submit_at(0 if i < len(budgets) else 1,
                      lambda i=i: arrival(i))
    t0 = time.time()
    eng.run()
    return eng, rids, time.time() - t0


def check_lm_served(eng, rids, cfg, new_tokens=NEW_TOKENS) -> dict:
    res = eng.requests
    for rid in rids:
        toks = res[rid].tokens
        check(len(toks) == new_tokens,
              f"request {rid} returned {len(toks)} of {new_tokens} tokens")
        check(all(0 <= t < cfg.vocab_size for t in toks),
              f"request {rid} returned out-of-vocabulary tokens")
    first = sorted({res[r].mean_wbits for r in rids
                    if res[r].admitted_tick == 0})
    check(len(first) == 3, f"first batch holds mean weight bits {first}, "
                           f"not the three configurations")
    traces = (eng.stats.prefill_traces, eng.stats.decode_traces)
    check(traces == (1, 1), f"prefill/decode compiled {traces} times")
    return {"first_batch_mean_wbits": first,
            "mean_wbits": [res[r].mean_wbits for r in rids],
            "prefill_traces": traces[0], "decode_traces": traces[1]}


def lm_phase(clock: CompileClock) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.models import lm

    cfg = lm_config()
    c0, t0 = clock.total, time.time()
    qparams = jax.block_until_ready(
        lm.init_serve_params(cfg, jax.random.PRNGKey(0)))
    log(f"LM: {LM_ARCH} serve-form params from seed 0 in "
        f"{time.time() - t0:.1f}s (smoke timing)")
    eng, rids, secs = serve_lm(cfg, qparams)
    out = check_lm_served(eng, rids, cfg)
    log(f"LM: {len(rids)} requests x {NEW_TOKENS} tokens served in "
        f"{secs:.1f}s (smoke timing, compile included); first batch mean "
        f"wbits {out['first_batch_mean_wbits']}; traces prefill="
        f"{out['prefill_traces']} decode={out['decode_traces']}")

    # one mixed-precision prefill through the Pallas dispatch and the ref
    wv, av = eng.controller.resolve(jnp.asarray([BUDGETS[1]], jnp.float32))
    tokens = jnp.asarray(np.asarray(smoke_prompt(cfg, 0))[None], jnp.int32)
    length = jnp.asarray([PROMPT_LEN], jnp.int32)

    def prefill(q, tok, ln, w, a):
        cache = lm.empty_cache(cfg, 1, MAX_LEN)
        return lm.prefill(q, {"tokens": tok}, cfg, w, a, cache,
                          lengths=ln)[0]

    with eng.compute_ctx():
        got = _fresh_jit(prefill)(qparams, tokens, length, wv, av)
        ref = _reference(lambda: _fresh_jit(prefill)(qparams, tokens,
                                                     length, wv, av))
    out["prefill_logits"] = compare_logits("LM prefill (mixed bits)", got,
                                           ref)
    rng = np.random.default_rng(0)
    w_up = qparams["layers"]["mlp"]["wu"]["q"][0]               # (2560, 9728)
    for m in (PROMPT_LEN, 8):                              # prefill, decode
        x = jnp.asarray(rng.integers(-127, 128, (m, cfg.d_model)), jnp.int8)
        out[f"exact_m{m}"] = exact_accumulators(f"LM MLP up-proj M={m}",
                                                x, w_up)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    out["peak_bytes_in_use"] = peak
    out["bytes_limit"] = stats.get("bytes_limit")
    out["compile_s"] = clock.total - c0
    out["wall_s"] = time.time() - t0
    log(f"LM: peak_bytes_in_use={peak} bytes_limit={out['bytes_limit']}; "
        f"compile {out['compile_s']:.1f}s of {out['wall_s']:.1f}s wall "
        f"(smoke timing)")
    check(peak is not None, "the device reports no peak_bytes_in_use")
    check(peak <= PEAK_LIMIT, f"LM peak {peak} bytes leaves under 2 GiB")
    return out


# ---------------------------------------------------------------------------
# CNN server
# ---------------------------------------------------------------------------

def cnn_setup(image: int = CNN_IMAGE, batch: int = CNN_BATCH):
    """ResNet18 params, layers, controller, images and per-image budgets
    that cycle through every HAWQ-V3 configuration."""
    import jax

    from repro.core import policy as pol
    from repro.models import cnn

    params, layers = cnn.init_cnn("resnet18", jax.random.PRNGKey(0),
                                  image=0 if image == 224 else image)
    ctrl = pol.cnn_budget_controller("resnet18", layers=layers)
    order = ctrl.order()
    budgets = [ctrl.predicted_latency_s[order[i % len(order)]] * 1.01
               for i in range(batch)]
    images = jax.random.normal(jax.random.PRNGKey(1),
                               (batch, image, image, 3))
    return params, layers, ctrl, images, budgets


def cnn_phase(clock: CompileClock) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.models import cnn
    from repro.serve.cnn import CNNServeEngine

    c0, t0 = clock.total, time.time()
    params, layers, ctrl, images, budgets = cnn_setup()
    eng = CNNServeEngine(params, layers, controller=ctrl,
                         max_batch=CNN_BATCH)
    logits, stats = eng.serve(images, budgets)
    configs = sorted({s.wbits for s in stats})
    check(logits.shape == (CNN_BATCH, 1000),
          f"CNN logits shape {logits.shape}")
    check(np.isfinite(logits).all(), "CNN logits are not finite")
    check(len(configs) >= 3, f"only {len(configs)} HAWQ-V3 configurations "
                             f"share the batch")
    # a second batch with the budgets reversed: same program
    eng.serve(images[::-1], budgets[::-1])
    check(eng.stats.forward_traces == 1,
          f"CNN forward compiled {eng.stats.forward_traces} times")
    log(f"CNN: ResNet18@{CNN_IMAGE} batch {CNN_BATCH}, {len(configs)} "
        f"HAWQ-V3 configurations in one batch, forward_traces="
        f"{eng.stats.forward_traces}, mean wbits "
        f"{[round(s.mean_wbits, 3) for s in stats]}")
    out = {"configs_in_batch": len(configs),
           "forward_traces": eng.stats.forward_traces}

    wmat = jnp.asarray([s.wbits for s in stats], jnp.int32)
    amat = jnp.asarray([s.abits for s in stats], jnp.int32)

    def forward(qp, x, w, a):
        return cnn.cnn_forward(qp, x, layers, w, a)

    with eng.compute_ctx():
        ref = _reference(lambda: _fresh_jit(forward)(eng.qparams, images,
                                                     wmat, amat))
    out["logits"] = compare_logits("CNN forward (mixed HAWQ-V3)", logits,
                                   ref)
    # the largest ungrouped GEMM whose width packs into 128-lane int4 blocks
    name = max((n for n, p in eng.qparams.items()
                if isinstance(p, dict) and getattr(p.get("q"), "ndim", 0)
                == 2 and p["q"].shape[1] % 256 == 0),
               key=lambda n: eng.qparams[n]["q"].size)
    w = eng.qparams[name]["q"]
    x = jnp.asarray(np.random.default_rng(1).integers(
        -127, 128, (CNN_BATCH * 49, w.shape[0])), jnp.int8)
    out["exact"] = exact_accumulators(f"CNN conv GEMM {name}", x, w)
    out["compile_s"] = clock.total - c0
    out["wall_s"] = time.time() - t0
    log(f"CNN: compile {out['compile_s']:.1f}s of {out['wall_s']:.1f}s wall "
        f"(smoke timing)")
    return out


# ---------------------------------------------------------------------------
# Scale-out: one device vs a four-device data mesh
# ---------------------------------------------------------------------------

def first_divergence(a, b):
    """Index of the first differing token of two sequences (None if equal)."""
    return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                None if len(a) == len(b) else min(len(a), len(b)))


def scaleout_phase(clock: CompileClock, n_devices: int = 4) -> dict:
    """One device against ``n_devices`` serving ``n_devices`` times the
    work, so that each chip runs the one-device program at its shapes
    (``N_SLOTS`` decode rows, ``CNN_BATCH`` images).  XLA's float rounding
    on the TPU depends on the local batch size, so only equal per-chip
    shapes make a bit-for-bit comparison meaningful."""
    import jax
    from jax.sharding import Mesh

    from repro.models import lm
    from repro.serve.cnn import CNNServeEngine

    devs = jax.devices()
    check(len(devs) >= n_devices, f"{len(devs)} devices, need {n_devices}")
    mesh = Mesh(np.asarray(devs[:n_devices]), ("data",))
    c0, t0 = clock.total, time.time()

    cfg = lm_config()
    qparams = jax.block_until_ready(
        lm.init_serve_params(cfg, jax.random.PRNGKey(0)))
    n_req = N_REQUESTS * n_devices
    one, rids_1, s1 = serve_lm(cfg, qparams, n_requests=n_req)
    many, rids_n, sn = serve_lm(cfg, qparams, mesh=mesh, plan="auto",
                                n_requests=n_req,
                                n_slots=N_SLOTS * n_devices)
    check(many.plan is not None and many.plan.fully_replicated
          and many.plan.dp == n_devices,
          f"LM plan on {n_devices} devices: {many.plan}")
    check_lm_served(one, rids_1, cfg)
    check_lm_served(many, rids_n, cfg)
    diff = {r1: first_divergence(one.requests[r1].tokens,
                                 many.requests[rn].tokens)
            for r1, rn in zip(rids_1, rids_n)}
    diff = {r: i for r, i in diff.items() if i is not None}
    log(f"scale-out LM: {n_req} requests, 1 device x {N_SLOTS} slots "
        f"{s1:.1f}s, {n_devices} devices x {N_SLOTS} slots {sn:.1f}s "
        f"(smoke timings); first differing token by request: {diff}")
    check(not diff, f"greedy tokens differ between 1 and {n_devices} "
                    f"devices (request: first differing token): {diff}")
    del one, many, qparams

    n_img = CNN_BATCH * n_devices
    params, layers, ctrl, images, budgets = cnn_setup(batch=n_img)
    c_one = CNNServeEngine(params, layers, controller=ctrl,
                           max_batch=CNN_BATCH)
    c_many = CNNServeEngine(params, layers, controller=ctrl,
                            max_batch=n_img, mesh=mesh, plan="auto")
    check(c_many.plan is not None and c_many.plan.fully_replicated,
          f"CNN plan on {n_devices} devices: {c_many.plan}")
    l1 = np.concatenate([
        c_one.serve(images[i:i + CNN_BATCH], budgets[i:i + CNN_BATCH])[0]
        for i in range(0, n_img, CNN_BATCH)])
    ln, _ = c_many.serve(images, budgets)
    max_diff = float(np.max(np.abs(l1 - ln)))
    log(f"scale-out CNN: max|logits(1) - logits({n_devices})| = "
        f"{max_diff!r}; forward_traces {c_one.stats.forward_traces}/"
        f"{c_many.stats.forward_traces}")
    check(np.array_equal(l1, ln), f"CNN logits differ between 1 and "
                                  f"{n_devices} devices ({max_diff!r})")
    check((c_one.stats.forward_traces, c_many.stats.forward_traces)
          == (1, 1), "CNN forward retraced")
    out = {"lm_tokens_identical": True, "cnn_logits_identical": True,
           "compile_s": clock.total - c0, "wall_s": time.time() - t0}
    log(f"scale-out: compile {out['compile_s']:.1f}s of {out['wall_s']:.1f}s "
        f"wall (smoke timing)")
    return out


# ---------------------------------------------------------------------------

def _refuse(msg: str) -> None:
    print(f"[smoke] refused: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: LM and CNN phases; 4: the scale-out phase only")
    ap.add_argument("--out", default="",
                    help="also write the phase results to this JSON file")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        _refuse(f"no repro package under {SRC}; run from the repository")
    sys.path.insert(0, SRC)
    import jax

    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    if ops.pallas_overrides():
        _refuse(f"test-only Pallas switches are set: "
                f"{ops.pallas_overrides()}")
    if jax.default_backend() != "tpu" or not ops.use_pallas():
        _refuse(f"jax found no TPU (default backend "
                f"{jax.default_backend()!r}); the Pallas path is not in "
                f"force")
    cache = enable_compile_cache()
    devs = jax.devices()
    log(f"jax {jax.__version__}, {len(devs)} x {devs[0].device_kind} "
        f"({devs[0].platform}), compile cache {cache}")

    clock = CompileClock()
    results = {}
    if args.chips == 4:
        results["scaleout"] = scaleout_phase(clock)
    else:
        results["lm"] = lm_phase(clock)
        results["cnn"] = cnn_phase(clock)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": device, "phases": results}, f, indent=1,
                      default=str)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
