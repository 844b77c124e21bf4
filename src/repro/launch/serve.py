"""Serving driver: load/initialize a model, quantize, and serve requests
with runtime latency budgets (dynamic bit fluidity).

Two modes:

  * ``--continuous`` (default): the continuous-batching engine — every
    request carries its OWN budget (cycled from ``--budgets``) and streams
    through a persistent slot pool; one compiled prefill + one compiled
    decode serve all precision mixes.

      PYTHONPATH=src python -m repro.launch.serve --arch qwen3_4b --smoke \\
          --requests 8 --steps 16 --budgets 2.0 0.75 0.5

  * ``--batch``: the legacy whole-batch path (one budget per batch);
    kept for A/B comparison and the paper's §V.B batch-switch story.

``--slo-edp <J*s>`` (continuous mode) swaps the open-loop controller
for a closed-loop :class:`repro.core.policy.FluidController`: every
admission's priced AP cost is charged against the system-level EDP SLO
window and later requests resolve from the REMAINING budget — the
paper's dynamic switching as a live control loop (DESIGN.md §8).

With ``--ckpt-dir`` it restores trained weights (from launch/train.py)
before quantizing — train -> checkpoint -> quantized bit-fluid serving is
the full production path.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import policy as pol
from repro.data.pipeline import make_batch
from repro.models import lm
from repro.serve import aggregate, predict_table
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.engine import ServeEngine
from repro.train.checkpoint import latest_step, restore_checkpoint


def default_controller(n: int) -> pol.BudgetController:
    return pol.BudgetController(
        {"int4": pol.fixed(4), "mixed": pol.per_layer([8, 4], name="mixed"),
         "int8": pol.fixed(8)},
        {"int4": 0.5, "mixed": 0.75, "int8": 1.0}, n)


def fluid_controller(cfg, n: int, args) -> pol.FluidController:
    """Closed-loop controller for --slo-edp: the same three configs, but
    predicted at their PRICED per-request AP EDP, charged against a
    system-level SLO window the size of the request stream."""
    base = default_controller(n)
    preds = predict_table(
        lm.layer_gemm_dims(cfg), base.configs, axis="edp",
        units=args.prompt_len + args.steps,     # planned tokens/request
        head=lm.head_gemm_dims(cfg))
    return pol.FluidController(base.configs, preds, n, budget_axis="edp",
                               slo=args.slo_edp, window=args.requests)


def load_serve_params(cfg, ckpt_dir: str = "", seed: int = 0) -> dict:
    """Serve-form params: trained weights from ``ckpt_dir`` when it holds
    a checkpoint, else random weights from ``seed``.  Neither path keeps
    the bf16 train-form tree once the int8 containers exist: a fresh
    model is initialized straight into serve form, and a restored tree
    is dropped as soon as it is quantized."""
    if ckpt_dir and latest_step(ckpt_dir) is not None:
        target = jax.eval_shape(lambda k: lm.init_params(cfg, k),
                                jax.random.PRNGKey(seed))
        restored, step = restore_checkpoint(ckpt_dir, {"params": target})
        print(f"[serve] restored weights from step {step}")
        return lm.quantize_params(restored.pop("params"), cfg)
    return lm.init_serve_params(cfg, jax.random.PRNGKey(seed))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3_4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching mode (the default)")
    ap.add_argument("--batch", action="store_true",
                    help="legacy whole-batch mode (one budget per batch)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--decode-block", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--budgets", type=float, nargs="+", default=None,
                    help="per-request latency budgets, cycled over the "
                         "stream (default: 2.0 0.5)")
    ap.add_argument("--slo-edp", type=float, default=0.0,
                    help="closed-loop mode: total modeled AP EDP budget "
                         "(J*s) for the whole request stream (0 = open "
                         "loop; continuous mode only)")
    ap.add_argument("--kv-bits", type=int, default=0, choices=(0, 8))
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()
    if args.continuous and args.batch:
        ap.error("--continuous and --batch are mutually exclusive")
    if args.slo_edp and args.batch:
        ap.error("--slo-edp needs the continuous scheduler")
    if args.slo_edp and args.budgets is not None:
        ap.error("--budgets are latency budgets; with --slo-edp the EDP "
                 "SLO window drives precision — omit --budgets")
    if args.budgets is None:
        args.budgets = [2.0, 0.5]

    enable_compile_cache()
    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    if args.kv_bits:
        cfg = cfg.with_(kv_cache_bits=args.kv_bits)
    qparams = load_serve_params(cfg, args.ckpt_dir)

    n = lm.n_bit_slots(cfg)
    if args.batch:
        _serve_batches(cfg, qparams, default_controller(n), args)
    elif args.slo_edp:
        _serve_continuous(cfg, qparams, fluid_controller(cfg, n, args), args)
    else:
        _serve_continuous(cfg, qparams, default_controller(n), args)


def _serve_continuous(cfg, qparams, ctrl, args) -> None:
    closed = isinstance(ctrl, pol.FluidController)
    eng = ServeEngine(cfg, qparams, max_len=args.max_len, controller=ctrl,
                      n_slots=args.n_slots, prefill_len=args.prompt_len,
                      decode_block=args.decode_block)
    t0 = time.time()
    rids = []
    for i in range(args.requests):
        prompt = make_batch(7, i, 1, args.prompt_len,
                            cfg.vocab_size)["tokens"][0]
        rids.append(eng.submit(
            np.asarray(prompt), max_new_tokens=args.steps,
            # closed loop: the SLO window picks precision, not requests
            budget_s=(None if closed
                      else args.budgets[i % len(args.budgets)]),
            temperature=args.temperature, top_k=args.top_k))
    res = eng.run()
    dt = time.time() - t0
    for rid in rids:
        st = res[rid]
        print(f"[serve] req{rid}: budget={st.budget_s:.3g} -> "
              f"{st.mean_wbits:.1f} mean wbits, {st.n_tokens} tokens "
              f"(slot {st.slot}, {st.finished_s - st.submitted_s:.2f}s, "
              f"AP {st.ap_latency_s * 1e3:.2f}ms / "
              f"{st.ap_energy_j * 1e3:.2f}mJ, EDP {st.edp:.3e} J·s)")
    print(f"[serve] {eng.stats.tokens} tokens in {dt:.2f}s "
          f"({eng.stats.tokens / dt:.1f} tok/s) across "
          f"{args.requests} requests on {args.n_slots} slots")
    if closed:
        agg = aggregate(res.values())
        print(f"[serve] closed loop: spent {agg['edp']:.3e} of "
              f"{ctrl.slo:.3e} J·s EDP SLO ({agg['edp'] / ctrl.slo:.2f}x) "
              f"over {agg['requests']} admissions")
    print(f"[serve] compiled programs: prefill={eng.stats.prefill_traces} "
          f"decode={eng.stats.decode_traces} (fluid across "
          f"{1 if closed else len(set(args.budgets))} budget levels, "
          f"{eng.stats.admitted} admissions)")


def _serve_batches(cfg, qparams, ctrl, args) -> None:
    eng = ServeEngine(cfg, qparams, max_len=args.max_len, controller=ctrl)
    for bi, budget in enumerate(args.budgets):
        eng.set_budget(budget)
        batch = {"tokens": make_batch(7, bi, args.requests, args.prompt_len,
                                      cfg.vocab_size)["tokens"]}
        t0 = time.time()
        eng.generate(batch, steps=args.steps)
        dt = time.time() - t0
        wv, _ = ctrl.resolve(jnp.asarray(budget))
        cost = eng.price_budget(budget)
        print(f"[serve] budget={budget}: mean_bits="
              f"{float(np.mean(np.asarray(wv))):.1f} "
              f"{args.requests * args.steps} tokens in {dt:.2f}s "
              f"({args.requests * args.steps / dt:.1f} tok/s; AP "
              f"{cost.cycles:.0f} cy/tok, {cost.energy_j * 1e3:.3f} mJ/tok)")
    print(f"[serve] compiled programs: prefill={eng.stats.prefill_traces} "
          f"decode={eng.stats.decode_traces} (fluid across "
          f"{len(args.budgets)} budgets)")


if __name__ == "__main__":
    main()
