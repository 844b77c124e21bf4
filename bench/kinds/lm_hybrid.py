"""LM serving cells of a Mamba-2 + GQA layer-pattern hybrid (granite-4.0-h):
``ServeEngine`` with continuous batching and per-request precision, as
``bench/kinds/lm.py`` runs a dense decoder, whose window, traffic and
controller this kind reuses.

What differs: the model (``family="mamba_attn"``, the layer pattern from
the config's ``layer_types``, the muP multipliers), the weights
(``bench/weights_hybrid.py``), the plain reference that decides
``correct`` (``bench/reference/granite_hybrid.py``), and the recurrent
state per slot beside the KV ring, which the pool installs at admission
and the decode kernel carries for live rows only.
"""
from __future__ import annotations

import gc
from typing import Dict

import numpy as np

from bench import gen
from bench.harness import expand_menu
from bench.kinds.lm import (Run, build_controller, control_bits,
                            menu_pick, traffic, window)

def pattern(layer_types) -> str:
    """The shortest period of ``layer_types`` as "M"/"A" letters."""
    s = "".join({"mamba": "M", "attention": "A"}[k] for k in layer_types)
    for n in range(1, len(s) + 1):
        if len(s) % n == 0 and s[:n] * (len(s) // n) == s:
            return s[:n]
    return s


def model_dims(config: dict) -> dict:
    """The flat size block the weights, reference and work counts read.
    The published config's keys sit at the top level of the file."""
    c, b = config, config["bench"]
    if c.get("position_embedding_type") != "nope":
        raise ValueError("only position_embedding_type 'nope' is served")
    d, H = c["hidden_size"], c["num_attention_heads"]
    vocab = c["vocab_size"]
    m = {"name": c["name"], "n_layers": c["num_hidden_layers"],
         "layer_types": list(c["layer_types"]), "d_model": d,
         "n_heads": H, "n_kv_heads": c["num_key_value_heads"],
         "head_dim": c.get("head_dim") or d // H,
         "d_ff": c["shared_intermediate_size"], "vocab_size": vocab,
         "padded_vocab": -(-vocab // 512) * 512,
         "norm_eps": c["rms_norm_eps"],
         "ssm_heads": c["mamba_n_heads"], "ssm_head_dim": c["mamba_d_head"],
         "ssm_state": c["mamba_d_state"], "ssm_groups": c["mamba_n_groups"],
         "conv_kernel": c["mamba_d_conv"], "ssm_chunk": c["mamba_chunk_size"],
         "expand": c["mamba_expand"],
         "attention_multiplier": c["attention_multiplier"],
         "embedding_multiplier": c["embedding_multiplier"],
         "residual_multiplier": c["residual_multiplier"],
         "logits_scaling": c["logits_scaling"],
         "out_scale": b["out_scale"], "emb_std": b["emb_std"]}
    if m["ssm_heads"] * m["ssm_head_dim"] != m["expand"] * d:
        raise ValueError("mamba_n_heads * mamba_d_head != expand * d_model")
    return m


def model_config(m: dict):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=m["name"], family="mamba_attn", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], d_ff=m["d_ff"],
        vocab_size=m["vocab_size"], head_dim=m["head_dim"], rope_theta=0.0,
        mlp_type="swiglu", norm_type="rms", norm_eps=m["norm_eps"],
        tie_embeddings=True, remat="none", kv_cache_bits=0,
        ssm_state=m["ssm_state"], ssm_head_dim=m["ssm_head_dim"],
        expand=m["expand"], d_conv=m["conv_kernel"],
        ssm_chunk=m["ssm_chunk"], ssm_groups=m["ssm_groups"],
        conv_bias=True, layer_pattern=pattern(m["layer_types"]),
        attention_multiplier=m["attention_multiplier"],
        embedding_multiplier=m["embedding_multiplier"],
        residual_multiplier=m["residual_multiplier"],
        logits_scaling=m["logits_scaling"])


def check_structure(qparams, cfg) -> None:
    """The bench-made weights have exactly the tree the program's own
    serve-form init would give (shapes and dtypes)."""
    import jax
    from repro.models import lm
    want = jax.eval_shape(lambda k: lm.init_serve_params(cfg, k),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       qparams)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError("bench-made weights do not match the program's "
                           "serve-form tree")


def setup(ctx):
    """Weights, engine and warm-up; returns the engine and its pieces."""
    import jax

    from bench import weights_hybrid
    from repro.serve.engine import ServeEngine

    cfgj, mix = ctx.cell.config, ctx.cell.traffic
    m = model_dims(cfgj)
    sv = cfgj["serve"]
    cfg = model_config(m)
    qp = weights_hybrid.serve_params(m, ctx.seed)
    check_structure(qp, cfg)
    eng = ServeEngine(cfg, qp, max_len=sv["max_len"],
                      controller=build_controller(mix["menu"], m["n_layers"]),
                      n_slots=sv["n_slots"], prefill_len=sv["prefill_len"],
                      decode_block=sv["decode_block"], seed=ctx.seed)
    # warm-up: the window's programs, through the window's calls
    wrng = gen.rng_for(ctx.seed, 40)
    for b in mix["budgets"]:
        eng.submit(wrng.integers(0, m["vocab_size"], 16, dtype=np.int32),
                   max_new_tokens=sv["decode_block"] + 1, budget_s=b)
    while eng.queued or eng._has_active():
        eng.sched_tick()
    jax.block_until_ready(eng.pool.cache)
    return eng, qp, m, sv


def run(ctx):
    eng, qp, m, sv = setup(ctx)
    mix = ctx.cell.traffic
    out = Run(ctx.peaks)
    out.m = m
    reqs = traffic(mix, m, ctx.seconds, ctx.seed)
    window(ctx, eng, reqs, mix, sv, ctx.seconds, out)
    served = {r["rid"]: list(eng.requests[r["rid"]].tokens)
              for r in out.requests if r["done"]}
    mean_wbits = {r["rid"]: eng.requests[r["rid"]].mean_wbits
                  for r in out.requests if r["done"]}
    del eng
    gc.collect()
    out.compare_args = (m, qp, mix, out.requests, served, mean_wbits,
                        sv["max_len"])
    out.checks = check(ctx, *out.compare_args)
    out.correct = all(v <= lim for v, lim in out.checks.values())
    return out


def compare(ctx, m, qp, mix, requests, served, mean_wbits, max_len,
            with_control: bool = False) -> dict:
    """The reference over a seed-drawn sample of the finished requests
    (the longest among them): ``bench/kinds/lm.py``'s readings, from
    ``bench/reference/granite_hybrid.py``."""
    from bench.reference import granite_hybrid as ref
    limits = ctx.cell.config["check"]
    bits = expand_menu(mix["menu"], m["n_layers"])
    done = [r for r in requests if r["done"]]
    out: Dict = {"unfinished": len(requests) - len(done),
                 "wrong_precision": 0, "short_or_invalid": 0,
                 "widest_gap": 0.0, "tokens": 0,
                 "control_widest_gap": 0.0 if with_control else None,
                 "by_config": {}}
    if not done:
        out["widest_gap"] = 1e9
        return out
    longest = max(range(len(done)), key=lambda i: len(served[done[i]["rid"]]))
    for i in gen.pick(len(done), limits["sample"], ctx.seed, must=longest):
        r = done[i]
        name = menu_pick(mix["menu"], r["budget"])
        wb, ab = bits[name]
        toks = served[r["rid"]]
        if abs(mean_wbits[r["rid"]] - float(np.mean(wb))) > 1e-9:
            out["wrong_precision"] += 1
        if len(toks) != r["max_new"] or not all(
                0 <= t < m["vocab_size"] for t in toks):
            out["short_or_invalid"] += 1
            continue
        g = ref.token_gaps(qp, m, r["prompt"], toks, wb, ab, max_len)
        out["widest_gap"] = max(out["widest_gap"], float(np.max(g)))
        out["tokens"] += len(toks)
        row = out["by_config"].setdefault(name, [0.0, 0.0, 0, 0.0, 0.0])
        row[0] = max(row[0], float(np.max(g)))
        row[1] += float(np.sum(g))
        row[2] += len(toks)
        if with_control:
            lw, la = control_bits(wb, ab, limits["control"])
            c = ref.control_gaps(qp, m, r["prompt"], toks, wb, ab, lw, la,
                                 max_len)
            out["control_widest_gap"] = max(out["control_widest_gap"],
                                            float(np.max(c)))
            row[3] = max(row[3], float(np.max(c)))
            row[4] += float(np.sum(c))
    ctx.log(f"reference: {min(limits['sample'], len(done))} requests, "
            f"{out['tokens']} served tokens")
    return out


def check(ctx, m, qp, mix, requests, served, mean_wbits, max_len):
    r = compare(ctx, m, qp, mix, requests, served, mean_wbits, max_len)
    lim = float(mix["check"]["widest_gap"])
    return {"unfinished": (float(r["unfinished"]), 0.0),
            "wrong_precision": (float(r["wrong_precision"]), 0.0),
            "short_or_invalid": (float(r["short_or_invalid"]), 0.0),
            "widest_gap": (r["widest_gap"], lim)}
