"""LM serving cells: ``ServeEngine`` (continuous batching,
per-request precision) under an open-loop or backlog mix.

Set-up makes the weights from the seed, builds the engine with the mix's
precision menu, and warms every program the window drives by serving a
few requests through the same entry points.  The window submits each
request when it is due and pumps ``sched_tick()`` (the runtime tick that
``run()`` drives: admission, prefill, one decode block); a request's
token times are the wall times at which the tick that delivered them
returned.  Requests that arrived in the window are drained after it, up
to ``drain_s``; what is unfinished then has failed.  With ``--trace 1``
the last ``trace_s`` seconds of the window are profiled.

After the window the pool is freed and a seed-drawn sample of finished
requests (with the longest among them) is run through the plain
reference: the number compared is the widest gap by which a served
token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import tempfile
import time
from typing import Dict, List

import numpy as np

from bench import gen
from bench.harness import expand_menu, memory_peak_bytes, reduce_trace


def model_config(m: dict):
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=m["name"], family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], d_ff=m["d_ff"],
        vocab_size=m["vocab_size"], head_dim=m["head_dim"], qk_norm=True,
        rope_theta=m["rope_theta"], mlp_type="swiglu", norm_type="rms",
        norm_eps=m["norm_eps"], tie_embeddings=True, remat="none",
        kv_cache_bits=0)


def model_dims(config: dict) -> dict:
    """The flat size block the weights, reference and work counts read."""
    hf, b = config["hf_config"], config["bench"]
    vocab = hf["vocab_size"]
    return {"name": config["name"], "n_layers": hf["num_hidden_layers"],
            "d_model": hf["hidden_size"],
            "n_heads": hf["num_attention_heads"],
            "n_kv_heads": hf["num_key_value_heads"],
            "head_dim": hf["head_dim"], "d_ff": hf["intermediate_size"],
            "vocab_size": vocab, "padded_vocab": -(-vocab // 512) * 512,
            "norm_eps": hf["rms_norm_eps"], "rope_theta": hf["rope_theta"],
            "out_scale": b["out_scale"], "emb_std": b["emb_std"]}


def menu_pick(menu: dict, budget: float) -> str:
    """The configuration a budget selects: the most costly one whose
    predicted cost fits, else the cheapest (the menu's stated rule)."""
    order = sorted(menu, key=lambda k: menu[k]["predicted"])
    fits = [k for k in order if menu[k]["predicted"] <= budget]
    return fits[-1] if fits else order[0]


def build_controller(menu: dict, n_layers: int):
    from repro.core import policy as pol
    confs = {k: pol.per_layer(v["wbits"], v["abits"], name=k)
             for k, v in menu.items()}
    return pol.BudgetController(
        confs, {k: float(v["predicted"]) for k, v in menu.items()}, n_layers)


def check_structure(qparams, cfg) -> None:
    """The bench-made weights have exactly the tree the program's own
    serve-form init would give (shapes and dtypes; no values)."""
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


class Run:
    """What the metric readers see."""

    def __init__(self, peaks):
        self.kind = "lm"
        self.peaks = peaks
        self.requests: List[dict] = []
        self.tick_start: Dict[int, float] = {}
        self.ticks: List[dict] = []
        self.trace = None
        self.checks: Dict[str, tuple] = {}

    def traced_ticks(self) -> List[dict]:
        """Ticks that ran wholly inside the traced slice."""
        if self.trace is None:
            return []
        return [x for x in self.ticks
                if x["t0"] >= self.trace_t0 and x["t1"] <= self.trace_t1]


def setup(ctx):
    """Weights, engine and warm-up; returns the engine and its pieces."""
    import jax

    from bench import weights
    from repro.serve.engine import ServeEngine

    cfgj, mix = ctx.cell.config, ctx.cell.traffic
    m = model_dims(cfgj)
    sv = cfgj["serve"]
    cfg = model_config(m)
    qp = weights.lm_serve_params(m, ctx.seed)
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


def traffic(mix: dict, m: dict, seconds: float, seed: int):
    if mix["kind"] == "open_loop":
        return gen.open_loop(mix, seconds, m["vocab_size"], seed)
    if mix["kind"] == "backlog":
        return gen.backlog(mix, m["vocab_size"], seed)
    raise ValueError(f"an LM cell cannot serve mix kind {mix['kind']!r}")


def window(ctx, eng, reqs, mix, sv, W, out, on_tick=None):
    """Serve ``reqs`` for a window of ``W`` seconds and drain; fills
    ``out`` with the per-request and per-tick records."""
    import jax

    trace_s = min(float(mix.get("trace_s", 6.0)), W / 2)
    trace_from = W - trace_s
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if ctx.trace else None
    tracing = False
    annotate = jax.profiler.TraceAnnotation
    compiles_before = ctx.compiles.count
    t_win = time.perf_counter()
    out.setup_s = t_win - ctx.t0
    ctx.log(f"set-up {out.setup_s:.3f} s, {ctx.compiles.count} compiles "
            f"({ctx.compiles.seconds:.1f} s)")

    inflight: Dict[int, dict] = {}
    done_recs: List[dict] = []
    nxt = 0
    deadline = W + float(mix.get("drain_s", 60.0))
    backlog = mix["kind"] == "backlog"
    # a backlog keeps as many requests waiting as there are slots, so a
    # slot that frees is refilled at the next tick
    pending_target = sv["n_slots"] if backlog else 0

    def submit(r, t_now):
        with annotate("submit"):
            rid = eng.submit(r.prompt, max_new_tokens=r.max_new,
                             budget_s=r.budget)
        rec = {"rid": rid, "t_sched": r.t_sched if not backlog else t_now,
               "prompt": r.prompt, "budget": r.budget,
               "max_new": r.max_new, "n": 0, "t_first": None,
               "t_last": None, "done": False, "in_window": t_now < W}
        inflight[rid] = rec

    while True:
        t = time.perf_counter() - t_win
        if on_tick is not None:
            on_tick(t)
        if ctx.trace and not tracing and trace_from <= t < W:
            jax.profiler.start_trace(trace_dir)
            tracing = True
            t = out.trace_t0 = time.perf_counter() - t_win
        if tracing and t >= W:
            jax.profiler.stop_trace()
            tracing, out.trace_t1 = False, t
            t = time.perf_counter() - t_win
        if t < W:
            if backlog:
                while eng.queued < pending_target:
                    submit(reqs[nxt % len(reqs)], t)
                    nxt += 1
            else:
                while nxt < len(reqs) and reqs[nxt].t_sched <= t:
                    submit(reqs[nxt], t)
                    nxt += 1
        elif not inflight or t >= deadline:
            break
        if eng.queued or eng._has_active():
            tick = eng._tick
            tok0 = eng.stats.tokens
            out.tick_start[tick] = t
            with annotate("step"):
                finished = eng.sched_tick()
            t_end = time.perf_counter() - t_win
            x = {"tick": tick, "t0": t, "t1": t_end,
                 "tokens": eng.stats.tokens - tok0, "rows": 0, "live": 0.0,
                 "prompt_tokens": 0, "prompt_pairs": 0.0, "gen_pairs": 0.0,
                 "fresh": 0}
            for rid, rec in inflight.items():
                n = len(eng.requests[rid].tokens)
                if n > rec["n"]:
                    fresh = rec["n"] == 0
                    P = len(rec["prompt"])
                    if fresh:
                        rec["t_first"] = t_end
                        x["fresh"] += 1
                        x["prompt_tokens"] += P
                        x["prompt_pairs"] += P * (P + 1) / 2
                    start = 1 if fresh else rec["n"]
                    # each token decoded this tick attends to the cache
                    # positions before it and itself
                    x["gen_pairs"] += sum(P + j for j in range(start, n))
                    if n > start or not fresh:
                        # rows that decoded this tick, and the cache
                        # positions each held at mid-block
                        x["rows"] += 1
                        x["live"] += P + start + sv["decode_block"] / 2
                    rec["t_last"], rec["n"] = t_end, n
            out.ticks.append(x)
            for rid in finished:
                rec = inflight.pop(rid, None)
                if rec is not None:
                    rec["done"] = True
                    rec["admitted_tick"] = eng.requests[rid].admitted_tick
                    done_recs.append(rec)
        elif not backlog and nxt < len(reqs):
            wait = reqs[nxt].t_sched - (time.perf_counter() - t_win)
            if wait > 0:
                with annotate("gen_sleep"):
                    time.sleep(min(wait, max(W - t, 0.0) + 1e-3))
    if tracing:             # the traced window ends before the dump
        out.trace_t1 = time.perf_counter() - t_win
        jax.profiler.stop_trace()
    out.drain_end = time.perf_counter() - t_win
    out.compiles_in_window = ctx.compiles.count - compiles_before
    ctx.log(f"compiles in window: {out.compiles_in_window}")
    for rec in inflight.values():
        rec["admitted_tick"] = eng.requests[rec["rid"]].admitted_tick
    out.requests = [r for r in done_recs + list(inflight.values())
                    if r["in_window"]]
    out.window_s = W
    out.deadline = deadline
    out.tokens_in_window = sum(x["tokens"] for x in out.ticks
                               if x["t1"] <= W)
    out.stats = eng.stats
    out.decode_block = sv["decode_block"]
    out.n_slots = sv["n_slots"]
    out.attempted = len(out.requests)
    out.failed = sum(1 for r in out.requests if not r["done"])
    out.memory_peak_bytes = memory_peak_bytes(ctx.devices)
    ctx.log(f"{out.attempted} requests in the window, {out.failed} "
            f"unfinished at the drain deadline; {out.tokens_in_window} "
            f"tokens in the window; peak {out.memory_peak_bytes} bytes")
    if ctx.trace:
        out.trace = reduce_trace(trace_dir, out.trace_t1 - out.trace_t0)
    return out



def run(ctx):
    eng, qp, m, sv = setup(ctx)
    mix = ctx.cell.traffic
    out = Run(ctx.peaks)
    out.m = m
    reqs = traffic(mix, m, ctx.seconds, ctx.seed)
    window(ctx, eng, reqs, mix, sv, ctx.seconds, out)
    # correctness: the served tokens against the plain reference, once
    # the pool is freed
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


def control_bits(wb, ab, control: dict):
    """The control's bits: each weight and activation width one step down
    (the config's ``check.control`` map: W8A8 -> W4A4, W4A4 -> W3A3)."""
    return ([int(control[str(b)]) for b in wb],
            [int(control[str(b)]) for b in ab])


def compare(ctx, m, qp, mix, requests, served, mean_wbits, max_len,
            with_control: bool = False) -> dict:
    """Run the reference over a seed-drawn sample of the finished
    requests (the longest among them); the readings the check uses, and
    with ``with_control`` the control's too."""
    from bench.reference import lm as ref
    limits = ctx.cell.config["check"]
    bits = expand_menu(mix["menu"], m["n_layers"])
    done = [r for r in requests if r["done"]]
    out = {"unfinished": len(requests) - len(done), "wrong_precision": 0,
           "short_or_invalid": 0, "widest_gap": 0.0, "tokens": 0,
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
        # per configuration: the program's widest gap, sum of gaps and
        # tokens, then the control's widest and sum (readings for the
        # limits; not compared)
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
    lim = float(mix["check"]["widest_gap"])     # the menu's rows set it
    return {"unfinished": (float(r["unfinished"]), 0.0),
            "wrong_precision": (float(r["wrong_precision"]), 0.0),
            "short_or_invalid": (float(r["short_or_invalid"]), 0.0),
            "widest_gap": (r["widest_gap"], lim)}
