"""CNN serving cells: ``CNNServeEngine.serve`` in a closed loop
of back-to-back batches.

Set-up makes the float weights and a pool of images on the device from
the seed (so the window measures the server, not the host-to-device
upload), builds the engine with the mix's HAWQ-V3 menu through
``policy.cnn_budget_controller`` (the engine quantizes the weights
itself), and serves one batch to warm the forward program.  Each image's
budget is its configuration's predicted cost times ``budget_margin``, so
a batch holds every configuration in equal shares.

The window serves batches until ``--seconds`` have passed; the images of
batches that returned inside it count.  A seed-drawn set of batches
keeps its logits, which are compared after the window with the plain
reference at each image's configuration: the number compared is the
widest relative logit error, max|served - reference| / max|reference|,
over those images.
"""
from __future__ import annotations

import gc
import tempfile
import time
from typing import Dict, List

import numpy as np

from bench import gen
from bench.harness import expand_menu, memory_peak_bytes, reduce_trace

KEEP_STRIDE, KEEP_MAX = 16, 32


def program_layers(table: List[dict]):
    from repro.apsim.workloads import Layer
    out = []
    for l in table:
        k = l["kind"]
        if k == "conv":
            out.append(Layer(l["name"], k, l["hin"], l["hin"], l["cin"],
                             l["hk"], l["hk"], l["cout"], stride=l["stride"],
                             pad=l["pad"], relu=l["relu"]))
        elif k in ("maxpool", "avgpool"):
            out.append(Layer(l["name"], k, l["hin"], l["hin"], l["cin"],
                             l["hk"], l["hk"], l["cin"], stride=l["stride"],
                             pad=l["pad"], window=l["hk"] * l["hk"]))
        elif k == "fc":
            out.append(Layer(l["name"], k, cin=l["cin"], cout=l["cout"],
                             relu=l["relu"]))
        elif k == "add":
            out.append(Layer(l["name"], k, hin=l["hin"], win=l["hin"],
                             cin=l["cin"]))
    return out


class Run:
    def __init__(self, peaks, layers):
        self.kind = "cnn"
        self.peaks = peaks
        self.layers = layers
        self.trace = None
        self.checks: Dict[str, tuple] = {}


def run(ctx):
    import jax

    from bench import weights
    from repro.core import policy as pol
    from repro.serve.cnn import CNNServeEngine

    cfgj, mix = ctx.cell.config, ctx.cell.traffic
    table = cfgj["layers"]
    out = Run(ctx.peaks, table)
    layers = program_layers(table)
    n_gemm = sum(1 for l in table if l["kind"] in ("conv", "fc"))
    bits = expand_menu(mix["menu"], n_gemm)
    B = int(mix["batch"])
    params = weights.cnn_params(table, ctx.seed)
    ctrl = pol.cnn_budget_controller(
        cfgj["name"], layers=layers,
        configs={k: pol.per_layer(w, a, name=k) for k, (w, a) in bits.items()})
    eng = CNNServeEngine(params, layers, controller=ctrl,
                         max_batch=cfgj["serve"]["max_batch"])
    del params
    H = cfgj["image"]
    n_pool = int(mix["pool_batches"])
    pool = jax.jit(lambda k: jax.random.normal(
        k, (n_pool, B, H, H, cfgj["channels"]), jax.numpy.float32))(
            weights.key_from_seed(ctx.seed, 2))
    batches = [pool[i] for i in range(n_pool)]
    names = gen.image_budgets(sorted(bits), B, n_pool, ctx.seed)
    margin = float(mix["budget_margin"])
    budgets = [[ctrl.predicted_latency_s[k] * margin for k in row]
               for row in names]
    jax.block_until_ready(batches)

    # ---- warm-up: the forward program and the per-batch host path
    eng.serve(batches[0], budgets[0])
    jax.block_until_ready(eng.qparams)

    W = ctx.seconds
    trace_s = min(float(mix.get("trace_s", 4.0)), W / 2)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if ctx.trace else None
    tracing = False
    annotate = jax.profiler.TraceAnnotation
    # logits kept for the check: every KEEP_STRIDE-th batch from a
    # seed-drawn offset, at most KEEP_MAX of them
    offset = gen.pick(KEEP_STRIDE, 1, ctx.seed)[0]
    kept = []
    compiles_before = ctx.compiles.count
    t_win = time.perf_counter()
    out.setup_s = t_win - ctx.t0
    ctx.log(f"set-up {out.setup_s:.3f} s, {ctx.compiles.count} compiles "
            f"({ctx.compiles.seconds:.1f} s)")
    done_images = traced_images = i = 0
    ends = []
    while True:
        t = time.perf_counter() - t_win
        if t >= W:
            break
        if ctx.trace and not tracing and t >= W - trace_s:
            jax.profiler.start_trace(trace_dir)
            tracing, out.trace_t0 = True, time.perf_counter() - t_win
        j = i % n_pool
        with annotate("serve"):
            logits, stats = eng.serve(batches[j], budgets[j])
        t_end = time.perf_counter() - t_win
        ends.append(t_end)
        if t_end <= W:
            done_images += B
            if tracing:
                traced_images += B
        if i % KEEP_STRIDE == offset and len(kept) < KEEP_MAX:
            kept.append((j, logits, [s.wbits for s in stats],
                         [s.abits for s in stats]))
        i += 1
    if tracing:             # the traced window ends before the dump
        out.trace_t1 = time.perf_counter() - t_win
        jax.profiler.stop_trace()
    out.compiles_in_window = ctx.compiles.count - compiles_before
    ctx.log(f"compiles in window: {out.compiles_in_window}")
    out.window_s = W
    out.images_in_window = done_images
    out.images_traced = traced_images
    out.attempted = i * B
    out.failed = 0
    out.memory_peak_bytes = memory_peak_bytes(ctx.devices)
    gaps = np.diff([0.0] + ends)
    half = len(gaps) // 2
    ctx.log(f"{i} batches ({done_images} images in the window); peak "
            f"{out.memory_peak_bytes} bytes; median batch "
            f"{np.median(gaps[:half]) * 1e3:.1f} ms in the first half of "
            f"the window, {np.median(gaps[half:]) * 1e3:.1f} ms in the second")
    if ctx.trace:
        out.trace = reduce_trace(trace_dir, out.trace_t1 - out.trace_t0)
    del eng
    gc.collect()
    out.compare_args = (cfgj, bits, batches, names, kept)
    out.checks = check(ctx, *out.compare_args)
    out.correct = all(v <= lim for v, lim in out.checks.values())
    return out


def compare(ctx, cfgj, bits, batches, names, kept,
            with_control: bool = False) -> dict:
    """The reference over a seed-drawn sample of the kept batches, every
    image at its configuration; with ``with_control`` also the control
    (each weight and activation width one step down, ``check.control``)
    against it."""
    from bench import weights
    from bench.reference import cnn as ref
    limits = cfgj["check"]
    out = {"batches": 0, "wrong_precision": 0, "widest_rel_err": 0.0,
           "control_widest_rel_err": 0.0 if with_control else None,
           "by_config": {}}
    if not kept:
        out["widest_rel_err"] = 1e9
        return out
    params = ref.quantize(weights.cnn_params(cfgj["layers"], ctx.seed))
    ctl = limits["control"]

    def rel(a, r):
        """Per image: max|a - r| / max|r|."""
        return np.max(np.abs(a - r), axis=1) / np.max(np.abs(r), axis=1)

    def widest(i, v, names_j):
        # per configuration: the program's widest, then the control's
        # (readings for the limits; not compared)
        for n, e in zip(names_j, v):
            cell = out["by_config"].setdefault(n, [0.0, 0.0])
            cell[i] = max(cell[i], float(e))

    for k in gen.pick(len(kept), min(limits["batches"], len(kept)),
                      ctx.seed + 1):
        j, logits, wb_served, ab_served = kept[k]
        wb = np.asarray([bits[n][0] for n in names[j]])
        ab = np.asarray([bits[n][1] for n in names[j]])
        out["wrong_precision"] += int(np.sum(
            np.any(np.asarray(wb_served) != wb, axis=1)
            | np.any(np.asarray(ab_served) != ab, axis=1)))
        r = np.asarray(ref.logits(params, cfgj["layers"], batches[j], wb, ab))
        got = np.asarray(logits, np.float32)
        out["batches"] += 1
        if got.shape != r.shape or not np.isfinite(got).all():
            out["widest_rel_err"] = 1e9
            continue
        e = rel(got, r)
        out["widest_rel_err"] = max(out["widest_rel_err"], float(e.max()))
        widest(0, e, names[j])
        if with_control:
            down = np.vectorize(lambda b: int(ctl[str(int(b))]))
            c = np.asarray(ref.logits(params, cfgj["layers"], batches[j],
                                      down(wb), down(ab)))
            e = rel(c, r)
            out["control_widest_rel_err"] = max(
                out["control_widest_rel_err"], float(e.max()))
            widest(1, e, names[j])
    ctx.log(f"reference: {out['batches']} batches, "
            f"{out['batches'] * len(names[0])} images")
    return out


def check(ctx, cfgj, bits, batches, names, kept) -> dict:
    r = compare(ctx, cfgj, bits, batches, names, kept)
    return {"wrong_precision": (float(r["wrong_precision"]), 0.0),
            "widest_rel_err": (r["widest_rel_err"],
                               float(cfgj["check"]["widest_rel_err"]))}
