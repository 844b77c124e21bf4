"""Sweep the offered rate of an open-loop LM cell, in one process, to find
its knee: the highest rate the server sustains without a growing backlog.

    python3 bench/tools/knee.py --workload lm-int8-poisson \\
        --rates 2 3 4 5 6 --seconds 20 --seed 5

Set-up (weights, engine, warm-up) runs once; then each rate gets a window
of ``--seconds`` with the cell's mix at that rate, drained before the
next.  One JSON line per rate: requests, the backlog left at the window's
end (queued and in slots), TTFT p50/p95, TPOT p95, tokens per second.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness
    from bench.kinds import lm

    cell = harness.load_cell(args.workload)
    devs = harness.devices(cell.chips, require_tpu=True)
    harness.enable_compile_cache()
    ctx = harness.Context(cell, args.seed, args.seconds, False, devs, None,
                          T0, harness.CompileCounter())
    eng, _, m, sv = lm.setup(ctx)
    for rate in args.rates:
        mix = dict(cell.traffic, rate_per_s=rate)
        out = lm.Run(None)
        out.m = m
        reqs = lm.traffic(mix, m, args.seconds, args.seed)
        end_backlog = {}

        def at_end(t, eng=eng, d=end_backlog):
            if not d and t >= args.seconds:
                d["queued"] = eng.queued
                d["active"] = int(eng.slots.active.sum())

        lm.window(ctx, eng, reqs, mix, sv, args.seconds, out, on_tick=at_end)
        ttft = [r["t_first"] - r["t_sched"] for r in out.requests
                if r["t_first"] is not None]
        tpot = [1e3 * (r["t_last"] - r["t_first"]) / (r["n"] - 1)
                for r in out.requests if r["done"] and r["n"] > 1]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(out.requests),
            "unfinished": out.failed, **end_backlog,
            "ttft_p50_s": harness.percentile(ttft, 50),
            "ttft_p95_s": harness.percentile(ttft, 95),
            "tpot_p95_ms": harness.percentile(tpot, 95),
            "tokens_per_s": out.tokens_in_window / args.seconds,
            "drain_s": out.drain_end - args.seconds,
            "compiles_in_window": out.compiles_in_window}), flush=True)


if __name__ == "__main__":
    main()
