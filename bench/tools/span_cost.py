"""Cost of the program's span recorder on this host.

Times ``RuntimeStats.span`` (a ring append and a profiler
``TraceAnnotation``) and ``RuntimeStats.mark`` with nothing inside, in
pairs nested as the engine nests them, first with the profiler off and
then with a profiler trace running, and prints one JSON line: ns per
span and per mark, each the median of ``--reps`` timings.

    python3 bench/tools/span_cost.py --n 100000
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def per_event_ns(fn, n: int, reps: int) -> float:
    out = []
    for _ in range(reps):
        t0 = time.perf_counter_ns()
        fn(n)
        out.append((time.perf_counter_ns() - t0) / n)
    return statistics.median(out)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.serve.accounting import RuntimeStats

    def spans(n):                       # n spans, as n/2 nested pairs
        st = RuntimeStats()
        for _ in range(n // 2):
            with st.span("decode"):
                with st.span("decode.sync"):
                    pass

    def marks(n):
        st = RuntimeStats()
        for _ in range(n):
            st.mark("trace.decode")

    res = {"device": jax.devices()[0].device_kind, "n": args.n}
    res["span_ns"] = per_event_ns(spans, args.n, args.reps)
    res["mark_ns"] = per_event_ns(marks, args.n, args.reps)
    d = tempfile.mkdtemp(prefix="span_cost_")
    jax.profiler.start_trace(d)
    try:
        res["span_ns_profiled"] = per_event_ns(spans, args.n, args.reps)
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    print(json.dumps(res), flush=True)


if __name__ == "__main__":
    main()
