"""Device idle time per traced scheduler tick: the median, over the
ticks of the traced slice, of the time inside the program's ``tick``
span in which no operation ran on the device, in milliseconds.  Each
``tick`` span is placed on the trace's clock through the benchmark's
``step`` span around the same call (``bench/spans.py``); an alignment
error over 1 ms refuses.  The idle time is split by the innermost
program span over it, and the split printed to stderr.  Device trace
(the idle time); the program's spans only attribute it."""
import statistics
import sys

from bench import spans


def read(run):
    rg = spans.ring(run)
    if rg is None or run.trace is None:
        return None
    err, per_tick, split = spans.tick_idle(run, rg)
    if err > spans.ALIGN_LIMIT_S:
        raise RuntimeError(f"program tick spans miss the traced step spans "
                           f"by {1e3 * err:.3f} ms (limit "
                           f"{1e3 * spans.ALIGN_LIMIT_S:g} ms)")
    if not per_tick:
        return None
    total = sum(split.values())
    parts = ", ".join(f"{k} {1e3 * v:.3f} ms ({100 * v / total:.1f}%)"
                      for k, v in sorted(split.items(), key=lambda kv: -kv[1]))
    print(f"[bench] tick_idle_ms: {len(per_tick)} ticks, alignment error "
          f"{1e3 * err:.4f} ms; idle inside ticks {1e3 * total:.3f} ms: "
          f"{parts}", file=sys.stderr, flush=True)
    return 1e3 * statistics.median(per_tick)
