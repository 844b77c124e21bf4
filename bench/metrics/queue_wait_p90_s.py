"""90th percentile of the wait for admission: from a request's scheduled
arrival to the start of the scheduler tick that admitted it
(``admitted_tick`` of its record), over the admitted requests due before
the traced slice began.  Host clock."""
from bench.harness import percentile


def read(run):
    if run.kind != "lm":
        return None
    cut = getattr(run, "trace_t0", run.window_s)
    v = [run.tick_start[r["admitted_tick"]] - r["t_sched"]
         for r in run.requests
         if r["t_sched"] < cut and r.get("admitted_tick") in run.tick_start]
    return percentile(v, 90) if v else None
