"""90th percentile of time to first token over every request that arrived
in the window: from its scheduled arrival to the return of the scheduler
tick that delivered its first token (no token reaches a client earlier).
A request with no token by the drain deadline counts at the deadline.
The 90th, not the 95th: at the cells' rate a 51 s window holds about
100 requests, and p90 is the highest percentile with ten beyond it.
Host clock."""
from bench.harness import percentile


def read(run):
    if run.kind != "lm" or not run.requests:
        return None
    v = [(r["t_first"] if r["t_first"] is not None else run.deadline)
         - r["t_sched"] for r in run.requests]
    return percentile(v, 90)
