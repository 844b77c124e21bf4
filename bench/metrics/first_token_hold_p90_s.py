"""90th percentile of how long a request's first token waits on the
host before its tick returns it: from the end of the request's
``admit.sync`` span (the token is on the host) to the end of the
``tick`` span it ran in, over the admitted requests due before the
traced slice began.  Program spans, host clock."""
from bench import spans
from bench.harness import percentile


def read(run):
    rg = spans.ring(run)
    if rg is None:
        return None
    adm = rg.by_rid("admit.request")
    v = []
    for r in spans.cohort(run):
        a = adm.get(r["rid"])
        sync = rg.child(a, "admit.sync") if a is not None else None
        if sync is not None and sync.tick in rg.ticks:
            v.append(spans.seconds(sync.t1_ns, rg.ticks[sync.tick].t1_ns))
    return percentile(v, 90) if v else None
