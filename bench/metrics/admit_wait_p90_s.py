"""90th percentile of the wait for admission inside the program: from
the end of a request's ``request.submit`` span to the start of its
``admit.request`` span, over the admitted requests due before the traced
slice began.  Program spans, host clock."""
from bench import spans
from bench.harness import percentile


def read(run):
    rg = spans.ring(run)
    if rg is None:
        return None
    sub, adm = rg.by_rid("request.submit"), rg.by_rid("admit.request")
    v = [spans.seconds(sub[r["rid"]].t1_ns, adm[r["rid"]].t0_ns)
         for r in spans.cohort(run) if r["rid"] in sub and r["rid"] in adm]
    return percentile(v, 90) if v else None
