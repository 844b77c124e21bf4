"""Host time per scheduler tick: the median, over the ticks that ended
before the traced slice began, of the program's ``tick`` span less its
``admit.sync`` and ``decode.sync`` spans (the device->host reads), in
milliseconds.  Program spans, host clock."""
import statistics

from bench import spans


def read(run):
    rg = spans.ring(run)
    if rg is None:
        return None
    v = []
    for x in run.ticks:
        ev = rg.ticks.get(x["tick"])
        if x["t1"] > spans.cut(run) or ev is None:
            continue
        sync = sum(e.t1_ns - e.t0_ns for e in rg.in_tick[x["tick"]]
                   if e.name.endswith(".sync"))
        v.append((ev.t1_ns - ev.t0_ns - sync) * 1e-6)
    return statistics.median(v) if v else None
