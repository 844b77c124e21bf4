"""Mean number of occupied decode slots per scheduler tick
(``RuntimeStats.active_depth``) over the ticks that ran inside the
window.  Program counter."""


def read(run):
    if run.kind != "lm":
        return None
    depth = run.stats.active_depth
    v = [depth[x["tick"]] for x in run.ticks
         if x["t1"] <= run.window_s and x["tick"] < len(depth)]
    return sum(v) / len(v) if v else None
