"""Share of the prefilled positions that are padding, in %:
100 x (1 - real prompt tokens prefilled / positions computed), from the
program's per-request counters (``prefill_by_rid``; a prefix-cache hit's
tokens are not prefilled), over the requests due in the first
``SHARE`` of the window.  Each of those is submitted and prefilled
whatever the tick lengths, so the reading repeats for a seed; a request
due near the window's end is sent only if a tick boundary falls between
its arrival and the end.  Program counter."""

SHARE = 0.9                 # leaves 5.1 s of a 51 s window: over 2 ticks


def read(run):
    per = getattr(getattr(run, "stats", None), "prefill_by_rid", None)
    if run.kind != "lm" or per is None:
        return None
    rows = [per.get(r["rid"], (0, 0)) for r in run.requests
            if r["t_sched"] < SHARE * run.window_s]
    real, pos = sum(t for t, _ in rows), sum(p for _, p in rows)
    return 100.0 * (1.0 - real / pos) if pos else None
