"""Share of the ``ssm_update`` kernel's roofline, in %: the bytes its work
needs (each live slot's float32 state read and written once per mixer
and step, plus the step's x, dt, B, C and y; ``bench/work_hybrid.py``)
over its device time in the traced slice, against the HBM's 819 GB/s.
The kernel is memory-bound (about 6 float ops per 8 state bytes moved).
Live slots per tick come from the program's counter ``state_rows``, so
empty slots count as no useful work.  Nothing without that counter or
the kernel in the trace."""
from bench import work_hybrid

KERNEL = "ssm_update"


def read(run):
    rows = getattr(getattr(run, "stats", None), "state_rows", None)
    if run.trace is None or run.peaks is None or rows is None:
        return None
    t = sum(v for k, v in run.trace.ops.items()
            if k == KERNEL or k.startswith(KERNEL + "."))
    ticks = run.traced_ticks()
    if t <= 0 or not ticks:
        return None
    nbytes = sum(run.decode_block
                 * work_hybrid.ssm_update_bytes(run.m, rows.get(x["tick"], 0))
                 for x in ticks)
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / t
