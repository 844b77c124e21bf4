"""Share of the hybrid's decode program's roofline, in %: the least time
the chip needs for a traced decode call's work (``bench/work_hybrid.py``:
weights read once, each live row's SSM state and conv window read and
written, the live KV read once; 2 ops a weight a row at the int8 peak,
attention and the state update at the bf16 peak), over the mean device
time of ``jit__decode_scan``.  Live rows per tick from the program's
``state_rows`` counter.  Nothing without it or the program's calls."""
from bench import work_hybrid

PROGRAM = "jit__decode_scan"


def read(run):
    rows = getattr(getattr(run, "stats", None), "state_rows", None)
    if run.trace is None or run.peaks is None or rows is None:
        return None
    dev = run.trace.module_times(PROGRAM)
    ticks = run.traced_ticks()
    if not dev or not ticks:
        return None
    pk = run.peaks
    least = []
    for x in ticks:
        n = rows.get(x["tick"], 0)
        if n == 0:
            continue
        i8, fl, nbytes = work_hybrid.decode_step_work(run.m, n,
                                                      int(x["live"]))
        least.append(run.decode_block * max(
            i8 / pk["int8_ops"] + fl / pk["bf16_flops"],
            nbytes / pk["hbm_bytes_per_s"]))
    if not least:
        return None
    return 100.0 * (sum(least) / len(least)) / (sum(dev) / len(dev))
