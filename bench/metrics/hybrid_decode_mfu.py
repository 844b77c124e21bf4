"""The hybrid's whole decode path's share of the chip's int8 peak, as
``decode_mfu`` reads it for a dense decoder: the useful decode work of
the ticks inside the traced slice (``bench/work_hybrid.py``: 2 ops per
weight per decoded token, attention on the real lengths, the state
update) over the span of those ticks times 393 TOP/s, in %.  Nothing for
a run that is not of the hybrid (no ``layer_types``)."""
from bench import work_hybrid


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    if "layer_types" not in getattr(run, "m", {}):
        return None
    ticks = run.traced_ticks()
    if not ticks:
        return None
    ops = sum(work_hybrid.useful_ops(run.m, x["tokens"] - x["fresh"],
                                     x["gen_pairs"]) for x in ticks)
    span = ticks[-1]["t1"] - ticks[0]["t0"]
    return 100.0 * ops / (span * run.peaks["int8_ops"])
