"""Device time of the ``ssm_update`` Pallas kernel per decode step: its
events in the traced slice (every mixer's call) summed, over the decode
steps the slice ran (``jit__decode_scan`` calls x ``decode_block``),
milliseconds.  Nothing where the trace holds no such kernel (a program
without it)."""

KERNEL = "ssm_update"
PROGRAM = "jit__decode_scan"


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.module_times(PROGRAM)
    t = sum(v for k, v in run.trace.ops.items()
            if k == KERNEL or k.startswith(KERNEL + "."))
    if not calls or t <= 0:
        return None
    return 1e3 * t / (len(calls) * run.decode_block)
