"""Device time per call of the row-prefill program (jit module
``jit__prefill_row``), milliseconds, from the profiler trace."""

PROGRAM = "jit__prefill_row"


def read(run):
    if run.trace is None:
        return None
    t = run.trace.module_times(PROGRAM)
    return 1e3 * sum(t) / len(t) if t else None
